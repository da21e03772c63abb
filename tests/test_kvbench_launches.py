"""The benchmark's pairing of device programs with the phases that launched
and awaited them (``kvbench/metrics/_launches.py``), on built slices, and the
five readers of PR 38 on the same (their cases live beside the harness's own
tests and are collected here too)."""

import pytest

from kvbench.metrics import _launches
from kvbench.tests.test_launches import (  # noqa: F401 (collected here too)
    DECODE,
    PREFILL,
    chunks_ahead,
    cut_at_both_ends,
    lone,
    pair_of_replicas,
    test_control_plane_readers,
    test_pairing_readers,
    test_pairing_readers_under_a_shifted_clock_and_untraced,
)

BOTH = pytest.mark.parametrize("numbered", [True, False],
                               ids=["launch", "parent"])


def owners(found):
    return [(p.pod, _launches.program_of(p.program.name),
             p.fetch is not None) for p in found.pairs]


@BOTH
def test_a_lone_replica(numbered):
    found = _launches.of(lone(numbered=numbered).run())
    assert found.numbered is numbered
    assert (found.programs, found.unpaired, found.clock_faults,
            found.offset) == (8, 0, 0, 0)
    assert owners(found) == 8 * [("pod-0", DECODE, True)]
    # Nothing ran before them: each waited from its own dispatch.
    assert all(p.waited_from == p.dispatch.start for p in found.pairs)
    assert found.lone_gaps_ms() == pytest.approx(7 * [3.0])
    assert "(n=7)" in found.summary()


@BOTH
def test_two_replicas_interleaved(numbered):
    run = pair_of_replicas(numbered=numbered).run()
    found = _launches.of(run)
    assert _launches.of(run) is found                  # paired once a run
    assert (found.programs, found.unpaired, found.clock_faults) == (12, 0, 0)
    assert [p.pod for p in found.pairs] == 6 * ["pod-0", "pod-1"]
    # Each program but the first waited for the other replica's to end.
    assert [p.waited_from == p.dispatch.start for p in found.pairs] == [
        True] + 11 * [False]
    for a, b in zip(found.pairs, found.pairs[1:]):
        assert b.waited_from == a.program.end


@BOTH
def test_chunks_dispatched_ahead_are_placed_by_order(numbered):
    found = _launches.of(chunks_ahead(numbered=numbered).run())
    assert (found.programs, found.unpaired, found.clock_faults) == (8, 0, 0)
    assert owners(found) == 6 * [("pod-0", PREFILL, False)] + [
        ("pod-0", PREFILL, True), ("pod-0", DECODE, True)]
    # The small program between them is the chip's, not a step program's:
    # it ended before the decode step's dispatch opened.
    assert found.pairs[-1].waited_from == found.pairs[-1].dispatch.start
    assert [p.dispatch.start for p in found.pairs[:7]] == [
        i * 1e6 for i in range(7)]


@BOTH
def test_a_slice_cut_at_both_ends(numbered):
    found = _launches.of(cut_at_both_ends(numbered=numbered).run())
    assert (found.programs, found.offset, found.clock_faults) == (7, 2, 0)
    assert found.unpaired == 2                 # launched before the slice
    assert owners(found) == 4 * [("pod-0", DECODE, True)] + [
        ("pod-1", DECODE, False)]              # its fetch was cut


def test_a_missing_dispatch_leaves_a_numbered_hole():
    """A dispatch the host plane lost: by ``launch`` the programs after it
    keep their owners; by order alone they would each take the next one's."""
    s = pair_of_replicas()
    lost = [e for e in s.host if e.stats.get("launch") == 5]
    s.host = [e for e in s.host if e.stats.get("launch") != 5]
    found = _launches.of(s.run())
    assert len(lost) == 2 and (found.unpaired, found.clock_faults) == (1, 0)
    assert [p.pod for p in found.pairs] == [
        "pod-0", "pod-1", "pod-0", "pod-1"] + ["pod-1"] + 3 * [
        "pod-0", "pod-1"]


def test_names_that_differ_are_not_placed():
    s = lone(cycles=4)
    s.host[2].stats["program"] = PREFILL       # the second dispatch
    found = _launches.of(s.run())
    assert (len(found.pairs), found.unpaired, found.clock_faults) == (3, 1, 0)


def test_a_host_clock_6_ms_off_is_counted_and_keeps_no_pair():
    found = _launches.of(lone(host_shift=6.0).run())
    assert (found.pairs, found.programs, found.unpaired) == ([], 8, 0)
    assert found.clock_faults == 8 == len(found.placed) == len(found.timed())
    # The program starts 0.6 ms after a dispatch that reads 6 ms late.
    assert found.worst_fault_ms == pytest.approx(5.4)
    assert "clock_fault 8 (worst 5.400 ms)" in found.summary()
    # One late fetch alone is that pair's fault, not an offset's.
    s = lone(cycles=24)
    s.host[3].start -= 1.0e6                   # the second fetch: ends early
    found = _launches.of(s.run())
    assert (len(found.pairs), found.clock_faults, found.offset) == (23, 1, 0)
    assert found.worst_fault_ms == pytest.approx(0.7)
    assert found.timed() == found.pairs        # the exception is kept out


def test_nothing_to_pair():
    from kvbench.harness.loop import Run
    from kvbench.tests.test_launches import Slice

    assert _launches.of(Run(seconds=1.0)) is None      # untraced
    s = Slice()
    s.program(DECODE, 0.0, 6.0)        # no dispatch names a step program
    found = _launches.of(s.run())
    assert (found.pairs, found.programs, found.unpaired) == ([], 0, 0)
    s.dispatch("pod-0", PREFILL, 7.0, 7.5)     # one that names another
    found = _launches.of(s.run())
    assert (found.pairs, found.programs, found.unpaired) == ([], 0, 0)
    s.dispatch("pod-0", DECODE, 8.0, 8.5)      # its program: cut
    found = _launches.of(s.run())
    assert (found.pairs, found.programs, found.unpaired) == ([], 1, 1)
    assert _launches.program_of("jit_forward_decode_pallas(12)") == DECODE
    assert _launches.program_of("jit_forward_decode_pallas") == DECODE
