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


def lone_ahead(cycles=8):
    """One replica decoding alone and a step ahead (PR 43): its programs
    run back to back, each dispatched while the one before it runs, and a
    program's fetch opens after the NEXT program's dispatch closed."""
    from kvbench.tests.test_launches import Slice

    s = Slice()
    for i in range(cycles):
        t = 6.0 * i
        first = i == 0
        s.launch("pod-0", DECODE, (0.0, 0.5) if first else (t - 5.4, t - 4.9),
                 (t + (0.6 if first else 0.0), t + 6.0),
                 fetch=(t + 1.1, t + 6.3), step=i + 1)
        s.host[-2].stats["ahead"] = int(not first)
    # As the host plane holds them: a fetch behind the later dispatch.
    s.host.sort(key=lambda e: e.start)
    return s


def test_a_fetch_that_follows_a_later_dispatch_pairs_by_launch():
    """The pairing reads no more ``unpaired`` and no more ``clock_fault``
    from a lone replica a step ahead than from the synchronous order."""
    names = [e.name for e in lone_ahead().host]
    assert names[:4] == ["step.dispatch", "step.dispatch", "step.fetch",
                         "step.dispatch"]
    found = _launches.of(lone_ahead().run())
    sync = _launches.of(lone().run())
    assert (found.programs, found.unpaired, found.clock_faults,
            found.offset) == (sync.programs, sync.unpaired,
                              sync.clock_faults, sync.offset) == (8, 0, 0, 0)
    assert owners(found) == 8 * [("pod-0", DECODE, True)]
    assert [p.fetch.stats["launch"] for p in found.pairs] == [
        p.dispatch.stats["launch"] for p in found.pairs]
    # All but the first found the chip busy: no gap is a lone round trip.
    assert [p.waited_from == p.dispatch.start for p in found.pairs] == [
        True] + 7 * [False]
    assert found.lone_gaps_ms() == []


def test_launched_ahead_share():
    from kvbench.harness import names
    from kvbench.harness.loop import Run

    reader = names.metric("launched_ahead_share")
    assert reader.compute(Run(seconds=1.0)) is None            # untraced
    assert reader.compute(lone_ahead().run()) == pytest.approx(87.5)
    # A program older than ``ahead`` (the parent): counts are 0, not None.
    assert reader.compute(lone().run()) == 0.0
    assert reader.compute(lone(numbered=False).run()) == 0.0
    # A prefill chunk is no decode program.
    assert reader.compute(chunks_ahead().run()) == 0.0
    s = chunks_ahead()
    s.host[-2].stats["ahead"] = 1
    assert reader.compute(s.run()) == 100.0


def test_prefill_per_head_share():
    """Of the slice's chunks (dispatches that name a ``prefill_pos``), those
    whose ``expanded_keys`` is above 0; and which cells report it."""
    from kvbench.harness import names
    from kvbench.harness.loop import Run

    reader = names.metric("prefill_per_head_share")
    assert reader.compute(Run(seconds=1.0)) is None            # untraced
    assert reader.compute(lone().run()) is None                # no chunk
    # A program older than ``expanded_keys`` (the parent): 0, not None.
    assert reader.compute(chunks_ahead().run()) == 0.0
    s = chunks_ahead()
    chunks = [e for e in s.host if e.name == "step.dispatch"
              and "prefill_pos" in e.stats]
    assert len(chunks) >= 2
    for e in chunks:
        e.stats["expanded_keys"] = 0        # the absorbed kernel ran
    assert reader.compute(s.run()) == 0.0
    chunks[0].stats["expanded_keys"] = 25_600
    assert reader.compute(s.run()) == pytest.approx(100.0 / len(chunks))
    bench = names.benchmark()
    reporting = {w["name"] for w in bench["workloads"]
                 if "prefill_per_head_share" in {
                     m["name"] for m in names.cell_metrics(
                         bench, w["name"], True)}}
    assert reporting == {"deepseek-v3.2-exp-ep16-l5.doc-reask-32k",
                         "gigachat3.5-ep16-l5.sessions-32k"}


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
