"""``metrics/_launches.py`` and the five readers of PR 38 on built slices:
step programs on a modules line, the engine's ``step.dispatch`` and
``step.fetch`` beside them. Times are written in ms and handed on in ns.
``tests/test_kvbench_launches.py`` (tier-1) imports the slices and the reader
cases from here and adds the pairing's own cases."""

import pytest

from kvbench.harness import names
from kvbench.harness.loop import Run
from kvbench.tests.test_reduce import device, ev, host
from kvbench.trace import reduce as R

MS = 1e6
PREFILL, DECODE = "forward_prefill_pallas", "forward_decode_pallas"


class Slice:
    """A traced slice under construction. ``launch()`` adds one program
    with its dispatch and, given ``fetch``, the fetch that read it."""

    def __init__(self, numbered=True, host_shift=0.0):
        self.numbered, self.shift = numbered, host_shift
        self.modules, self.host, self.n = [], [], 0

    def program(self, name, start, end):
        """A program on the device alone (its dispatch is not in the slice,
        or it is no step program)."""
        self.modules.append(ev(f"jit_{name}({len(self.modules)})",
                               start * MS, (end - start) * MS))

    def dispatch(self, pod, name, start, end, fetch=None, step=1):
        """A dispatch (and its fetch) alone: its program is not in the
        slice."""
        self.n += 1
        stats = dict(pod=pod, step=step, rows=1)
        if name == PREFILL:
            stats["prefill_pos"] = 0
        if self.numbered:
            stats.update(program=name, launch=self.n)
        self.host.append(ev("step.dispatch", (start + self.shift) * MS,
                            (end - start) * MS, **stats))
        if fetch is not None:
            stats = dict(pod=pod, step=step)
            if self.numbered:
                stats["launch"] = self.n
            self.host.append(ev("step.fetch", (fetch[0] + self.shift) * MS,
                                (fetch[1] - fetch[0]) * MS, **stats))

    def launch(self, pod, name, dispatch, program, fetch=None, step=1):
        self.dispatch(pod, name, *dispatch, fetch=fetch, step=step)
        self.program(name, *program)

    def run(self, *more):
        from kvbench.run import spans_of
        from llmd_kv_cache_tpu.telemetry.tracing import PHASE_NAMES

        ops = [ev(f"fusion.{i}", m.start, m.dur)
               for i, m in enumerate(self.modules)]
        run = Run(seconds=1.0)
        run.trace = R.reduce(
            [device(0, ops, self.modules), host(*self.host, *more)], 1,
            spans_of(PHASE_NAMES))
        return run


def lone(cycles=8, **kw):
    """One replica decoding alone: a program 9 ms after the last, 0.6 ms
    after its dispatch opened, read 0.3 ms after it ended."""
    s = Slice(**kw)
    for i in range(cycles):
        t = 9.0 * i
        s.launch("pod-0", DECODE, (t, t + 0.5), (t + 0.6, t + 6.6),
                 fetch=(t + 0.5, t + 6.9), step=i + 1)
    return s


def pair_of_replicas(cycles=6, **kw):
    """Two replicas that keep the chip busy: each dispatches while the
    other's program runs, so its own starts when that one ends."""
    s = Slice(**kw)
    for i in range(2 * cycles):
        t = 6.0 * i                   # a program every 6 ms, back to back
        pod = f"pod-{i % 2}"
        # Dispatched 5 ms before its turn, read 0.2 ms after it ended.
        first = i == 0
        s.launch(pod, DECODE, (t - 5.0, t - 4.5),
                 (t + (0.6 if first else 0.0), t + 6.0),
                 fetch=(t - 4.5, t + 6.2), step=i // 2 + 1)
    return s


def chunks_ahead(**kw):
    """A replica with nothing to decode sends a prompt's seven chunks at
    once; only the last one's token is read. Then it decodes."""
    s = Slice(**kw)
    for i in range(7):
        s.launch("pod-0", PREFILL, (1.0 * i, 1.0 * i + 0.8),
                 (0.9 + 20.0 * i, 0.9 + 20.0 * (i + 1)),
                 fetch=(6.8, 141.2) if i == 6 else None, step=1)
    s.program("copy_state_slot", 141.3, 141.4)        # no step program
    s.launch("pod-0", DECODE, (142.0, 142.5), (142.7, 148.7),
             fetch=(142.5, 149.0), step=2)
    return s


def cut_at_both_ends(**kw):
    """Two programs launched before the slice began (the first running as
    it does), and at its end a fetch and two programs it does not hold."""
    s = Slice(**kw)
    s.program(DECODE, 0.0, 2.0)
    s.program(DECODE, 2.0, 8.0)
    for i in range(4):
        t = 10.0 + 9.0 * i
        s.launch("pod-0", DECODE, (t, t + 0.5), (t + 0.6, t + 6.6),
                 fetch=(t + 0.5, t + 6.9), step=i + 1)
    s.launch("pod-1", DECODE, (46.0, 46.5), (46.7, 52.7), step=9)
    s.dispatch("pod-0", DECODE, 47.0, 47.5, step=5)
    s.dispatch("pod-1", PREFILL, 52.9, 53.4, step=10)
    return s


READINGS = [
    # slice, launch_lag, readback_lag, other_pod_share
    ("lone", lone, 0.6, 0.3, 0.0),
    # Queued: no idle time in front (the first found the chip idle); of a
    # fetch of 10.7 ms, 4.5 + 0.2 are the other replica's programs... and
    # the replica's own 6.0.
    ("pair", pair_of_replicas, 0.0, 0.2, None),
    ("chunks", chunks_ahead, 0.0, 0.3, 0.0),
    ("cut", cut_at_both_ends, 0.6, 0.3, 0.0),
]


@pytest.mark.parametrize("numbered", [True, False], ids=["launch", "parent"])
@pytest.mark.parametrize("name,build,lag,readback,share", READINGS,
                         ids=[r[0] for r in READINGS])
def test_pairing_readers(name, build, lag, readback, share, numbered):
    run = build(numbered=numbered).run()
    got = {n: names.metric(n).compute(run) for n in (
        "launch_lag_ms_p50", "readback_lag_ms_p50", "fetch_other_pod_share")}
    assert got["launch_lag_ms_p50"] == pytest.approx(lag, abs=1e-6)
    assert got["readback_lag_ms_p50"] == pytest.approx(readback, abs=1e-6)
    if share is None:
        # Each fetch but the first two spans the other replica's program
        # whole (6.0) and the end of the one before (here 4.5 of it).
        assert 40.0 < got["fetch_other_pod_share"] < 60.0
    else:
        assert got["fetch_other_pod_share"] == pytest.approx(share)


def test_pairing_readers_under_a_shifted_clock_and_untraced():
    """Where every program lies outside its phases the clock is what is
    off: the readers read all of them, shifted as they are (a lag below
    zero says so), and not the none that fit."""
    trio = ("launch_lag_ms_p50", "readback_lag_ms_p50",
            "fetch_other_pod_share")
    run = lone(host_shift=6.0).run()
    assert [names.metric(n).compute(run) for n in trio] == pytest.approx(
        [0.6 - 6.0, 0.3 + 6.0, 0.0])
    # One fault among many sound pairs is kept out.
    s = lone(cycles=24)
    s.host[3].start -= 1.0 * MS                # the second fetch ends early
    assert names.metric("readback_lag_ms_p50").compute(
        s.run()) == pytest.approx(0.3)
    run = Run(seconds=1.0)
    assert [names.metric(n).compute(run) for n in trio] == [None] * 3


@pytest.mark.parametrize("reader,own,stand_in", [
    ("route_decide_ms_p50", "route.decide", "route"),
    ("ingest_apply_ms_p50", "ingest", "step.emit"),
])
def test_control_plane_readers(reader, own, stand_in):
    """The program's own phase where it opens one, else what stands around
    the same work on the parent."""
    mod = names.metric(reader)
    both = [ev(own, 10 * MS, 2 * MS), ev(own, 20 * MS, 4 * MS),
            ev(own, 30 * MS, 3 * MS), ev(stand_in, 9 * MS, 8 * MS)]
    assert mod.compute(lone().run(*both)) == pytest.approx(3.0)
    assert mod.compute(lone().run(both[-1])) == pytest.approx(8.0)
    assert mod.compute(lone().run()) is None
    assert mod.compute(Run(seconds=1.0)) is None
