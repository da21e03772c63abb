"""The trace reduction on synthetic planes (each rule of the issue's §7) and
on a small trace recorded on the chip."""

import random
from pathlib import Path

import pytest

from kvbench.harness import names
from kvbench.trace import reduce as R

FIXTURE = Path(__file__).with_name("fixture.xplane.pb")
SPANS = ["ingest", "enqueue", "route", "step", "restore.wait",
         "replica.idle", "generator.sleep"]


def ev(name, start, dur, **stats):
    return R.Event(name, float(start), float(dur), dict(stats))


def device(idx, ops, modules=(), steps=()):
    return R.Plane(f"/device:TPU:{idx}", {
        R.OPS_LINE: list(ops), R.MODULES_LINE: list(modules),
        "Steps": list(steps)})


def host(*events):
    return R.Plane(R.HOST_PLANE, {"python3": list(events)})


def test_union_and_gaps():
    assert R.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert R.union([(0, 10)], clip=(2, 4)) == [(2, 4)]
    assert R.union([(0, 1)], clip=(2, 4)) == []
    assert R.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]
    assert R.overlap((0, 10), [(1, 2), (8, 12)]) == 3


def test_busy_is_the_union_of_the_ops_line_only():
    """Ops, modules and steps cover the same time; summing lines or events
    would pass the window."""
    ops = [ev("fusion.1", 0, 40), ev("fusion.2", 30, 30),   # overlap
           ev("copy.3", 80, 10)]
    mods = [ev("jit_forward_decode_pallas(1)", 0, 95)]
    steps = [ev("0", 0, 100)]
    red = R.reduce([device(0, ops, mods, steps),
                    host(ev("step", 0, 100))], 1, SPANS)
    assert red.window == (0, 100)
    assert red.busy["/device:TPU:0"] == [(0, 60), (80, 90)]
    assert red.busy_s == pytest.approx(70e-9)
    assert 0 < red.busy_s <= red.window_s
    assert sum(e.dur for e in ops + mods + steps) > 100  # the wrong sum


def test_ops_are_clipped_to_the_window_and_name_their_program():
    ops = [ev("a", 10, 10), ev("b", 50, 10)]
    mods = [ev("jit_forward_prefill_pallas(7)", 5, 20),
            ev("jit_forward_decode_pallas(9)", 45, 20)]
    red = R.reduce([device(0, ops, mods)], 1, SPANS)
    assert red.window == (10, 60)
    assert [e.stats["program"] for e in red.ops["/device:TPU:0"]] == [
        "jit_forward_prefill_pallas(7)", "jit_forward_decode_pallas(9)"]


def test_several_chips_give_the_mean_not_the_sum():
    planes = [device(0, [ev("a", 0, 100)]), device(1, [ev("a", 0, 50)]),
              device(2, [ev("a", 0, 100)]), device(3, [ev("a", 50, 50)]),
              host(ev("step", 0, 100))]
    red = R.reduce(planes, 4, SPANS)
    assert red.busy_s == pytest.approx(75e-9)
    assert red.busy_s <= red.window_s
    # A one-chip cell on a host with four reads its own chip only.
    assert R.reduce(planes, 1, SPANS).busy_s == pytest.approx(100e-9)


def test_empty_slice_has_no_busy_time():
    """A slice that fell into a restore holds host spans and no device op:
    busy is 0 and the run must fail rather than print it."""
    red = R.reduce([host(ev("restore.wait", 0, 100))], 1, SPANS)
    assert red.busy_s == 0.0 and red.window_s == pytest.approx(100e-9)
    with pytest.raises(ValueError):
        R.reduce([R.Plane(R.HOST_PLANE)], 1, SPANS)


def test_window_is_the_traces_own_clock():
    red = R.reduce([device(0, [ev("a", 1e9 + 10, 10)]),
                    host(ev("step", 1e9, 100))], 1, SPANS)
    assert red.window == (1e9, 1e9 + 100)
    assert red.window_s == pytest.approx(100e-9)


def test_idle_gaps_go_to_the_span_that_covered_them():
    ops = [ev("a", 0, 10), ev("b", 60, 10), ev("c", 90, 10)]
    spans = host(ev("step", 0, 40), ev("ingest", 20, 10),
                 ev("generator.sleep", 0, 100), ev("step", 60, 40))
    red = R.reduce([device(0, ops), spans], 1, SPANS)
    idle = red.idle_by_span(SPANS)
    # Gap 10..60: ingest 20..30 (inner wins), step 10..20 and 30..40, the
    # generator's sleep the rest; gap 70..90 is inside the second step.
    assert idle["ingest"] == pytest.approx(10e-9)
    assert idle["step"] == pytest.approx(40e-9)
    assert idle["generator.sleep"] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    longest = red.longest_gaps(2)
    assert longest[0][0] == pytest.approx(50e-9)


def test_work_markers_inside_the_window():
    marks = [ev(R.WORK_MARKER, t, 0, prefill_tokens=16 * i, decode_rows=i)
             for i, t in enumerate((5, 50, 500))]
    red = R.reduce([device(0, [ev("a", 0, 100)]), host(*marks)], 1, SPANS)
    assert [w["decode_rows"] for w in red.work] == [0, 1, 2]
    assert red.window == (0, 500)


def test_cpu_backend_reads_ops_from_the_host_plane():
    ops = [ev("dot.1", 0, 10, hlo_module="jit_f", run_id=1),
           ev("add.2", 10, 5, hlo_module="jit_f", run_id=1),
           ev("dot.1", 40, 10, hlo_module="jit_f", run_id=2)]
    red = R.reduce([host(*ops, ev("step", 0, 60))], 1, SPANS)
    assert red.planes == [R.HOST_PLANE]
    assert red.busy_s == pytest.approx(25e-9)
    assert sorted(e.dur for e in red.modules[R.HOST_PLANE]) == [10, 15]


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded fixture")
def test_recorded_chip_trace():
    """A toy engine's slice recorded on one v5e through this harness
    (``run.py --toy --trace 1``): device plane, ops inside modules, the
    harness's spans and work markers on the same clock."""
    planes = R.load(str(FIXTURE), SPANS)
    assert any(R.DEVICE_PLANE.match(p.name) for p in planes)
    red = R.reduce(planes, 1, SPANS)
    assert red.planes == ["/device:TPU:0"]
    assert 0 < red.busy_s <= red.window_s
    ops = red.ops["/device:TPU:0"]
    assert ops and all(e.dur >= 0 for e in ops)
    by_line = sum(e.dur for e in ops) * 1e-9
    assert red.busy_s <= by_line + 1e-12
    programs = {e.stats.get("program", "") for e in ops}
    assert any("forward_decode_pallas" in p for p in programs)
    assert red.spans["step"] and red.work
    assert all("decode_rows" in w for w in red.work)
    # Host spans and device ops share a clock: device work falls inside
    # the steps that launched it.
    inside = sum(R.overlap((e.start, e.end), red.spans["step"]) for e in ops)
    assert inside >= 0.9 * sum(e.dur for e in ops)
    idle = red.idle_by_span(SPANS)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)


# -- idle_by_span: the sweep against the version it replaced ----------------


def idle_by_span_unswept(red, priority):
    """``Reduced.idle_by_span`` as it stood until PR 27, verbatim: every gap
    against every interval of every span name. The reference the sweep is
    held to; its cost is gaps x intervals."""
    out: dict = {}
    for p in red.planes:
        for gap in R.gaps(red.busy[p], red.window):
            left = [gap]
            for name in priority:
                cover = red.spans.get(name, [])
                if not cover:
                    continue
                rest = []
                for piece in left:
                    got = R.overlap(piece, cover)
                    if got <= 0:
                        rest.append(piece)
                        continue
                    out[name] = out.get(name, 0.0) + got
                    rest.extend(R.gaps(R.union(cover, clip=piece), piece))
                left = rest
            if left:
                out["(no span)"] = out.get("(no span)", 0.0) + R.total(left)
    n = max(1, len(red.planes))
    return {k: v * R.NS / n for k, v in out.items()}


def same_idle(red):
    new, old = red.idle_by_span(SPANS), idle_by_span_unswept(red, SPANS)
    assert set(new) == set(old)
    for name in old:
        assert new[name] == pytest.approx(old[name], rel=0, abs=1e-9), name
    return new, old


def random_planes(rng):
    """A few chips, each with ops in bursts; host threads whose spans nest
    (``ingest`` and ``enqueue`` inside ``step``), stand side by side (two
    replicas' steps overlap), end inside gaps and inside ops, share edges
    with them, and leave some names of SPANS empty."""
    horizon = rng.choice([200, 2_000, 50_000])
    t0 = rng.choice([0, 1_700_000_000_000_000_000])  # a trace's own epoch
    grid = rng.choice([1, 1, 7])      # 1: many shared edges; 7: fewer

    def at(x):
        return float(t0 + grid * int(x))

    planes = []
    for chip in range(rng.randint(1, 3)):
        ops, t = [], rng.randint(0, 20)
        while t < horizon:
            dur = rng.randint(0, 12)
            ops.append(ev(f"fusion.{len(ops)}", at(t), grid * dur))
            t += rng.choice([dur, dur, rng.randint(0, 40)])  # abut or gap
        planes.append(device(chip, ops))
    names = [n for n in SPANS if rng.random() < 0.75]
    threads = []
    for _ in range(rng.randint(1, 3)):
        evs, t = [], rng.randint(0, 30)
        while t < horizon and names:
            dur = rng.randint(1, 90)
            outer = rng.choice(names)
            evs.append(ev(outer, at(t), grid * dur))
            for _ in range(rng.randint(0, 3)):       # nested, or straddling
                a = t + rng.randint(0, dur)
                evs.append(ev(rng.choice(names), at(a),
                              grid * rng.randint(0, dur)))
            t += rng.choice([dur, rng.randint(1, 150)])
        threads.append(evs)
    planes.append(R.Plane(R.HOST_PLANE, {f"python3#{i}": evs
                                         for i, evs in enumerate(threads)}))
    return planes, len(planes) - 1


@pytest.mark.parametrize("block", range(8))
def test_sweep_equals_the_unswept_on_random_planes(block):
    """Forty seeded cases a block (320 in all): the same names in the same
    order and every value within 1e-9 s; the sums go in the same order, so
    they are in fact the same to the last bit."""
    for seed in range(block * 40, block * 40 + 40):
        rng = random.Random(seed)
        planes, chips = random_planes(rng)
        try:
            red = R.reduce(planes, rng.randint(1, chips), SPANS)
        except ValueError:      # nothing at all in the trace
            continue
        new, old = same_idle(red)
        assert new == old, seed
        assert sum(new.values()) == pytest.approx(
            red.window_s - red.busy_s, rel=1e-9, abs=1e-15)


def test_sweep_on_the_corners():
    """A gap that one span covers exactly, spans that only touch a gap's
    ends, a span over the whole window, a plane with no gap at all."""
    ops = [ev("a", 0, 10), ev("b", 20, 10), ev("c", 50, 10)]
    spans = host(ev("enqueue", 10, 10),             # exactly the first gap
                 ev("route", 0, 10), ev("route", 30, 0), ev("route", 60, 5),
                 ev("ingest", 28, 4),               # 2 busy, 2 idle
                 ev("ingest", 48, 2),               # ends where an op starts
                 ev("generator.sleep", 0, 65))
    red = R.reduce([device(0, ops), spans], 1, SPANS)
    new, _ = same_idle(red)
    assert new == {"enqueue": pytest.approx(10e-9),
                   "ingest": pytest.approx(4e-9),
                   "route": pytest.approx(5e-9),
                   "generator.sleep": pytest.approx(16e-9)}
    full = R.reduce([device(0, [ev("a", 0, 100)]), host(ev("step", 0, 100))],
                    1, SPANS)
    assert full.idle_by_span(SPANS) == {} == idle_by_span_unswept(full, SPANS)


class Counted(list):
    """A list that counts every item handed out, by index (``bisect`` and
    ``cover[i]``) or by iteration: one interval looked at is one count."""

    looked = 0

    def __getitem__(self, i):
        Counted.looked += 1
        return list.__getitem__(self, i)

    def __iter__(self):
        for x in list.__iter__(self):
            Counted.looked += 1
            yield x


def slice_like(n_gaps, n_steps):
    """A traced slice in the proportions of the chip's: ``n_gaps`` short
    gaps between ops, ``n_steps`` steps of two replicas side by side that
    cover nearly all of it, an ``enqueue`` in every fourth step, ``ingest``
    inside those, a generator asleep over most of it."""
    span = 1000.0 * n_gaps
    busy = [(1000.0 * i, 1000.0 * i + 800.0) for i in range(n_gaps)]
    step = span / n_steps
    spans = {
        "step": R.union((i * step + 10, (i + 1) * step - 10)
                        for i in range(n_steps)),
        "enqueue": R.union((i * step, i * step + 9) for i in range(0, n_steps, 4)),
        "ingest": R.union((i * step + 2, i * step + 5)
                          for i in range(0, n_steps, 4)),
        "route": [], "restore.wait": [], "replica.idle": [],
        "generator.sleep": R.union((i * 50 * step, (i * 50 + 49) * step)
                                   for i in range(max(1, n_steps // 50))),
    }
    return R.Reduced(window=(0.0, span), planes=["/device:TPU:0"],
                     busy={"/device:TPU:0": busy}, ops={}, modules={},
                     spans=spans, work=[])


def looked_at(red, fn):
    red.spans = {n: Counted(ivs) for n, ivs in red.spans.items()}
    Counted.looked = 0
    fn(red)
    return Counted.looked


def test_sweep_work_grows_with_the_trace_not_its_square():
    """Counted work, not the clock: intervals looked at. Twice the gaps and
    twice the spans at most double it (and a logarithm's step); from PR 26's
    parent slice (70k gaps, 110 steps) to its change's (300k, 460) it stays
    within twice the growth of the trace. The unswept version quadruples,
    which is what this test would say of the parent's code."""
    def sweep(red):
        return red.idle_by_span(SPANS)

    def unswept(red):
        return idle_by_span_unswept(red, SPANS)

    small = looked_at(slice_like(2_000, 40), sweep)
    double = looked_at(slice_like(4_000, 80), sweep)
    assert double <= 2.0 * small * 1.25
    assert (looked_at(slice_like(2_000, 80), unswept)
            >= 3.5 * looked_at(slice_like(1_000, 40), unswept))
    parent = looked_at(slice_like(70_000, 110), sweep)
    change = looked_at(slice_like(300_000, 460), sweep)
    assert change <= 2.0 * (300_000 / 70_000) * parent
    # And not by doing less: the two agree where the unswept can be afforded.
    same_idle(slice_like(3_000, 60))


def test_op_names():
    text = ("%pallas_paged_decode_attention.30 = bf16[32,8,2,128]{3,2,1,0} "
            "custom-call(s32[32,264]{1,0} %x), custom_call_target=\"tpu\"")
    assert R.op_name(text) == "pallas_paged_decode_attention.30"
    assert R.op_name("dot.1") == "dot.1"
    assert R.base_name("pallas_paged_decode_attention.30") == (
        "pallas_paged_decode_attention")
    assert R.base_name("fusion") == "fusion"
    assert R.base_name("copy.v2") == "copy.v2"
    ops = [ev("k.1", 0, 10), ev("k.2", 10, 10), ev("other", 20, 5)]
    red = R.reduce([device(0, ops)], 1, SPANS)
    assert red.op_seconds() == {"k": pytest.approx(20e-9),
                                "other": pytest.approx(5e-9)}


def test_threads_of_one_name_keep_their_lines(tmp_path):
    """Host threads share a name ("python3"); a replica's spans must not
    be lost to the generator's line."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    import threading

    def worker(name):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(name):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation(R.WORK_MARKER, decode_rows=1):
                pass

    jax.profiler.start_trace(str(tmp_path))
    time.sleep(0.05)
    threads = [threading.Thread(target=worker, args=(n,))
               for n in ("step", "route", "ingest")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jax.profiler.stop_trace()
    planes = R.load(R.find_xplane(str(tmp_path)), SPANS)
    red = R.reduce(planes, 1, SPANS)
    assert all(red.spans[n] for n in ("step", "route", "ingest"))
    assert len(red.work) == 9
    assert int(red.work[0]["decode_rows"]) == 1


# -- the engine's phases -------------------------------------------------------


def phase(name, start, dur, pod="pod-0", step=1, **stats):
    return ev(name, start, dur, pod=pod, step=step, **stats)


def phased_run(*events, names=None):
    """A run whose trace holds one device op and the given host events."""
    from kvbench.harness.loop import Run
    from kvbench.run import SPANS as RUN_SPANS

    run = Run(seconds=1.0)
    run.trace = R.reduce(
        [device(0, [ev("fusion.1", 0, 10)]), host(*events)], 1,
        names or RUN_SPANS)
    return run


def test_run_spans_hold_every_phase_inner_before_outer_work_before_wait():
    from kvbench import run as run_py
    from llmd_kv_cache_tpu.telemetry.tracing import PHASE_NAMES

    spans = run_py.SPANS
    assert set(PHASE_NAMES) <= set(spans) and len(set(spans)) == len(spans)
    assert run_py.spans_of(PHASE_NAMES) == spans
    at = spans.index
    assert at("step.emit") < at("step.commit") < at("step.fetch") < at("step")
    assert at("enqueue.lookup") < at("enqueue.admit") < at("enqueue")
    assert at("ingest") < at("step.emit")      # the sink runs inside emit
    # A phase a later PR's program names goes among the work, before the
    # waits, with no edit.
    later = run_py.spans_of((*PHASE_NAMES, "step.experts"))
    assert later.index("step.finish") < later.index("step.experts") < (
        later.index("step.fetch"))
    assert [n for n in later if n != "step.experts"] == spans


def test_phase_events_keep_their_attributes_in_start_order():
    from kvbench.metrics import _read

    run = phased_run(
        phase("step.finish", 90, 5, programs=4, transfers=6),
        phase("step.finish", 40, 5, step=0, programs=1, transfers=3),
        phase("step.dispatch", 20, 10, rows=2, programs=1),
        ev("step", 0, 100, pod="pod-0"),
        ev("step.work", 99, 0, pod="pod-0", decode_rows=2))
    got = _read.phase_events(run, "step.finish")
    assert [(e.start, e.dur, e.stats["programs"], e.stats["transfers"],
             e.stats["step"]) for e in got] == [(40, 5, 1, 3, 0),
                                                (90, 5, 4, 6, 1)]
    assert _read.phase_events(run, "step.dispatch")[0].stats["rows"] == 2
    assert [e.dur for e in _read.phase_events(run, "step")] == [100]
    assert _read.phase_events(run, "step.commit") == []
    # The work marker is handed on as before, not as an event.
    assert _read.phase_events(run, "step.work") == []
    assert run.trace.work == [{"pod": "pod-0", "decode_rows": 2}]
    run.trace = None
    assert _read.phase_events(run, "step.finish") == []


def test_idle_goes_to_the_phase_before_the_step_around_it():
    """Work before wait: while one replica builds inputs and the other
    waits in its fetch, the gap is the inputs'."""
    from kvbench.run import SPANS as RUN_SPANS

    events = [ev("step", 10, 90, pod="pod-0"), ev("step", 10, 90, pod="pod-1"),
              phase("step.inputs", 10, 30),
              phase("step.fetch", 10, 80, pod="pod-1"),
              phase("step.fetch", 40, 50)]
    red = R.reduce([device(0, [ev("fusion.1", 0, 10), ev("fusion.2", 95, 5)]),
                    host(*events)], 1, RUN_SPANS)
    idle = red.idle_by_span(list(red.spans))
    assert idle == pytest.approx({"step.inputs": 30e-9, "step.fetch": 50e-9,
                                  "step": 5e-9})


def test_step_host_ms_is_the_union_of_a_steps_phases_without_the_fetch():
    reader = names.metric("step_host_ms_p50")
    ms = 1e6
    one = [phase("step.offload_poll", 0 * ms, 0.1 * ms),
           phase("step.schedule", 0.1 * ms, 0.1 * ms),
           phase("step.inputs", 0.2 * ms, 1.0 * ms),
           phase("step.dispatch", 1.2 * ms, 0.5 * ms, programs=1),
           phase("step.sample", 1.7 * ms, 1.0 * ms, programs=1),
           phase("step.fetch", 2.7 * ms, 6.0 * ms),
           phase("step.commit", 8.7 * ms, 1.0 * ms),
           phase("step.emit", 8.9 * ms, 0.5 * ms, events=3),   # nested
           phase("step.finish", 9.7 * ms, 0.3 * ms, programs=2)]
    # An eviction's emit inside enqueue() carries the last step's ordinal
    # and lies outside the step; a step the slice cut has no finish.
    stray = [phase("step.emit", 12 * ms, 2 * ms, events=1),
             phase("enqueue.lookup", 11 * ms, 4 * ms),
             phase("step.offload_poll", 20 * ms, 0.1 * ms, step=2),
             phase("step.inputs", 20.1 * ms, 3 * ms, step=2)]
    other = [phase("step.offload_poll", 1 * ms, 0.5 * ms, pod="pod-1"),
             phase("step.fetch", 1.5 * ms, 3 * ms, pod="pod-1"),
             phase("step.finish", 4.5 * ms, 0.5 * ms, pod="pod-1")]
    run = phased_run(*one, *stray, *other)
    assert sorted(reader.step_host_ms(run)) == pytest.approx([1.0, 4.0])
    assert reader.compute(run) == pytest.approx(2.5)
    assert (reader.SOURCE, reader.UNIT, reader.MOVES) == (
        "program_span", "ms", "itl_mean_ms")


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded fixture")
def test_recorded_trace_holds_no_phase_and_the_reader_finds_nothing():
    """Recorded before a traced run switched the phases on: the accessor
    returns nothing and the reader, finding nothing, returns None."""
    from kvbench.harness.loop import Run
    from kvbench.metrics import _read
    from kvbench.run import SPANS as RUN_SPANS
    from llmd_kv_cache_tpu.telemetry.tracing import PHASE_NAMES

    run = Run(seconds=1.0)
    run.trace = R.reduce(R.load(str(FIXTURE), RUN_SPANS), 1, RUN_SPANS)
    for name in PHASE_NAMES:
        assert _read.phase_events(run, name) == []
        assert run.trace.spans[name] == []
    assert len(_read.phase_events(run, "step")) == len(run.trace.work) > 0
    assert names.metric("step_host_ms_p50").compute(run) is None
    # What the old names read is what they read before.
    old = R.reduce(R.load(str(FIXTURE), SPANS), 1, SPANS)
    assert old.window == run.trace.window and old.busy == run.trace.busy
    assert old.idle_by_span(SPANS) == run.trace.idle_by_span(RUN_SPANS)
