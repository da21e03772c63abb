#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 kvbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds what is missing, loads, warms up, measures for ``--seconds``, and
prints one JSON object as the last line of its standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy seconds and a breakdown. The line is
checked against the contract before it is printed; a run that cannot print
a good line prints what is wrong and exits non-zero. It raises without a
TPU holding the chips the cell asks for. ``--rehearse`` walks the same code
at toy widths through the Pallas interpreter on the CPU: its line says
``platform: "cpu"`` and none of its numbers is a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import atexit  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# None of these imports JAX: --rehearse must set JAX_PLATFORMS first.
from kvbench.harness import names  # noqa: E402
from kvbench.harness.check_line import BadLine, check_line  # noqa: E402
from kvbench.harness.fleet import log, memory_peak_bytes  # noqa: E402

# Host spans, in the order in which one takes an idle gap's time where
# several cover it: inner before outer, work before wait (a gap in which
# one replica builds inputs while the other waits in ``step.fetch`` goes to
# the one working), a replica's before the generator's. The dotted names
# are the engine's phases (``telemetry/tracing.py: PHASE_NAMES``), which a
# traced run switches on; the outer spans keep what no phase covers.
SPANS = ["ingest", "step.emit", "step.commit", "step.inputs", "step.dispatch",
         "step.sample", "step.schedule", "step.offload_poll", "step.finish",
         "enqueue.hash", "enqueue.lookup", "enqueue.admit", "step.fetch",
         "enqueue", "route", "step", "restore.wait", "replica.idle",
         "generator.sleep"]


def spans_of(program_phases) -> list:
    """``SPANS`` with every phase the program names that ``SPANS`` does not
    (a later PR's) among the work phases, before the waits: such a phase
    reaches the readers and the breakdown with no edit here."""
    new = [n for n in program_phases if n not in SPANS]
    at = SPANS.index("step.fetch")
    return SPANS[:at] + new + SPANS[at:]


# The check stops a run that is still going after CHECK_LIMIT_S; a run
# that passes WARN_S says so on stderr (README, "The time budget").
CHECK_LIMIT_S = 360.0
WARN_S = 300.0


@contextlib.contextmanager
def stage(after: dict, name: str):
    """Host seconds of one stage after the window, into ``after``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        after[name] = after.get(name, 0.0) + time.perf_counter() - t0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths, Pallas interpreted, on the CPU")
    ap.add_argument("--toy", action="store_true",
                    help="the rehearsal's toy widths on the device that is "
                         "present (records the tests' trace fixture)")
    ap.add_argument("--trace-seconds", type=float, default=0.0,
                    help="length of the traced slice, instead of the "
                         "traffic file's")
    ap.add_argument("--keep-trace", default="",
                    help="copy the .xplane.pb here (a path in the checkout)")
    return ap.parse_args(argv)


def tracer_calls(ctx):
    """(start, stop) for the traced slice; ``stop`` leaves the serialized
    XSpace in ``ctx.xspace``. Host spans come from the harness's
    TraceAnnotations.

    A session of the profiler itself, not ``jax.profiler.stop_trace``: that
    also exports, which beside the ``.xplane.pb`` (these same bytes) writes
    a ``trace.json.gz`` nothing here reads, at a cost that grows faster than
    the trace (6 s of 34 at 0.29 M device ops, 84 s of 129 at 1.18 M; my
    chip runs, PR 27). ``stop`` alone takes about 21 s + 21 us per op."""
    import jax
    from jax._src.lib import _profiler  # what jax.profiler itself wraps

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events: they slow
    opts.host_tracer_level = 2     # the host; TraceMe spans stay
    ctx.xspace = b""
    session = []

    def start():
        session.append(_profiler.ProfilerSession(opts))

    def stop():
        ctx.xspace = session.pop().stop()

    return start, stop


def measure(ctx, cell, traffic, seconds, traced, after, keep_trace=""):
    """One window, and the run record the metric readers take. ``after``
    takes the seconds of every stage past the window's end."""
    from kvbench.harness import loop
    from kvbench.harness.prepare import programs_first_used
    from kvbench.trace import opcount, reduce as trace_reduce
    from llmd_kv_cache_tpu.telemetry.tracing import PHASE_NAMES

    spans = spans_of(PHASE_NAMES)
    at = None
    if traced:
        at = (1.0 / 3.0, float(traffic["trace_seconds"]),
              traffic.get("trace_steps"), *tracer_calls(ctx))
    setup_seconds = time.perf_counter() - ctx.t_process
    run = loop.serve(ctx.fleet, ctx.schedule, traffic, seconds,
                     lambda: programs_first_used(ctx.stats), at)
    run.setup_seconds = setup_seconds
    run.cfg = ctx.cfg
    run.counts = ctx.counts
    run.peaks = (opcount.peaks(ctx.device["kind"])
                 if ctx.device["platform"] == "tpu"
                 else opcount.rehearsal_peaks())
    after.update(run.stages)
    if traced:
        run.trace_bytes = len(ctx.xspace)
        with stage(after, "load"):
            planes = trace_reduce.load(ctx.xspace, spans)
        with stage(after, "reduce"):
            run.trace = trace_reduce.reduce(planes, int(cell["chips"]), spans)
        log(f"trace: {run.trace_bytes / 2**20:.1f} MiB reduced "
                  f"in {after['load'] + after['reduce']:.1f}s; window "
                  f"{run.trace.window_s:.3f}s busy {run.trace.busy_s:.3f}s "
                  f"on {run.trace.planes}; {len(run.trace.work)} steps")
        if keep_trace:
            os.makedirs(os.path.dirname(keep_trace) or ".", exist_ok=True)
            Path(keep_trace).write_bytes(ctx.xspace)
        ctx.xspace = b""   # the planes are what is read from here on
    return run


def correctness(ctx, run) -> list:
    """Every reason why ``correct`` is false; empty when it is true."""
    faults = list(ctx.probe["faults"])                      # (a), (b)
    bad = [r.idx for r in run.requests if r.done and not r.failed
           and not r.tokens_ok]
    if bad:                                                 # (c)
        faults.append(f"{len(bad)} finished requests have the wrong number "
                      f"of tokens or one outside the vocabulary: {bad[:5]}")
    faults += ctx.served_faults                             # (d)
    if run.compiles_in_window:                              # (e)
        faults.append(f"{run.compiles_in_window} programs were first used "
                      f"(compiled or fetched) inside the window")
    faults += run.errors
    return faults


def compared(ctx, run) -> dict:
    """name -> [number, limit]: every number ``correctness`` compares,
    beside its limit, for the line's last key and the last lines on stderr
    (what the driver's record keeps of a run that is not correct)."""
    from kvbench.harness.correct import MAX_ALTERNATIVES

    probe = ctx.probe
    tol = probe["tolerance"]
    bad_tokens = sum(1 for r in run.requests
                     if r.done and not r.failed and not r.tokens_ok)
    out = {"prefill_err": (probe["prefill_rel_err"], tol),
           "decode_shortfall": (probe["decode_worst_shortfall"], tol),
           "hit_err": (probe["hit_rel_err"], tol),
           "replica_err": (probe["other_replicas_rel_err"], tol),
           "alternatives_max": (max(probe["alternatives"]),
                                MAX_ALTERNATIVES),
           "probe_faults": (len(probe["faults"]), 0),
           "bad_token_requests": (bad_tokens, 0),
           "serving_faults": (len(ctx.served_faults) + len(run.errors), 0),
           "programs_first_used_in_window": (run.compiles_in_window, 0)}
    # A number that is not one (NaN logits) shows as a probe fault.
    return {k: [float(v), float(lim)] for k, (v, lim) in out.items()
            if math.isfinite(v)}


def breakdown(run, after) -> dict:
    with stage(after, "op_seconds"):
        ops = sorted(run.trace.op_seconds().items(), key=lambda kv: -kv[1])
    with stage(after, "idle_by_span"):
        # ``spans`` holds the names in the order they were reduced in.
        idle = sorted(run.trace.idle_by_span(list(run.trace.spans)).items(),
                      key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops[:10]],
            "idle_gaps": [[n, s] for n, s in idle[:10]]}


def report(run, readers, expected, after, toy=False) -> dict:
    """name -> {value, unit} for every reader that found something."""
    out = {}
    for mod, entry in zip(readers, expected):
        with stage(after, f"reader.{mod.NAME}"):
            value = mod.compute(run)
        if value is None and toy and mod.SOURCE == "device_trace":
            # The interpreter runs no kernel a trace could name: the toy
            # walk-through goes on with 0, a real run stops at the check.
            log(f"metric {mod.NAME}: no such device events at toy "
                      f"size; 0 stands in")
            value = 0.0
        if value is None:
            log(f"metric {mod.NAME}: nothing to read in this run")
            continue
        out[mod.NAME] = {"value": float(value), "unit": entry["unit"]}
    return out


def after_the_window(run, after, traced) -> dict:
    """What the run spent past its window's end, by stage, and the sizes of
    the trace that drive it. ``unaccounted`` is what no stage timed."""
    total = time.perf_counter() - run.t_end
    out = {"total_s": round(total, 3),
           "unaccounted_s": round(total - sum(after.values()), 3),
           "stages_s": {name: round(s, 3) for name, s in after.items()}}
    if traced:
        from kvbench.trace import reduce as trace_reduce

        tr = run.trace
        # Beside the window, on the tracer's own thread: what of its stop
        # lay past the window's end is the wait in ``join.tracer``.
        out["tracer"] = {k: round(v, 3) if isinstance(v, float) else v
                         for k, v in run.tracer.items()}
        out["counts"] = {
            "trace_bytes": run.trace_bytes,
            "device_ops": sum(len(tr.ops[p]) for p in tr.planes),
            "busy_intervals": sum(len(tr.busy[p]) for p in tr.planes),
            "gaps": sum(len(trace_reduce.gaps(tr.busy[p], tr.window))
                        for p in tr.planes),
            "span_intervals": {n: len(ivs) for n, ivs in tr.spans.items()},
            "span_events": {n: len(evs) for n, evs in tr.events.items()},
            "step.work": len(tr.work)}
    return out


class GcPauses:
    """The collector's pauses while it is registered (``gc.callbacks``):
    a collection stops every Python thread, the generator's included, so a
    run whose generator was seconds late can be told from one the host
    stalled (PERF.md, PR 29)."""

    def __init__(self):
        self.t0, self.pauses = None, []   # pauses: (generation, seconds)

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.t0))

    def summary(self) -> dict:
        by_gen = {g: [s for gen, s in self.pauses if gen == g]
                  for g in (0, 1, 2)}
        return {f"gen{g}": {"n": len(v), "longest_ms": round(max(v) * 1e3, 2),
                            "total_ms": round(sum(v) * 1e3, 1)}
                for g, v in by_gen.items() if v}


def gap_summary(run) -> dict:
    """Where the gaps between tokens lie: which percentile sits on an edge
    between two kinds of gap shows here, run by run (PERF.md, PR 29)."""
    from kvbench.harness.stats import mean, percentile
    from kvbench.metrics import _read

    gaps = _read.token_gaps_ms(run)
    out = {"n": len(gaps), "mean": mean(gaps)}
    out.update({f"p{q}": percentile(gaps, q) for q in (50, 90, 95, 99)})
    return {k: round(v, 3) if isinstance(v, float) else v
            for k, v in out.items()}


def say_exit(t_line: list) -> None:
    """``t_line``: when the last line was printed, and what was compared.
    The numbers beside their limits are the last lines on stderr."""
    if t_line:
        print(f"[kvbench] exit: {time.perf_counter() - t_line[0]:.1f}s "
              f"after the last line", file=sys.stderr)
        for name, (value, limit) in t_line[1].items():
            print(f"[kvbench] compared: {name} {value!r} limit {limit!r}",
                  file=sys.stderr)
        sys.stderr.flush()


def main(argv=None, bench=None) -> int:
    """``bench``: the contract to run a cell of, where it is not the
    checkout's ``BENCHMARK.json`` (the tests' fixture cell)."""
    args = parse_args(argv)
    # Registered before JAX is imported, so it runs after JAX's own exit
    # handlers: what the runtime's shutdown takes shows on stderr.
    t_line: list = []
    atexit.register(say_exit, t_line)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    toy = args.rehearse or args.toy
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s: %(message)s")
    from kvbench.harness.prepare import prepare

    if bench is None:
        bench = names.benchmark()
    cell = names.workload(bench, args.workload)
    traced = bool(args.trace)
    expected = names.cell_metrics(bench, cell["name"], traced)
    both = (names.cell_metrics(bench, cell["name"], False)
            + names.cell_metrics(bench, cell["name"], True))
    all_readers = [names.metric(m["name"]) for m in both]
    conf = names.config_for_run(bench, cell["config"], toy)
    traffic = names.with_rehearsal(names.traffic(cell["traffic"]), toy)
    if args.trace_seconds:
        traffic["trace_seconds"] = args.trace_seconds
    gen = names.generator(traffic["generator"])

    ctx = prepare(cell, conf, traffic, gen.schedule, args.seed, args.seconds,
                  toy, T_PROCESS, traced)
    after: dict = {}
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        run = measure(ctx, cell, traffic, args.seconds, traced, after,
                      args.keep_trace)
        peak = memory_peak_bytes(ctx.devices)
    finally:
        gc.callbacks.remove(pauses)
        with stage(after, "close"):
            ctx.close()

    log(f"window: {run.summary()}")
    log(f"token gaps of the sampled requests, ms: {gap_summary(run)}")
    log(f"collector's pauses from the window's start: {pauses.summary()}")
    # Every metric of the cell on an earlier line, whatever the mode, so
    # that the cost of tracing can be read against the untraced runs; the
    # last line takes this mode's from the same readings.
    everything = report(run, all_readers, both, after, toy)
    log("all metrics of this run (the last line holds this mode's): "
              + json.dumps(everything))
    faults = correctness(ctx, run)
    if faults:
        log(f"NOT correct: {faults}")

    device = dict(ctx.device, memory_peak_bytes=peak)
    line = {"correct": not faults, "attempted": len(run.sampled()),
            "failed": len(run.failed()),
            "metrics": {m["name"]: everything[m["name"]] for m in expected
                        if m["name"] in everything},
            "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = breakdown(run, after)
        with stage(after, "longest_gaps"):
            longest = run.trace.longest_gaps()
        log(f"longest idle gaps (s, plane): "
                  f"{[(round(s, 4), p) for s, p, _ in longest]}")
    line["compared"] = compared(ctx, run)
    try:
        with stage(after, "check_line"):
            text = check_line(line, expected, traced)
    except BadLine as exc:
        log(f"no result: the line would be refused: {exc}")
        return 1
    summary = after_the_window(run, after, traced)
    log("after the window: " + json.dumps(summary))
    spent = time.perf_counter() - T_PROCESS
    budget = (f"budget: set-up {run.setup_seconds:.1f}s + window "
              f"{args.seconds:.1f}s + after the window "
              f"{summary['total_s']:.1f}s = {spent:.1f}s of the check's "
              f"{CHECK_LIMIT_S:.0f}s")
    log(budget)
    if spent > WARN_S:
        print(f"[kvbench] WARNING {budget}: over {WARN_S:.0f}s",
              file=sys.stderr, flush=True)
    sys.stdout.flush()
    print(text, flush=True)
    t_line += [time.perf_counter(), line["compared"]]
    return 0


if __name__ == "__main__":
    sys.exit(main())
