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
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# None of these imports JAX: --rehearse must set JAX_PLATFORMS first.
from kvbench.harness import names  # noqa: E402
from kvbench.harness.check_line import BadLine, check_line  # noqa: E402
from kvbench.harness.fleet import log, memory_peak_bytes  # noqa: E402

# Harness spans, in the order in which one takes an idle gap's time where
# several cover it (inner before outer, a replica's before the generator's).
SPANS = ["ingest", "enqueue", "route", "step", "restore.wait",
         "replica.idle", "generator.sleep"]


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
    """(start, stop) for the traced slice: the profiler writes under
    TMPDIR; host spans come from the harness's TraceAnnotations."""
    import jax

    ctx.trace_dir = Path(tempfile.mkdtemp(prefix="kvbench-trace-"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events: they slow
    opts.host_tracer_level = 2     # the host; TraceMe spans stay
    state = {}

    def start():
        jax.profiler.start_trace(str(ctx.trace_dir), profiler_options=opts)
        state["on"] = True

    def stop():
        if state.pop("on", False):
            jax.profiler.stop_trace()

    return start, stop


def measure(ctx, cell, traffic, seconds, traced, keep_trace=""):
    """One window, and the run record the metric readers take."""
    from kvbench.harness import loop
    from kvbench.harness.prepare import programs_first_used
    from kvbench.trace import opcount, reduce as trace_reduce

    at = None
    if traced:
        start, stop = tracer_calls(ctx)
        at = (1.0 / 3.0, float(traffic["trace_seconds"]), start, stop)
    setup_seconds = time.perf_counter() - ctx.t_process
    run = loop.serve(ctx.fleet, ctx.schedule, traffic, seconds,
                     lambda: programs_first_used(ctx.stats), at)
    run.setup_seconds = setup_seconds
    run.cfg = ctx.cfg
    run.peaks = (opcount.peaks(ctx.device["kind"])
                 if ctx.device["platform"] == "tpu"
                 else opcount.rehearsal_peaks())
    if traced:
        stop()
        path = trace_reduce.find_xplane(str(ctx.trace_dir))
        t0 = time.perf_counter()
        planes = trace_reduce.load(path, SPANS)
        run.trace = trace_reduce.reduce(planes, int(cell["chips"]), SPANS)
        log(f"trace: {os.path.getsize(path) / 2**20:.1f} MiB reduced "
                  f"in {time.perf_counter() - t0:.1f}s; window "
                  f"{run.trace.window_s:.3f}s busy {run.trace.busy_s:.3f}s "
                  f"on {run.trace.planes}; {len(run.trace.work)} steps")
        if keep_trace:
            os.makedirs(os.path.dirname(keep_trace) or ".", exist_ok=True)
            shutil.copy(path, keep_trace)
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


def breakdown(run) -> dict:
    ops = sorted(run.trace.op_seconds().items(), key=lambda kv: -kv[1])
    idle = sorted(run.trace.idle_by_span(SPANS).items(),
                  key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops[:10]],
            "idle_gaps": [[n, s] for n, s in idle[:10]]}


def report(run, readers, expected, toy=False) -> dict:
    """name -> {value, unit} for every reader that found something."""
    out = {}
    for mod, entry in zip(readers, expected):
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


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    toy = args.rehearse or args.toy
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s: %(message)s")
    from kvbench.harness.prepare import prepare

    bench = names.benchmark()
    cell = names.workload(bench, args.workload)
    traced = bool(args.trace)
    expected = names.cell_metrics(bench, cell["name"], traced)
    readers = [names.metric(m["name"]) for m in expected]
    both = (names.cell_metrics(bench, cell["name"], False)
            + names.cell_metrics(bench, cell["name"], True))
    all_readers = [names.metric(m["name"]) for m in both]
    conf = names.config_for_run(bench, cell["config"], toy)
    traffic = names.with_rehearsal(names.traffic(cell["traffic"]), toy)
    if args.trace_seconds:
        traffic["trace_seconds"] = args.trace_seconds
    gen = names.generator(traffic["generator"])

    ctx = prepare(cell, conf, traffic, gen.schedule, args.seed, args.seconds,
                  toy, T_PROCESS)
    try:
        run = measure(ctx, cell, traffic, args.seconds, traced,
                      args.keep_trace)
        peak = memory_peak_bytes(ctx.devices)
    finally:
        ctx.close()

    log(f"window: {run.summary()}")
    # Every metric of the cell on an earlier line, whatever the mode, so
    # that the cost of tracing can be read against the untraced runs.
    log("all metrics of this run (the last line holds this mode's): "
              + json.dumps(report(run, all_readers, both, toy)))
    faults = correctness(ctx, run)
    if faults:
        log(f"NOT correct: {faults}")

    device = dict(ctx.device, memory_peak_bytes=peak)
    line = {"correct": not faults, "attempted": len(run.sampled()),
            "failed": len(run.failed()),
            "metrics": report(run, readers, expected, toy),
            "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = breakdown(run)
        log(f"longest idle gaps (s, plane): "
                  f"{[(round(s, 4), p) for s, p, _ in run.trace.longest_gaps()]}")
    try:
        text = check_line(line, expected, traced)
    except BadLine as exc:
        log(f"no result: the line would be refused: {exc}")
        return 1
    sys.stdout.flush()
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
