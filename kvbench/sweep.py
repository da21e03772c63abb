#!/usr/bin/env python3
"""Several rates of one open-loop cell in ONE process (one set-up), to find
its knee: the highest rate at which no more requests are in flight at the
window's end than when sampling started.

    python3 kvbench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 4,6,8,10,12 [--toy]

Prints one JSON line per rate (not the benchmark's result line): the cell's
metrics that need no trace, requests in flight at both ends, failures,
generator lateness. The caches are not reset between rates: each starts from the
state the one before left, as a serving system would.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second (open loop) "
                         "or clients (closed loop)")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    from kvbench.harness import loop, names
    from kvbench.harness.fleet import log, memory_peak_bytes
    from kvbench.harness.prepare import prepare, programs_first_used

    bench = names.benchmark()
    cell = names.workload(bench, args.workload)
    entries = (names.cell_metrics(bench, cell["name"], False)
               + names.cell_metrics(bench, cell["name"], True))
    readers = [names.metric(m["name"]) for m in entries]
    conf = names.config_for_run(bench, cell["config"], args.toy)
    traffic = names.with_rehearsal(names.traffic(cell["traffic"]), args.toy)
    gen = names.generator(traffic["generator"])
    knob = "clients" if traffic["loop"] == "closed" else "rate"
    rates = [float(x) for x in args.rates.split(",")]

    ctx = prepare(cell, conf, {**traffic, knob: rates[0]}, gen.schedule,
                  args.seed, args.seconds, args.toy, T_PROCESS)
    try:
        for i, rate in enumerate(rates):
            mix = {**traffic, knob: int(rate) if knob == "clients" else rate}
            if i:
                ctx.schedule = gen.schedule(args.seed + i, mix,
                                            ctx.cfg.vocab_size, args.seconds)
            run = loop.serve(ctx.fleet, ctx.schedule, mix, args.seconds,
                             lambda: programs_first_used(ctx.stats))
            run.setup_seconds = 0.0
            run.cfg = ctx.cfg
            log(f"window: {run.summary()}")
            row = {knob: rate, "sampled": len(run.sampled()),
                   "failed": len(run.failed()),
                   "inflight_start": run.inflight_start,
                   "inflight_end": run.inflight_end,
                   "first_used_in_window": run.compiles_in_window,
                   "errors": run.errors,
                   "memory_peak_bytes": memory_peak_bytes(ctx.devices)}
            for mod in readers:
                value = mod.compute(run)
                if value is not None:
                    row[mod.NAME] = value
            log("sweep " + json.dumps(row))
    finally:
        ctx.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
