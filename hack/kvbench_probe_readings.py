#!/usr/bin/env python3
"""The readings a configuration's ``TOLERANCE`` and ``MARGIN`` are set from,
taken with the benchmark's own probe (``kvbench/harness/correct.py``) over
several seeds: one replica of the configuration as the cell builds it, a
line a seed. Not part of a run of the benchmark; a builder's tool, for the
chip (``chiprun -- python3 hack/kvbench_probe_readings.py ...``) or, with
``--rehearse``, the CPU at toy widths.

  --seeds 1,2,3        the probe against the served program
  --margins            also the reference's ``margin_readings`` (how far
                       bfloat16 moves a router's deciding gaps)
  --control TYPE       the probe against the reference's own ``Control``
                       (its forward rounded to TYPE, e.g. float8_e4m3fn)
                       in the engine's place: it has to come out not ok
  --set KEY=VALUE      a published key of the configuration replaced
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench.harness import correct, fleet as F, names  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="deepseek-v3.2-exp-ep16-l5")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--margins", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()

    import jax

    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

    conf = names.config_for_run(names.benchmark(), args.config, args.rehearse)
    for pair in args.set:
        key, value = pair.split("=", 1)
        conf[key] = json.loads(value)
    ref = names.reference(conf)
    eng = conf["kvbench"]["engine"]
    sizes = conf["kvbench"]["probe"]
    n, new = int(sizes["prompt_tokens"]), int(sizes["decode_tokens"])
    gaps = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        cfg, params = F.build_model(conf, seed)
        fl = F.Fleet()
        fl.cfg = cfg
        if args.control:
            fl.engines["pod-0"] = ref.Control(params, cfg, args.control)
        else:
            fl.engines["pod-0"] = MiniEngine(EngineConfig(
                model=cfg, model_name="m", pod_identifier="pod-0",
                num_pages=int(eng["num_pages"]),
                max_pages_per_seq=int(eng["max_pages_per_seq"]),
                max_batch=int(eng["max_batch"]),
                max_prefill_tokens=int(eng["max_prefill_tokens"])),
                params=params)
        rep = correct.probe(fl, params, ref, seed, n, new)
        stats = jax.devices()[0].memory_stats() or {}
        print(f"READING seed {seed} {args.control or 'served'}: ok "
              f"{rep['ok']} prefill_err {rep['prefill_rel_err']:.4f} "
              f"shortfall {rep['decode_worst_shortfall']:.4f} hit "
              f"{rep['hit_rel_err']:.4f} alternatives "
              f"{rep['alternatives']} faults {rep['faults']} | "
              f"{time.time() - t0:.0f}s peak "
              f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f}GB", flush=True)
        if args.margins:
            rng = np.random.default_rng(seed + 1)
            tokens = rng.integers(1, cfg.vocab_size, n + new).tolist()
            gaps += ref.margin_readings(params, cfg, tokens,
                                        list(range(n - 1, n + new)))
        del fl, params, rep
    if gaps:
        for what, values in (("expert", [a for a, _ in gaps]),
                             ("group", [b for _, b in gaps])):
            print(f"MARGIN {what} gap moved by", json.dumps(
                {p: float(np.percentile(values, p))
                 for p in (50, 90, 99, 100)}), f"({len(values)} readings)",
                flush=True)


if __name__ == "__main__":
    main()
