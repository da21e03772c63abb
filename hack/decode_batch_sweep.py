#!/usr/bin/env python
"""Decode throughput vs batch and context at the bench's production
sizing (0.46 B params): engine decode in fused 32-token bursts.

Serves each (batch, ctx) point end-to-end through MiniEngine: admit
`batch` requests of `ctx` prompt tokens, decode 128 tokens each in
fused 32-token bursts. Two timed windows per point (r5 methodology
fix — the r4 single window started after ONE step, so at batch 32 the
other 31 interleaved prefills dominated it and the "decode tok/s"
number mostly measured prefill):

- e2e: first step -> all done (prefill interleave included; the
  serving-throughput view, comparable to the r4 numbers), and
- decode-only: clock starts once EVERY request has emitted its first
  token, so the window holds nothing but full-batch decode bursts —
  the number the kernel-level GB/s sweeps (mfu_probe --decode)
  predict.

`add_request` prefills synchronously at admission (unlike `enqueue`,
whose prefills are chunk-interleaved one request per step), so in
practice every request is prefilled before the first step() and the
two windows coincide — the printed live/done split at the decode-clock
start makes the window composition checkable from the log.

Usage (on a TPU, through the chip tool): python hack/decode_batch_sweep.py
"""

from __future__ import annotations

import time

import jax
import numpy as np

from llmd_kv_cache_tpu.models import engine as engine_mod
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params


def main():
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=16,
                      num_heads=16, num_kv_heads=8, head_dim=128,
                      intermediate_size=5632, page_size=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    max_new = 128
    print(f"device: {jax.devices()[0]}", flush=True)

    for batch, ctx in ((8, 64), (16, 64), (32, 64), (8, 2048), (32, 2048)):
        prompts = [rng.integers(1, 30000, ctx).tolist() for _ in range(batch)]
        pages_needed = batch * ((ctx + max_new) // 16 + 2)
        eng = engine_mod.MiniEngine(
            engine_mod.EngineConfig(
                model=cfg, num_pages=pages_needed + 64,
                max_pages_per_seq=(ctx + max_new) // 16 + 2,
                max_batch=batch, model_name="bench-decode",
                pod_identifier="p", decode_burst=32,
                max_prefill_tokens=2048,
            ),
            params=params, seed=0,
        )
        reqs = [eng.add_request(f"r{i}", p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        eng.step()  # compile + first prefills outside the timed window
        start = time.perf_counter()
        before = sum(len(r.output) for r in reqs)
        # Phase 1: run until every request has its first token — the
        # remaining prefills (and the decode bursts interleaving with
        # them) stay inside the e2e window only.
        while any(len(r.output) == 0 for r in reqs):
            eng.step()
        dec_start = time.perf_counter()
        dec_before = sum(len(r.output) for r in reqs)
        live = sum(1 for r in reqs if not r.done)
        # Phase 2: pure full-batch decode to completion.
        while not all(r.done for r in reqs):
            eng.step()
        end = time.perf_counter()
        toks = sum(len(r.output) for r in reqs) - before
        dec_toks = sum(len(r.output) for r in reqs) - dec_before
        dec_dt = end - dec_start
        print(f"0.46B decode b{batch:<3d} ctx{ctx:<5d} burst32: "
              f"e2e {toks / (end - start):7.0f} tok/s "
              f"({toks} toks in {end - start:.2f}s)   decode-only "
              f"{dec_toks / dec_dt:7.0f} tok/s "
              f"({dec_toks} toks in {dec_dt:.2f}s, "
              f"{dec_dt / (dec_toks / live) * 1e3:.2f} ms/step, "
              f"{live}/{batch} rows live at clock start)",
              flush=True)


if __name__ == "__main__":
    main()
