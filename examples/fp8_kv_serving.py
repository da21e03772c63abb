#!/usr/bin/env python
"""fp8 (e4m3) KV-cache serving: half the KV bytes, same engine seams.

Serves the same prompts through a bf16-cache and an fp8-cache engine
(`EngineConfig.kv_cache_dtype="f8_e4m3"`) sharing one parameter tree,
then prints the pool byte accounting and the token agreement. On a TPU
the fp8 engine's decode rides the merged flash kernel's quantized arm
(flat whole-page 1-byte DMAs) — the measured lever for the
attention-bandwidth-bound long-context shapes (ROADMAP S1, D4);
on CPU this demo exercises the identical code paths via XLA attention.

Usage:
  PYTHONPATH=. python examples/fp8_kv_serving.py
"""

from __future__ import annotations

import numpy as np

import jax

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params


def cache_bytes(eng) -> int:
    total = eng.k_cache.size * eng.k_cache.dtype.itemsize
    total += eng.v_cache.size * eng.v_cache.dtype.itemsize
    return total


def main() -> None:
    cfg = LlamaConfig(vocab_size=4096, hidden_size=256, num_layers=4,
                      num_heads=8, num_kv_heads=4, head_dim=128,
                      intermediate_size=704, page_size=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 4000, 96).tolist() for _ in range(2)]

    engines = {}
    for dtype in ("bf16", "f8_e4m3"):
        engines[dtype] = MiniEngine(
            EngineConfig(model=cfg, num_pages=128, max_pages_per_seq=16,
                         model_name="fp8-demo", pod_identifier=f"pod-{dtype}",
                         kv_cache_dtype=dtype),
            params=params, seed=0)

    outs = {}
    for dtype, eng in engines.items():
        outs[dtype] = [eng.generate(f"r{i}", p, max_new_tokens=16)
                       for i, p in enumerate(prompts)]
        print(f"{dtype:>8s}: pool {cache_bytes(eng) / 1e6:6.2f} MB "
              f"({eng.k_cache.dtype})")

    agree = sum(
        a == b for pa, pb in zip(outs["bf16"], outs["f8_e4m3"])
        for a, b in zip(pa, pb))
    total = sum(len(p) for p in outs["bf16"])
    ratio = cache_bytes(engines["bf16"]) / cache_bytes(engines["f8_e4m3"])
    print(f"KV pool bytes: {ratio:.1f}x smaller under fp8")
    print(f"greedy tokens agree {agree}/{total} "
          f"(fp8 quantization may legitimately flip near-tie logits)")
    assert ratio > 1.9
    print("OK")


if __name__ == "__main__":
    main()
