#!/usr/bin/env python3
"""One latent-attention layer's prefill attention, the absorbed form against
the per-head form (``ops/pallas_latent_prefill.py``), on the chip: 256 and
512 queries x 11,264 and 25,088 keys at the published widths of the two
latent configurations (latent 512 + rope 64 in pages of 640 lanes, heads of
128; 128 heads under a random 2048-of-n selection, 64 heads without one),
outputs compared. A builder's tool and the go-ahead measurement of a kernel
PR on this path; no cell of the benchmark runs it.

    chiprun -- python3 hack/bench_mla_prefill.py
    JAX_PLATFORMS=cpu python3 hack/bench_mla_prefill.py --rehearse

Each form is timed whole, as a layer pays for it: the absorbed form with its
``q @ W_UK^T`` in front and ``@ W_UV`` behind, the per-head form with the
transposes its wrapper makes. A line a case, as JSON, and all of them in
``chiprun_out/bench_mla_prefill.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llmd_kv_cache_tpu.ops import sparse_index  # noqa: E402
from llmd_kv_cache_tpu.ops.pallas_latent_prefill import (  # noqa: E402
    pallas_per_head_prefill_attention, per_head_min_queries)
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (  # noqa: E402
    pallas_paged_prefill_attention)

LAYERS, LAYER = 2, 1


def build(key, *, heads, q_seq, keys, rank, rope, pad, nope, page,
          row_pages, topk):
    """A row of ``keys`` tokens whose last ``q_seq`` are the chunk."""
    ks = jax.random.split(key, 6)
    width = rank + rope + pad
    dt = jnp.bfloat16
    latent = jax.random.normal(ks[0], (row_pages * page, width), dt)
    latent = latent.at[:, rank + rope:].set(0)
    pages = jnp.zeros((LAYERS, row_pages + 1, 1, page, width), dt)
    pages = pages.at[LAYER, 1:, 0].set(latent.reshape(row_pages, page, width))
    case = {
        "q_nope": jax.random.normal(ks[1], (1, q_seq, heads, nope), dt),
        "q_rope": jax.random.normal(ks[2], (1, q_seq, heads, rope), dt),
        "w_uk": jax.random.normal(ks[3], (heads, rank, nope), dt)
        * rank ** -0.5,
        "w_uv": jax.random.normal(ks[4], (heads, rank, nope), dt)
        * rank ** -0.5,
        "pages": pages,
        "table": 1 + jnp.arange(row_pages, dtype=jnp.int32)[None, :],
        "ctx": jnp.asarray([keys - q_seq], jnp.int32),
        "total": jnp.asarray([keys], jnp.int32),
    }
    bias = None
    if topk:
        scores = jax.random.normal(ks[5], (1, q_seq, row_pages * page),
                                   jnp.float32)
        positions = case["ctx"][:, None] + jnp.arange(q_seq)[None, :]
        bias = sparse_index.dsa_keep_bias(
            scores, positions, case["total"], topk=topk,
            interpret=jax.default_backend() != "tpu")
    return case, bias


def forms(scale, rank, pad, interpret):
    def absorbed(c, bias):
        q_lat = jnp.einsum("bshd,hrd->bshr", c["q_nope"], c["w_uk"])
        q = jnp.concatenate([q_lat, c["q_rope"]], axis=-1)
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad)])
        q = q * (q.shape[-1] ** 0.5 * scale)
        ctx = pallas_paged_prefill_attention(
            q, c["pages"], c["pages"], c["table"], c["ctx"], c["total"],
            q_tile=16, shared_kv=True, layer_idx=LAYER, bias=bias,
            interpret=interpret)
        return jnp.einsum("bshr,hrv->bshv", ctx[..., :rank], c["w_uv"])

    def per_head(c, bias):
        return pallas_per_head_prefill_attention(
            c["q_nope"], c["q_rope"], c["w_uk"], c["w_uv"], c["pages"],
            c["table"], c["ctx"], c["total"], scale=scale, layer_idx=LAYER,
            bias=bias, interpret=interpret)

    return jax.jit(absorbed), jax.jit(per_head)


def timed(fn, *args, reps):
    out = jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - start) / reps * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths through the interpreter (the CPU): "
                         "the outputs' comparison only, no time means "
                         "anything")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=47)
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.rehearse):
        print("no TPU: run through chiprun, or with --rehearse",
              file=sys.stderr)
        return 1
    if args.rehearse:
        widths = dict(rank=32, rope=16, pad=16, nope=16, page=16)
        cases = [(4, 24, 256, 560, 40), (2, 0, 256, 400, 40)]
        reps = 1
    else:
        widths = dict(rank=512, rope=64, pad=64, nope=128, page=64)
        cases = [(heads, topk, q_seq, keys, 528)
                 for heads, topk in ((128, 2048), (64, 0))
                 for q_seq in (256, 512) for keys in (11_264, 25_088)]
        reps = args.reps
    scale = (widths["nope"] + widths["rope"]) ** -0.5
    absorbed, per_head = forms(scale, widths["rank"], widths["pad"],
                               interpret=not on_chip)
    least = per_head_min_queries(
        sum(widths[k] for k in ("rank", "rope", "pad")), widths["rank"],
        widths["nope"], widths["nope"])
    lines = []
    for i, (heads, topk, q_seq, keys, row_pages) in enumerate(cases):
        case, bias = build(jax.random.PRNGKey(args.seed + i), heads=heads,
                           q_seq=q_seq, keys=keys, row_pages=row_pages,
                           topk=topk, **widths)
        a, a_ms = timed(absorbed, case, bias, reps=reps)
        p, p_ms = timed(per_head, case, bias, reps=reps)
        a, p = np.asarray(a, np.float32), np.asarray(p, np.float32)
        line = {
            "device": jax.devices()[0].device_kind, "heads": heads,
            "topk": topk, "queries": q_seq, "keys": keys,
            "min_queries": least,
            "absorbed_ms": round(a_ms, 3), "per_head_ms": round(p_ms, 3),
            "speedup": round(a_ms / p_ms, 3),
            "rel_err": float(np.abs(a - p).max() / np.abs(a).max()),
        }
        if not on_chip:
            # The interpreter's times say nothing about the chip.
            for k in ("absorbed_ms", "per_head_ms", "speedup"):
                line[k] = None
        print(json.dumps(line), flush=True)
        lines.append(line)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "bench_mla_prefill.json").write_text(json.dumps(lines, indent=1))
    return 0 if all(line["rel_err"] < 0.05 for line in lines) else 2


if __name__ == "__main__":
    sys.exit(main())
