#!/usr/bin/env python3
"""A decode row's selection alone, at the shapes of
``deepseek-v3.2-exp-ep16-l5`` (8 rows a step, 528 pages x 64 = 33,792 score
slots a row, ``index_topk`` 2048): the parent's form (``jax.lax.top_k`` over
every row, which XLA lowers to a full sort; kept here as the yardstick),
``sparse_index.select_topk`` (one counting kernel, rows that keep every key
skipped) and the same counting in XLA with the positions from a cumulative
sum and a binary search a slot (the compaction form the kernel's 0/1
products were measured against; a first form of the kernel, one product
for each chunk of 128 keys, read 0.056 / 0.107 / 0.406 ms at 33 k keys and
was not kept: PERF.md section 6, PR 54), at 1, 2 and 8 live rows of 8 and
at 8 k / 16 k / 33 k keys a live row: milliseconds a layer. Before the times,
each form's set of positions against ``jax.lax.top_k``'s on this device,
on scores with many ties at the threshold and zeros of both signs.

  chiprun -- python3 hack/bench_dsa_select.py       # one v5e, ~1 min
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

ROWS, KEYS, TOPK, LAYERS = 8, 528 * 64, 2048, 10


def main() -> None:
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.ops import sparse_index

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the times are a chip's")

    def by_sort(scores, lens):
        """The parent's ``select_topk``."""
        pos = jnp.arange(scores.shape[-1])[None, :]
        short = (lens <= TOPK)[:, None]
        scores = jnp.where(short, -pos.astype(jnp.float32), scores)
        scores = jnp.where(pos < lens[:, None], scores, -jnp.inf)
        return jax.lax.top_k(scores, TOPK)[1].astype(jnp.int32)

    def by_count_xla(scores, lens):
        """Threshold by ``kth_largest``, slots by a cumulative sum, each
        slot's position by a binary search over it."""
        pos = jnp.arange(scores.shape[-1])[None, :]
        masked = jnp.where(pos < lens[:, None], scores, -jnp.inf)
        bits = sparse_index._ordered_bits(masked)
        thr = sparse_index.kth_largest(masked, TOPK)
        above, tie = bits > thr, bits == thr
        room = TOPK - above.sum(-1, keepdims=True)
        keep = above | (tie & (jnp.cumsum(tie, -1) - tie < room))
        slots = jnp.cumsum(keep, -1)
        picked = jax.vmap(lambda c: jnp.searchsorted(
            c, jnp.arange(1, TOPK + 1), side="left"))(slots)
        return jnp.where((lens <= TOPK)[:, None], jnp.arange(TOPK)[None],
                         picked).astype(jnp.int32)

    def by_kernel(scores, lens):
        return sparse_index.select_topk(scores, lens, TOPK)[0]

    forms = {"top_k (parent)": by_sort, "select_topk": by_kernel,
             "counted in XLA": by_count_xla}
    rng = np.random.default_rng(54)
    print(f"device {jax.devices()[0].device_kind}", flush=True)

    # What the platform's top_k does with ties and with zeros of both
    # signs, and that every form picks its set.
    x = -np.abs(rng.standard_normal((ROWS, KEYS)).astype(np.float32)) - 1.0
    order = rng.permutation(KEYS)
    x[:, order[:1000]] = 1.0 + rng.random(1000).astype(np.float32)
    x[:, order[1000:3000:2]] = 0.0
    x[:, order[1001:3000:2]] = -0.0
    lens = jnp.asarray([KEYS, KEYS - 37, 2049, 2048, 1, 0, 20000, 9000],
                       jnp.int32)
    want = np.asarray(jax.jit(by_sort)(jnp.asarray(x), lens))
    row = x[0, want[0]]
    print(f"top_k of 1000 positives, 1000 +0.0 and 1000 -0.0: "
          f"+0.0 picked {int(((row == 0) & ~np.signbit(row)).sum())}, "
          f"-0.0 picked {int(((row == 0) & np.signbit(row)).sum())}",
          flush=True)
    for name, fn in forms.items():
        got = np.asarray(jax.jit(fn)(jnp.asarray(x), lens))
        same = all(
            set(got[r, :n].tolist()) == set(want[r, :n].tolist())
            and len(set(got[r, :n].tolist())) == n
            for r, n in enumerate(np.minimum(np.asarray(lens), TOPK)))
        print(f"{name}: the sets are top_k's: {same}", flush=True)
    y = rng.standard_normal((ROWS, KEYS)).astype(np.float32)
    want = np.asarray(jax.jit(by_sort)(jnp.asarray(y), lens))
    got = np.asarray(jax.jit(by_kernel)(jnp.asarray(y), lens))
    print("select_topk on random scores: "
          f"{all(set(got[r].tolist()) == set(want[r].tolist()) for r in (0, 1, 2, 6, 7))}",
          flush=True)

    stack = jnp.asarray(rng.standard_normal((LAYERS, ROWS, KEYS)),
                        jnp.float32)
    print(f"ms a layer ({LAYERS} layers a program, 20 programs); "
          "live rows of 8 x keys a live row:", flush=True)
    for name, fn in forms.items():
        program = jax.jit(lambda s, n, fn=fn: sum(
            fn(s[i], n).sum() for i in range(LAYERS)))
        for keys in (8192, 16384, KEYS):
            for live in (1, 2, 8):
                n = jnp.asarray([keys] * live + [0] * (ROWS - live),
                                jnp.int32)
                jax.block_until_ready(program(stack, n))
                t0 = time.perf_counter()
                for _ in range(20):
                    out = program(stack, n)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / 20 / LAYERS * 1e3
                print(f"  {name}: {live} x {keys}: {ms:.4f}", flush=True)


if __name__ == "__main__":
    main()
