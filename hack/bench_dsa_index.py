#!/usr/bin/env python3
"""A decode layer's index scores alone, at the shapes of
``deepseek-v3.2-exp-ep16-l5`` (8 rows a step, a pool of 2,500 pages of 64 x
128 bfloat16 index keys a layer, 528 pages a row, 64 index heads):
milliseconds a layer at 1 x 8 k, 1 x 33 k, 2 x 20 k and 8 x 33 k live rows
of 8, of

- the decode step's form until PR 62: ``gather_index_keys`` (XLA's gather
  of all 8 x 528 pages into ``[8, 33792, 128]``, 69 MB whatever the rows
  hold) and ``dsa_index_scores`` over it; the chunk's callers keep it,
- ``sparse_index.dsa_index_scores_paged`` (the decode step's since): a live
  row's own pages streamed from the pool through its page table.

Before the times, the new form's scores against the old one's on this
device: bit for bit below every row's ``lens``, zeros from there on.

  chiprun -- python3 hack/bench_dsa_index.py       # one v5e, ~1 min
  python3 hack/bench_dsa_index.py --rehearse       # CPU, toy sizes, interpreted: the check alone
"""

from __future__ import annotations

import functools
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llmd_kv_cache_tpu.ops import sparse_index  # noqa: E402

LAYERS, POOL, PAGE, WIDTH, HEADS = 5, 2500, 64, 128, 64
ROWS, ROW_PAGES, CALLS = 8, 528, 100
REHEARSE = "--rehearse" in sys.argv[1:]
if REHEARSE:
    POOL, ROW_PAGES, HEADS = 80, 32, 4


def _gathered(q, w, idx, layer, table, lens, *, interpret=False):
    return sparse_index.dsa_index_scores(
        q, w, sparse_index.gather_index_keys(idx, layer, table), lens,
        interpret=interpret)


def _table(rng, lens):
    """Scattered pages, no two rows sharing one; page 0 is nobody's."""
    table = np.zeros((ROWS, ROW_PAGES), np.int32)
    free = rng.permutation(np.arange(1, POOL))
    at = 0
    for r, n in enumerate(lens):
        need = -(-n // PAGE)
        # 8 x 33 k asks more pages than the pool holds: rows then share.
        table[r, :need] = np.take(free, np.arange(at, at + need),
                                  mode="wrap")
        at += need
    return jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def main() -> None:
    if jax.devices()[0].platform != "tpu" and not REHEARSE:
        raise SystemExit("no TPU: the times are a chip's")
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    rng = np.random.default_rng(62)
    idx = jax.jit(lambda key: jax.random.normal(
        key, (LAYERS, POOL, 1, PAGE, WIDTH), jnp.bfloat16))(
            jax.random.PRNGKey(62))
    q = jax.random.normal(jax.random.PRNGKey(1), (ROWS, 1, HEADS, WIDTH),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(2), (ROWS, 1, HEADS),
                          jnp.float32)
    forms = {
        "gather_index_keys + dsa_index_scores (until PR 62)":
            functools.partial(_gathered, interpret=REHEARSE),
        "dsa_index_scores_paged": functools.partial(
            sparse_index.dsa_index_scores_paged, interpret=REHEARSE),
    }

    lens = [33000, 2049, 0, 1024, 33792, 20001, 8192, 65]
    if REHEARSE:
        lens = [700, 257, 0, 1024, 2048, 511, 1, 65]
    table, n = _table(rng, lens)
    old, new = (np.asarray(jax.jit(fn)(q, w, idx, 3, table, n))
                for fn in forms.values())
    same = all(np.array_equal(old[r, :, :k], new[r, :, :k])
               for r, k in enumerate(lens))
    zeros = all((new[r, :, k:] == 0).all() for r, k in enumerate(lens))
    print(f"dsa_index_scores_paged: below lens the gathered form's scores "
          f"bit for bit: {same}; zeros from lens on: {zeros}", flush=True)
    if not (same and zeros):
        raise SystemExit(1)
    if REHEARSE:
        return

    print(f"ms a layer ({CALLS} calls one after another in a program, each "
          "given its lens by the one before; 5 programs); live rows of 8 x "
          "keys a live row:", flush=True)
    shapes = [(1, 8192), (1, ROW_PAGES * PAGE), (2, 20000),
              (8, ROW_PAGES * PAGE)]

    def chained(fn):
        """``CALLS`` calls of ``fn`` in one program, a call's ``lens``
        hanging on a score of the one before (plus 0, which the compiler
        cannot know): none is dropped, hoisted or overlapped, and no
        result leaves the loop (``hack/bench_dsa_gather.py``)."""
        def program(idx, table, lens):
            def call(i, lens):
                got = fn(q, w, idx, i % LAYERS, table, lens)
                bits = jax.lax.bitcast_convert_type(got[0, 0, 0], jnp.int32)
                return lens + jnp.minimum(jnp.abs(bits), 0)

            return jax.lax.fori_loop(0, CALLS, call, lens)

        return jax.jit(program)

    for name, fn in forms.items():
        program = chained(fn)
        for live, keys in shapes:
            table, n = _table(rng, [keys] * live + [0] * (ROWS - live))
            jax.block_until_ready(program(idx, table, n))
            t0 = time.perf_counter()
            for _ in range(5):
                out = program(idx, table, n)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 5 / CALLS * 1e3
            print(f"  {name}: {live} x {keys}: {ms:.4f}", flush=True)


if __name__ == "__main__":
    main()
