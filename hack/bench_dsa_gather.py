#!/usr/bin/env python3
"""A decode row's gather of its chosen latents alone, at the shapes of
``deepseek-v3.2-exp-ep16-l5`` (8 rows a step, a pool of 2,500 pages of 64 x
640 bfloat16 a layer, 528 pages a row, ``index_topk`` 2048): milliseconds a
layer at 1 x 8 k, 1 x 33 k, 2 x 20 k and 8 x 33 k live rows of 8, of

- today's function until PR 55 (``sparse_index._gather_by_index``: XLA's
  gather of all 8 x 2048 slots behind a 16,384-element look-up of their
  pages; kept as what runs off the chip, and here as the yardstick),
- ``sparse_index.gather_by_product`` (what runs on the chip: a live row's own
  pages streamed through VMEM and compacted by 0/1 products),
- one small copy a chosen row, the other form ISSUE 55 asked to be sized:
  as written (2048 copies of one 1,280 B cache row, pool to result) the
  chip's compiler refuses it, and this script prints its words: the pool's
  tiling is 8 rows x 128 lanes, and a copy may not cut a tile. What the
  tiling allows is kept here and not in the library: the 8 cache rows around
  each chosen one (10 KB a copy, 21 MB a live row whatever it holds) into
  VMEM, a tile of 128 slots at a time, and the same 0/1 product to pick
  one row in eight.

Before the times, each kernel's first ``count`` slots of every live row
against the yardstick's on this device, bit for bit.

  chiprun -- python3 hack/bench_dsa_gather.py       # one v5e, ~1 min
  python3 hack/bench_dsa_gather.py --rehearse       # CPU, toy sizes, interpreted: the check alone
"""

from __future__ import annotations

import functools
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llmd_kv_cache_tpu.ops import sparse_index  # noqa: E402

LAYERS, POOL, PAGE, WIDTH = 5, 2500, 64, 640
ROWS, ROW_PAGES, TOPK, CALLS = 8, 528, 2048, 100
TILE, GROUP = 128, 8
REHEARSE = "--rehearse" in sys.argv[1:]
if REHEARSE:
    POOL, WIDTH, ROW_PAGES, TOPK = 60, 128, 12, 256


def _row_copy_as_asked():
    """2048 copies of one cache row each, pool to result."""
    def kernel(table_ref, pos_ref, count_ref, layer_ref, k_hbm, o_hbm, sem):
        b = pl.program_id(0)

        def copy(s):
            p = pos_ref[b, s]
            return pltpu.make_async_copy(
                k_hbm.at[layer_ref[0], table_ref[b, p // PAGE], 0,
                         pl.ds(p % PAGE, 1), :],
                o_hbm.at[b * (TOPK // PAGE) + s // PAGE, 0,
                         pl.ds(s % PAGE, 1), :], sem.at[0])

        @pl.loop(0, count_ref[b])
        def _(s):
            copy(s).start()

        @pl.loop(0, count_ref[b])
        def _(s):
            copy(s).wait()

    def gather(k, layer, table, positions, count):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (ROWS * TOPK // PAGE, 1, PAGE, WIDTH), k.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(ROWS,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        )(table, positions, count, jnp.asarray(layer, jnp.int32).reshape(1),
          k)

    return gather


def _group_copy():
    """What the tiling allows of it: a chosen row's group of 8 into VMEM,
    128 slots a tile, twice buffered, then one product a tile."""
    tiles, per_tile = TOPK // TILE, TILE // PAGE

    def kernel(table_ref, pos_smem, count_ref, layer_ref, pos_ref, k_hbm,
               o_hbm, landed, chosen, slot_pos, sem):
        b = pl.program_id(0)
        count = count_ref[b]
        layer = layer_ref[0]
        pages = TOPK // PAGE
        i32 = jnp.int32

        def iota(shape, dim):
            return jax.lax.broadcasted_iota(i32, shape, dim)

        slot = iota((tiles, TILE), 0) * TILE + iota((tiles, TILE), 1)
        pos = jnp.where(slot < count, pos_ref[0], -1)
        leading = jnp.max(pos) == count - 1

        def page_copy(j):
            return pltpu.make_async_copy(
                k_hbm.at[layer, table_ref[b, j], 0],
                o_hbm.at[b * pages + j, 0], sem.at[0])

        @pl.when((count > 0) & leading)
        def _():
            whole = (count + PAGE - 1) // PAGE

            @pl.loop(0, whole)
            def _(j):
                page_copy(j).start()

            @pl.loop(0, whole)
            def _(j):
                page_copy(j).wait()

        @pl.when((count > 0) & ~leading)
        def _():
            def start(t, buf):
                @pl.loop(0, TILE)
                def _(i):
                    # A slot past ``count`` fetches the row's key 0: finite,
                    # and multiplied by 0.
                    s = t * TILE + i
                    p = jnp.where(s < count, pos_smem[b, s], 0)
                    pltpu.make_async_copy(
                        k_hbm.at[layer, table_ref[b, p // PAGE], 0,
                                 pl.ds(pl.multiple_of(
                                     p % PAGE // GROUP * GROUP, GROUP),
                                     GROUP), :],
                        landed.at[buf, pl.ds(pl.multiple_of(
                            i * GROUP, GROUP), GROUP), :],
                        sem.at[buf]).start()

            start(0, 0)
            for t in range(tiles):
                slot_pos[t * TILE:(t + 1) * TILE, :] = jnp.transpose(
                    jnp.broadcast_to(pos[t:t + 1], (TILE, TILE)))
            line = iota((TILE, TILE * GROUP), 0)
            lane = iota((TILE, TILE * GROUP), 1)

            @pl.loop(0, tiles)
            def _(t):
                buf = t % 2

                @pl.when(t + 1 < tiles)
                def _():
                    start(t + 1, 1 - buf)

                # All 128 copies of the tile: as many bytes as the buffer.
                pltpu.make_async_copy(landed.at[buf], landed.at[buf],
                                      sem.at[buf]).wait()
                at = slot_pos[pl.ds(pl.multiple_of(t * TILE, TILE), TILE),
                              :][:, :1]
                is_key = (at >= 0) & (lane == line * GROUP + at % GROUP)
                got = jnp.dot(is_key.astype(landed.dtype), landed[buf],
                              preferred_element_type=jnp.float32)
                chosen[pl.ds(t * per_tile, per_tile)] = got.astype(
                    chosen.dtype).reshape(per_tile, PAGE, WIDTH)

            out = pltpu.make_async_copy(
                chosen, o_hbm.at[pl.ds(b * pages, pages), 0], sem.at[0])
            out.start()
            out.wait()

    def gather(k, layer, table, positions, count):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (ROWS * TOPK // PAGE, 1, PAGE, WIDTH), k.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(ROWS,),
                in_specs=[pl.BlockSpec((1, tiles, TILE),
                                       lambda b, *_p: (b, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[
                    pltpu.VMEM((2, TILE * GROUP, WIDTH), k.dtype),
                    pltpu.VMEM((TOPK // PAGE, PAGE, WIDTH), k.dtype),
                    pltpu.VMEM((TOPK, TILE), jnp.int32),
                    pltpu.SemaphoreType.DMA((2,))]),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 * 2 ** 20),
            interpret=REHEARSE,
        )(table, positions, count, jnp.asarray(layer, jnp.int32).reshape(1),
          positions.reshape(ROWS, tiles, TILE), k)

    return gather


def _inputs(rng, lens):
    """A page table of scattered pages and ascending positions, as
    ``select_topk`` leaves them: a row of at most TOPK keys its first."""
    table = np.zeros((ROWS, ROW_PAGES), np.int32)
    positions = np.tile(np.arange(TOPK, dtype=np.int32), (ROWS, 1))
    for r, n in enumerate(lens):
        need = -(-n // PAGE)
        table[r, :need] = rng.choice(np.arange(1, POOL), need, replace=False)
        if n > TOPK:
            positions[r] = np.sort(rng.choice(n, TOPK, replace=False))
    count = np.minimum(np.asarray(lens), TOPK).astype(np.int32)
    return jnp.asarray(table), jnp.asarray(positions), jnp.asarray(count)


def main() -> None:
    if jax.devices()[0].platform != "tpu" and not REHEARSE:
        raise SystemExit("no TPU: the times are a chip's")
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    rng = np.random.default_rng(55)
    k = jax.jit(lambda key: jax.random.normal(
        key, (LAYERS, POOL, 1, PAGE, WIDTH), jnp.bfloat16))(
            jax.random.PRNGKey(55))
    forms = {
        "XLA gather (until PR 55)": sparse_index._gather_by_index,
        "gather_by_product": functools.partial(
            sparse_index.gather_by_product, interpret=REHEARSE),
        "a copy a chosen row's group of 8": _group_copy(),
    }

    lens = [33000, 2049, 2048, 1000, 0, 20001, 8192, 64]
    if REHEARSE:
        lens = [700, 257, 256, 100, 0, 511, 300, 64]
    table, positions, count = _inputs(rng, lens)
    try:
        if REHEARSE:
            raise RuntimeError("not asked of the interpreter, which has no "
                               "tiling to align to")
        jax.block_until_ready(jax.jit(_row_copy_as_asked())(
            k, 3, table, positions, count))
        print("a copy a chosen row, as asked: ran", flush=True)
    except Exception as e:  # the compiler's refusal is the finding
        said = [line for line in str(e).splitlines() if "align" in line]
        print("a copy a chosen row, as asked: refused: "
              f"{(said or [str(e)[:200]])[0].strip()}", flush=True)
    want = np.asarray(jax.jit(sparse_index._gather_by_index)(
        k, 3, table, positions, count).astype(jnp.float32)).reshape(
            ROWS, TOPK, WIDTH)
    for name, fn in list(forms.items())[1:]:
        got = np.asarray(jax.jit(fn)(k, 3, table, positions, count).astype(
            jnp.float32)).reshape(ROWS, TOPK, WIDTH)
        same = all(np.array_equal(got[r, :n], want[r, :n])
                   for r, n in enumerate(np.asarray(count)))
        print(f"{name}: a live row's first count slots are the gather's: "
              f"{same}", flush=True)
    if REHEARSE:
        return

    print(f"ms a layer ({CALLS} calls one after another in a program, each "
          "given its count by the one before; 5 programs); live rows of 8 x "
          "keys a live row:", flush=True)
    shapes = [(1, 8192), (1, ROW_PAGES * PAGE), (2, 20000),
              (8, ROW_PAGES * PAGE)]

    def chained(fn):
        """``CALLS`` calls of ``fn`` in one program. A call's ``count``
        hangs on an element of the result before it (plus 0, which the
        compiler cannot know), so none is dropped, hoisted or overlapped,
        and no result leaves the loop: a program of calls that each return
        21 MB read 0.087 ms a call with a kernel that does nothing."""
        def program(k, table, positions, count):
            def call(i, count):
                got = fn(k, i % LAYERS, table, positions[i % len(positions)],
                         count)
                bits = jax.lax.bitcast_convert_type(got[0, 0, 0, 0],
                                                    jnp.uint16)
                return count + jnp.minimum(bits.astype(jnp.int32), 0)

            return jax.lax.fori_loop(0, CALLS, call, count)

        return jax.jit(program)

    for name, fn in forms.items():
        program = chained(fn)
        for live, keys in shapes:
            drawn = [_inputs(rng, [keys] * live + [0] * (ROWS - live))
                     for _ in range(4)]
            table, count = drawn[0][0], drawn[0][2]
            positions = jnp.stack([d[1] for d in drawn])
            jax.block_until_ready(program(k, table, positions, count))
            t0 = time.perf_counter()
            for _ in range(5):
                out = program(k, table, positions, count)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 5 / CALLS * 1e3
            print(f"  {name}: {live} x {keys}: {ms:.4f}", flush=True)


if __name__ == "__main__":
    main()
