"""Learned sparse attention's lightning indexer over a paged cache.

A model with an indexer (DeepSeek-V3.2's DSA) caches a second, narrow
stream per token beside its attention stream: the indexer's key, in the
same pages under the same page ids. A query scores every cached key of its
own row with a few light heads,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s]),

keeps the ``topk`` best positions ``s <= t`` (all of them while there are
no more than ``topk``) and attends those only. Three pieces, shared by the
XLA and the Pallas step programs:

- ``index_scores``: the row's pages of the index stream gathered into
  logical order (a page is the pool's own unit, so the gather moves whole
  16 KiB pages) and scored by one kernel, ``dsa_index_scores``, which reads
  ``lens`` keys of each row and no more; ``index_scores_xla`` is the same
  function in ``jax.numpy`` for the XLA programs.
- ``kth_largest`` / ``keep_mask``: exact selection as a threshold, by
  bisection on the scores' bits: what a query keeps is ``score >= its
  topk-th largest``. Keys tied with the topk-th are all kept (with real
  scores a tie is a key whose 64 heads all score below zero, twice).
  A prefill chunk masks dense latent attention with it, as the model's own
  prefill does. ``dsa_keep_bias`` is the same selection as one kernel, for
  the Pallas prefill program: a tile of queries reads its live scores once
  and bisects on the copy in VMEM, where ``kth_largest`` passes 32 times
  over the whole padded width in HBM.
- ``select_topk``: the positions themselves, exact (``jax.lax.top_k``;
  ``approx_max_k`` would be another model), for a decode row, which then
  gathers the selected latents and attends them alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A selection bias: this entry drops its key, 0 keeps it (what
# ``pallas_paged_prefill_attention(bias=)`` reads).
DROPPED = -1e30

# The scoring kernel's name as a device trace has it (its jitted wrapper's
# ``__name__``, as ``ops.pallas_paged_attention`` names its kernels).
KERNEL_INDEX = "dsa_index_scores"
KERNEL_KEEP = "dsa_keep_bias"


def gather_index_keys(idx_stack: jax.Array, layer_idx, page_table: jax.Array
                      ) -> jax.Array:
    """A row's index keys in logical order: ``[batch, pages_per_seq *
    page_size, width]`` from the ``[layers, pages, 1, page_size, width]``
    stack. Slots past a row's pages name the garbage page; the caller masks
    by position."""
    batch, pages = page_table.shape
    got = idx_stack[layer_idx, page_table]  # [b, pages, 1, page_size, w]
    return got.reshape(batch, pages * got.shape[-2], got.shape[-1])


def index_scores_xla(q_idx: jax.Array, w_idx: jax.Array, keys: jax.Array
                     ) -> jax.Array:
    """``I`` as float32 ``[batch, q_seq, keys]``: ``q_idx [batch, q_seq,
    heads, width]``, ``w_idx [batch, q_seq, heads]`` float32, ``keys
    [batch, keys, width]``."""
    dots = jnp.einsum("bqhd,bkd->bqhk", q_idx, keys.astype(q_idx.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bqhk,bqh->bqk", jnp.maximum(dots, 0.0),
                      w_idx.astype(jnp.float32))


def _index_kernel(lens_ref, q_ref, w_ref, k_ref, o_ref, *, tq, heads, tk):
    b, kb = pl.program_id(0), pl.program_id(2)

    @pl.when(kb * tk < lens_ref[b])
    def _():
        dots = jax.lax.dot_general(
            q_ref[0], k_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [tq * heads, tk]
        dots = jnp.maximum(dots, 0.0) * w_ref[0]
        for i in range(tq):
            o_ref[0, i:i + 1, :] = jnp.sum(
                dots[i * heads:(i + 1) * heads], axis=0, keepdims=True)

    @pl.when(kb * tk >= lens_ref[b])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _tile(n: int, target: int) -> int:
    """The largest power of two up to ``target`` that divides ``n``."""
    t = 1
    while t * 2 <= target and n % (t * 2) == 0:
        t *= 2
    return t


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores(q_idx: jax.Array, w_idx: jax.Array, keys: jax.Array,
                     lens: jax.Array, *, interpret: bool = False
                     ) -> jax.Array:
    """``index_scores_xla`` as one kernel that reads ``lens[b]`` keys of
    row ``b`` (rounded up to a block) and writes 0 past them: grid (row,
    query tile, key block), a ``[tq * heads, width] x [width, tk]`` matmul,
    ReLU, the heads' weights and the sum over heads per program. A key
    block past a row's ``lens`` is not fetched again (its index map names
    the row's last live block) and not scored."""
    batch, q_seq, heads, width = q_idx.shape
    n_keys = keys.shape[1]
    tq = _tile(q_seq, 16)
    tk = _tile(n_keys, 1024)
    q2 = q_idx.reshape(batch, q_seq * heads, width)
    w2 = w_idx.astype(jnp.float32).reshape(batch, q_seq * heads, 1)

    def key_block(b, qt, kb, lens_ref):
        last = jnp.maximum((lens_ref[b] + tk - 1) // tk - 1, 0)
        return (b, jnp.minimum(kb, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, q_seq // tq, n_keys // tk),
        in_specs=[
            pl.BlockSpec((1, tq * heads, width),
                         lambda b, qt, kb, *_p: (b, qt, 0)),
            pl.BlockSpec((1, tq * heads, 1),
                         lambda b, qt, kb, *_p: (b, qt, 0)),
            pl.BlockSpec((1, tk, width), key_block),
        ],
        out_specs=pl.BlockSpec((1, tq, tk),
                               lambda b, qt, kb, *_p: (b, qt, kb)),
    )
    return pl.pallas_call(
        functools.partial(_index_kernel, tq=tq, heads=heads, tk=tk),
        out_shape=jax.ShapeDtypeStruct((batch, q_seq, n_keys), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(lens.astype(jnp.int32), q2, w2, keys.astype(q_idx.dtype))


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 in the same order (-inf lowest, +inf highest)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = bits >> 31 == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(scores: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest of each row of float32 ``scores [..., n]``, as
    ordered bits ``[..., 1]`` (``_ordered_bits``), exactly: bit by bit from
    the top, the largest threshold that still leaves ``k`` scores at or
    above it. 32 passes over the scores, each a compare and a count; a
    sort of every query's row costs some hundred. A row with fewer than
    ``k`` finite scores gets a threshold at or below its ``-inf``s."""
    bits = _ordered_bits(scores)

    def step(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - jnp.asarray(i, jnp.uint32)))
        enough = jnp.sum(bits >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, thr)

    return jax.lax.fori_loop(
        0, 32, step, jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))


def keep_mask(scores: jax.Array, q_positions: jax.Array,
              total_lens: jax.Array, topk: int) -> jax.Array:
    """Which keys each query attends: bool ``[batch, q_seq, keys]``. Key
    ``s`` is a candidate of the query at position ``t`` where ``s <= t``
    and ``s < total_lens``; of its candidates a query keeps those scoring
    at least its ``topk``-th largest (all, while it has no more)."""
    pos = jnp.arange(scores.shape[-1])[None, None, :]
    cand = (pos <= q_positions[:, :, None]) & (
        pos < total_lens[:, None, None])
    scores = jnp.where(cand, scores, -jnp.inf)
    if scores.shape[-1] <= topk:
        return cand
    return cand & (_ordered_bits(scores) >= kth_largest(scores, topk))


# Float bits as int32 in the floats' order: ``_ordered_bits`` with the top
# bit flipped, so that signed compares order them. -inf's, which a key that
# is no candidate of its query counts as (``keep_mask`` masks to -inf):
_MASKED = (0xFF800000 ^ 0x7FFFFFFF) - 2 ** 32


def _keep_tiles(q_seq: int, n_keys: int) -> tuple[int, int]:
    """``dsa_keep_bias``'s tile: (queries a program, keys a block)."""
    return _tile(q_seq, 16), _tile(n_keys, 1024)


def _keep_kernel(qpos_ref, lens_ref, s_hbm, o_ref, landed, buf, sem, *, tq,
                 tk, topk, q_seq):
    # qpos_ref [rows * q_seq], lens_ref [rows] (SMEM); s_hbm the scores
    # [rows, q_seq, keys], left in HBM; o_ref [1, tq, keys]; landed and buf
    # [keys // tk, tq, tk], the tile's live blocks in order: as float32
    # they arrive, as ordered int32 they are counted.
    b, qt = pl.program_id(0), pl.program_id(1)
    total = lens_ref[b]
    first = b * q_seq + qt * tq
    row = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    last, q_pos = qpos_ref[first], jnp.zeros((tq, 1), jnp.int32)
    for i in range(tq):
        at = qpos_ref[first + i]
        last = jnp.maximum(last, at)
        q_pos = jnp.where(row == i, at, q_pos)
    # The tile's candidates lie in [0, reach): whole blocks up to there are
    # copied in and counted. No query of a tile that reaches ``topk`` keys
    # at most drops one: nothing is copied or counted for it, its threshold
    # stays below every key and what ``buf`` holds does not matter.
    reach = jnp.minimum(last, total - 1) + 1
    live = (reach + tk - 1) // tk
    counted = jnp.where(reach > topk, live, 0)
    lane = min(tk, 128)

    def copy(j):
        return pltpu.make_async_copy(
            s_hbm.at[b, pl.ds(qt * tq, tq),
                     pl.ds(pl.multiple_of(j * tk, tk), tk)],
            landed.at[j], sem.at[0])

    def candidates(j):
        k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        return (k_pos <= q_pos) & (k_pos < total)

    @pl.loop(0, counted)
    def _(j):
        copy(j).start()

    @pl.loop(0, counted)
    def _(j):
        copy(j).wait()
        bits = jax.lax.bitcast_convert_type(landed[j], jnp.int32)
        ordered = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        buf[j] = jnp.where(candidates(j), ordered, _MASKED)

    def step(i, thr):
        # ``kth_largest``'s step on signed keys: or-ing a bit into the
        # unsigned threshold is flipping it here (the top one from set).
        trial = thr ^ (jnp.int32(1) << (31 - i))
        wide = jnp.broadcast_to(trial, (tq, lane))

        def count(j, acc):
            block = buf[j]
            for c in range(tk // lane):
                acc = acc + (block[:, c * lane:(c + 1) * lane] >= wide
                             ).astype(jnp.int32)
            return acc

        acc = jax.lax.fori_loop(0, counted, count,
                                jnp.zeros((tq, lane), jnp.int32))
        enough = jnp.sum(acc, axis=1, keepdims=True) >= topk
        return jnp.where(enough, trial, thr)

    thr = jax.lax.fori_loop(0, jnp.where(counted > 0, 32, 0), step,
                            jnp.full((tq, 1), jnp.iinfo(jnp.int32).min,
                                     jnp.int32))

    @pl.loop(0, live)
    def _(j):
        keep = candidates(j) & (buf[j] >= thr)
        o_ref[0, :, pl.ds(pl.multiple_of(j * tk, tk), tk)] = jnp.where(
            keep, 0.0, DROPPED)

    @pl.loop(live, o_ref.shape[2] // tk)
    def _(j):
        o_ref[0, :, pl.ds(pl.multiple_of(j * tk, tk), tk)] = jnp.full(
            (tq, tk), DROPPED, jnp.float32)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def dsa_keep_bias(scores: jax.Array, q_positions: jax.Array,
                  total_lens: jax.Array, *, topk: int,
                  interpret: bool = False) -> jax.Array:
    """``jnp.where(keep_mask(...), 0.0, DROPPED)`` as one kernel: the
    float32 bias ``[batch, q_seq, keys]`` that the prefill attention kernel
    reads, the same selection bit for bit (ties with the ``topk``-th kept,
    a query of at most ``topk`` candidates keeps them all).

    Grid (row, query tile). A program copies its tile's scores from HBM
    once, in blocks of keys and only as far as the tile's last candidate
    (``min(its highest q_position, total_len - 1)``), masks and orders them
    as ``keep_mask`` and ``_ordered_bits`` do, and runs ``kth_largest``'s 32
    steps on the copy in VMEM: counts add up lane-wise and are reduced
    across lanes once a step. A tile whose reach is at most ``topk`` keys
    copies and counts nothing. Then one pass writes the bias: ``DROPPED``
    past the tile's reach too, where the attention kernel never looks."""
    batch, q_seq, n_keys = scores.shape
    tq, tk = _keep_tiles(q_seq, n_keys)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, q_seq // tq),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tq, n_keys),
                               lambda b, qt, *_p: (b, qt, 0)),
        scratch_shapes=[pltpu.VMEM((n_keys // tk, tq, tk), jnp.float32),
                        pltpu.VMEM((n_keys // tk, tq, tk), jnp.int32),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        functools.partial(_keep_kernel, tq=tq, tk=tk, topk=topk,
                          q_seq=q_seq),
        out_shape=jax.ShapeDtypeStruct((batch, q_seq, n_keys), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(q_positions.astype(jnp.int32).reshape(-1),
      total_lens.astype(jnp.int32), scores.astype(jnp.float32))


def threshold_keys(ctx_len: int, new_len: int, q_seq: int, n_keys: int,
                   topk: int) -> int:
    """The scores ``dsa_keep_bias`` counts a layer for one row's chunk of
    ``q_seq`` queries (``new_len`` of them real) behind ``ctx_len`` cached
    tokens: over its query tiles, queries x the keys of the blocks copied
    in. ``kth_largest`` counts ``q_seq * n_keys``."""
    tq, tk = _keep_tiles(q_seq, n_keys)
    counted = 0
    for start in range(0, q_seq, tq):
        reach = min(ctx_len + start + tq - 1, ctx_len + new_len - 1) + 1
        if reach > topk:
            counted += tq * -(-reach // tk) * tk
    return counted


def select_topk(scores: jax.Array, total_lens: jax.Array, topk: int
                ) -> tuple[jax.Array, jax.Array]:
    """A decode row's selection: ``(positions [batch, topk], count
    [batch])``. The first ``count = min(total_lens, topk)`` positions are
    the row's ``topk`` best-scoring keys among its ``total_lens`` (in score
    order; attention does not care); a row of at most ``topk`` keys gets
    all of them whatever ``scores`` holds for it."""
    pos = jnp.arange(scores.shape[-1])[None, :]
    short = (total_lens <= topk)[:, None]
    scores = jnp.where(short, -pos.astype(jnp.float32), scores)
    scores = jnp.where(pos < total_lens[:, None], scores, -jnp.inf)
    _, picked = jax.lax.top_k(scores, topk)
    return picked.astype(jnp.int32), jnp.minimum(total_lens, topk)


def gather_selected(k_stack: jax.Array, layer_idx, page_table: jax.Array,
                    positions: jax.Array) -> jax.Array:
    """The cache rows at ``positions [batch, n]`` of each row's own pages,
    as a pool of their own: ``[batch * pages, 1, page_size, width]`` with
    ``pages = ceil(n / page_size)`` pages a row, row ``b``'s at ``b *
    pages``. The decode kernel then streams them as it streams any pool."""
    batch, n = positions.shape
    page_size, width = k_stack.shape[-2:]
    pages = -(-n // page_size)
    if pages * page_size != n:
        positions = jnp.pad(positions, [(0, 0), (0, pages * page_size - n)])
    page = jnp.take_along_axis(
        page_table, jnp.minimum(positions // page_size,
                                page_table.shape[1] - 1), axis=1)
    rows = k_stack[layer_idx, page, 0, positions % page_size]  # [b, n, w]
    return rows.reshape(batch * pages, 1, page_size, width)
