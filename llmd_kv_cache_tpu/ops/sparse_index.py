"""Learned sparse attention's lightning indexer over a paged cache.

A model with an indexer (DeepSeek-V3.2's DSA) caches a second, narrow
stream per token beside its attention stream: the indexer's key, in the
same pages under the same page ids. A query scores every cached key of its
own row with a few light heads,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s]),

keeps the ``topk`` best positions ``s <= t`` (all of them while there are
no more than ``topk``) and attends those only. Four pieces; the first three
are shared by the XLA and the Pallas step programs, the last is the Pallas
decode program's:

- ``index_scores``: the row's pages of the index stream gathered into
  logical order (a page is the pool's own unit, so the gather moves whole
  16 KiB pages) and scored by one kernel, ``dsa_index_scores``, which reads
  ``lens`` keys of each row and no more; ``index_scores_xla`` is the same
  function in ``jax.numpy`` for the XLA programs. A chunk's one row is
  scored so. A decode step, which has the pool, the rows' page table and
  one live row in eight, scores the keys where they lie
  (``dsa_index_scores_paged``: the same products over a live row's own
  pages, streamed from the pool through its line of the page table): the
  gather wrote 69 MB a layer for eight padded rows whatever they held.
- ``kth_largest`` / ``keep_mask``: exact selection as a threshold, by
  bisection on the scores' bits: what a query keeps is ``score >= its
  topk-th largest``. Keys tied with the topk-th are all kept (with real
  scores a tie is a key whose 64 heads all score below zero, twice).
  A prefill chunk masks dense latent attention with it, as the model's own
  prefill does. ``dsa_keep_bias`` is the same selection as one kernel, for
  the Pallas prefill program: a tile of queries reads its live scores once
  and bisects on the copy in VMEM, where ``kth_largest`` passes 32 times
  over the whole padded width in HBM.
- ``select_topk``: the positions themselves, for a decode row, which then
  gathers the selected latents and attends them alone. Exact, and by
  counting as well (``approx_max_k`` would be another model, and a sort of
  every row's 33,792 slots was a seventh of a decode step): one kernel,
  ``topk_by_count``, bisects a row's threshold on a copy of its live scores
  in VMEM, breaks ties at the threshold by position as ``jax.lax.top_k``
  does, and finds every slot's key from prefix sums of what is kept, all
  of it 0/1 products on the MXU. Positions come out ascending. A
  row of at most ``topk`` keys, every padded row among them, costs
  nothing.
- ``gather_selected``: the chosen latents as a pool of their own, which the
  decode kernel then streams as it streams any pool. On a TPU one kernel,
  ``gather_by_product``, that reads ``count`` of each row: a padded row
  fetches nothing, a row that keeps its first ``count`` keys has those
  pages copied whole, and a row that chose among more streams its own
  pages through VMEM, each looked up in its line of the page table on the
  way, and compacts them with 0/1 products (the positions ascend, so a
  round of pages fills a run of slots). A copy of one 1,280 B cache row the
  chip's compiler refuses (the pool's tiling is 8 rows), and XLA's gather
  of all 8 x 2048 slots, a row at a time behind a 16,384-element look-up of
  their pages, was 0.38 ms a layer whatever the rows held
  (``hack/bench_dsa_gather.py``); it stays as what runs off the chip.
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

# The kernels' names as a device trace has them (their jitted wrappers'
# ``__name__``, as ``ops.pallas_paged_attention`` names its kernels); the
# benchmark's ``dsa_select_share`` counts the ops whose names start ``topk``
# or ``gather``.
KERNEL_INDEX = "dsa_index_scores"
KERNEL_KEEP = "dsa_keep_bias"
KERNEL_SELECT = "topk_by_count"
KERNEL_GATHER = "gather_by_product"


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


# Keys of a round of a row's own pages through VMEM: the decode kernel's
# superblock, and one block of ``dsa_index_scores``.
_ROUND_KEYS = 1024


def _round_copies(k_hbm, layer, table_ref, b, last_page, landed, sem):
    """``copies(buf, r)``: round ``r`` of row ``b``'s own pages of layer
    ``layer`` of the pool ``k_hbm [layers, pages, 1, page_size, width]``
    into ``landed[buf] [pages a round, page_size, width]``, each page looked
    up in the row's line of the page table by a scalar read and signalled
    on ``sem[buf, t]``. Past ``last_page`` that page again: every key of a
    round then is one the row holds (finite, where a product meets it)."""
    kpb = landed.shape[1]

    def copies(buf, r):
        return [pltpu.make_async_copy(
            k_hbm.at[layer, table_ref[b, jnp.minimum(r * kpb + t,
                                                     last_page)], 0],
            landed.at[buf, t], sem.at[buf, t]) for t in range(kpb)]

    return copies


def _paged_index_kernel(table_ref, lens_ref, layer_ref, q_ref, w_ref, k_hbm,
                        o_ref, landed, sem, *, tq, heads):
    # table_ref [rows, pages a row], lens_ref [rows], layer_ref [1] (SMEM);
    # q_ref [1, tq * heads, width], w_ref [1, tq * heads, 1]; k_hbm the pool
    # [layers, pages, 1, page_size, width], left in HBM; o_ref [1, tq, pages
    # a row * page_size]; landed [2, kpb, page_size, width]: a round of the
    # row's own pages, twice.
    b = pl.program_id(0)
    total = lens_ref[b]
    _, kpb, page_size, width = landed.shape
    keys = kpb * page_size
    rounds = (total + keys - 1) // keys
    copies = _round_copies(k_hbm, layer_ref[0], table_ref, b,
                           (total - 1) // page_size, landed, sem)

    def at(r):
        return pl.ds(pl.multiple_of(r * keys, keys), keys)

    @pl.when(total > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    @pl.loop(0, rounds)
    def _(r):
        buf = r % 2

        @pl.when(r + 1 < rounds)
        def _():
            for c in copies(1 - buf, r + 1):
                c.start()

        for c in copies(buf, r):
            c.wait()
        dots = jax.lax.dot_general(
            q_ref[0], landed[buf].reshape(keys, width).astype(q_ref.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [tq * heads, keys]
        dots = jnp.maximum(dots, 0.0) * w_ref[0]
        live = r * keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1) < total
        for i in range(tq):
            o_ref[0, i:i + 1, at(r)] = jnp.where(live, jnp.sum(
                dots[i * heads:(i + 1) * heads], axis=0, keepdims=True), 0.0)

    @pl.loop(rounds, o_ref.shape[2] // keys)
    def _(r):
        o_ref[0, :, at(r)] = jnp.zeros((tq, keys), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores_paged(q_idx: jax.Array, w_idx: jax.Array,
                           idx_stack: jax.Array, layer_idx,
                           page_table: jax.Array, lens: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """``dsa_index_scores`` over ``gather_index_keys(idx_stack, layer_idx,
    page_table)`` without the gather: the keys are scored where they lie.
    ``idx_stack [layers, pages, 1, page_size, width]`` stays in HBM, and a
    program (grid: row, query tile) streams its row's own ``ceil(lens /
    page_size)`` pages through VMEM, a round of 1024 keys at a time, twice
    buffered, each page looked up in the row's line of ``page_table [rows,
    pages a row]`` on the way (``gather_by_product``'s round). A round is
    scored as ``dsa_index_scores`` scores a block (the same products in the
    same types: below ``lens`` the scores are that kernel's bit for bit),
    and everything from ``lens`` on is 0. A row of ``lens`` 0 starts no
    copy: a decode step's padded rows, and its rows of at most
    ``index_topk`` keys, cost the zeros written for them."""
    batch, q_seq, heads, width = q_idx.shape
    pages = page_table.shape[1]
    page_size = idx_stack.shape[-2]
    tq = _tile(q_seq, 16)
    kpb = _tile(pages, _ROUND_KEYS // page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, q_seq // tq),
        in_specs=[
            pl.BlockSpec((1, tq * heads, width),
                         lambda b, qt, *_p: (b, qt, 0)),
            pl.BlockSpec((1, tq * heads, 1), lambda b, qt, *_p: (b, qt, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tq, pages * page_size),
                               lambda b, qt, *_p: (b, qt, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kpb, page_size, idx_stack.shape[-1]),
                       idx_stack.dtype),
            pltpu.SemaphoreType.DMA((2, kpb))],
    )
    return pl.pallas_call(
        functools.partial(_paged_index_kernel, tq=tq, heads=heads),
        out_shape=jax.ShapeDtypeStruct((batch, q_seq, pages * page_size),
                                       jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.asarray(layer_idx, jnp.int32).reshape(1),
      q_idx.reshape(batch, q_seq * heads, width),
      w_idx.astype(jnp.float32).reshape(batch, q_seq * heads, 1), idx_stack)


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


def _ordered_int32(x: jax.Array) -> jax.Array:
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


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
        buf[j] = jnp.where(candidates(j), _ordered_int32(landed[j]),
                           _MASKED)

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


# A block of a decode row's scores: one float32 vreg, 8 chunks of 128 keys.
_LANES = 128
_BLOCK = 8 * _LANES


def _select_kernel(lens_ref, s_hbm, o_ref, landed, buf, before, upto,
                   in_chunk, sem, *, topk):
    # lens_ref [rows] (SMEM); s_hbm the scores [rows, chunks, 128], left in
    # HBM: key p of a row lies at [p // 128, p % 128]; o_ref [1, tiles,
    # 128]: slot s of the row's selection at [s // 128, s % 128]; landed
    # and buf [chunks rounded up to 128, 128]: the row's live blocks as
    # they arrive and as ordered int32; before, upto [chunks, 128] and
    # in_chunk [chunks // 128, 128, 128]: the prefix sums of what is kept.
    b = pl.program_id(0)
    total = lens_ref[b]
    tiles = o_ref.shape[1]
    chunks = landed.shape[0]
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(i32, shape, dim)

    @pl.when(total <= topk)
    def _():
        # The row keeps every key it has: nothing is copied or counted.
        o_ref[0] = iota((tiles, _LANES), 0) * _LANES + iota(
            (tiles, _LANES), 1)

    @pl.when(total > topk)
    def _():
        live = (total + _BLOCK - 1) // _BLOCK

        def block(j):
            return pl.ds(pl.multiple_of(j * 8, 8), 8)

        def copy(j):
            return pltpu.make_async_copy(
                s_hbm.at[b, block(j), :], landed.at[block(j), :], sem.at[0])

        def positions(j):
            return j * _BLOCK + iota((8, _LANES), 0) * _LANES + iota(
                (8, _LANES), 1)

        @pl.loop(0, live)
        def _(j):
            copy(j).start()

        @pl.loop(0, live)
        def _(j):
            copy(j).wait()
            # Past the row's length: below -inf's, which a score may be.
            buf[block(j), :] = jnp.where(
                positions(j) < total, _ordered_int32(landed[block(j), :]),
                jnp.iinfo(i32).min)

        def count(holds):
            """How many keys of the live blocks ``holds(block, its
            positions)`` is true of, as ``[1, 1]``."""
            def one(j, acc):
                return acc + holds(buf[block(j), :], positions(j)
                                   ).astype(i32)

            acc = jax.lax.fori_loop(0, live, one,
                                    jnp.zeros((8, _LANES), i32))
            return jnp.sum(jnp.sum(acc, axis=0, keepdims=True), axis=1,
                           keepdims=True)

        def score_bit(i, thr):
            # ``kth_largest``'s step on signed keys (``_keep_kernel``).
            trial = thr ^ (jnp.int32(1) << (31 - i))
            return jnp.where(count(lambda x, _p: x >= trial) >= topk,
                             trial, thr)

        thr = jax.lax.fori_loop(
            0, 32, score_bit, jnp.full((1, 1), jnp.iinfo(i32).min, i32))
        # Of the keys that score exactly the threshold the lowest
        # positions are kept, as many as the scores above it leave room
        # for: those before ``cut``, the largest position that has no more
        # than that many of them before it (every one, bits set, where
        # there are no more).
        room = topk - count(lambda x, _p: x > thr)
        ties = count(lambda x, _p: x == thr)

        def position_bit(i, cut):
            trial = cut | (jnp.int32(1) << (bits_of_a_position - 1 - i))
            fit = count(lambda x, p: (x == thr) & (p < trial)) <= room
            return jnp.where(fit, trial, cut)

        bits_of_a_position = (chunks * _LANES).bit_length()
        cut = jax.lax.fori_loop(
            0, jnp.where(jnp.sum(ties - room) > 0, bits_of_a_position, 0),
            position_bit, jnp.zeros((1, 1), i32))
        cut = jnp.where(ties > room, cut, jnp.iinfo(i32).max)

        # What is kept before each chunk of 128 keys and up to its end, and
        # up to each lane within it, as 0/1 triangles on the MXU (a chunk
        # keeps 128 at most and a row ``topk``: bfloat16 and the float32
        # sums hold them exactly).
        at = iota((chunks, _LANES), 0) * _LANES + iota((chunks, _LANES), 1)
        scores = buf[...]
        kept = ((at < total) & ((scores > thr) | ((scores == thr)
                                                 & (at < cut)))).astype(bf16)
        a_chunk = jnp.dot(kept, jnp.ones((_LANES, _LANES), bf16),
                          preferred_element_type=f32)  # every lane: its sum
        earlier = iota((chunks, chunks), 1) < iota((chunks, chunks), 0)
        before[...] = jnp.dot(earlier.astype(bf16), a_chunk.astype(bf16),
                              preferred_element_type=f32)
        upto[...] = before[...] + a_chunk
        to_lane = iota((_LANES, _LANES), 1) <= iota((_LANES, _LANES), 0)
        for g in range(chunks // _LANES):  # [lane, chunk of the group]
            in_chunk[g] = jax.lax.dot_general(
                to_lane.astype(bf16), kept[g * _LANES:(g + 1) * _LANES],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=f32).astype(bf16)

        # A tile of 128 slots is filled from the chunks whose slots reach
        # into it, [first, last): lane t of both rows below is tile t's.
        edge = (iota((1, _LANES), 1) * _LANES).astype(f32)
        first = jnp.sum((upto[...] <= edge).astype(i32), axis=0,
                        keepdims=True)
        last = jnp.sum((before[...] < edge + _LANES).astype(i32), axis=0,
                       keepdims=True)
        lane = iota((1, _LANES), 1)

        @pl.loop(0, tiles)
        def _(t):
            # Slot s is the key that has s kept before it: in the chunk
            # that owns s, at the lane where the count up to it passes s.
            # A group of 128 chunks at a time: which chunk owns each slot
            # of the tile is a 0/1 matrix, and its product with the
            # group's counts brings every slot its chunk's.
            slot = (t * _LANES + lane).astype(f32)

            def group(g, found):
                chunk, kept_before, counts = found
                rows = pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)
                ahead = before[rows, :]
                owns = (ahead <= slot) & (slot < upto[rows, :])
                index = (g * _LANES + iota((_LANES, _LANES), 0)).astype(f32)
                return (
                    chunk + jnp.sum(jnp.where(owns, index, 0.0), axis=0,
                                    keepdims=True),
                    kept_before + jnp.sum(jnp.where(owns, ahead, 0.0),
                                          axis=0, keepdims=True),
                    counts + jnp.dot(in_chunk[g], owns.astype(bf16),
                                     preferred_element_type=f32))

            chunk, kept_before, counts = jax.lax.fori_loop(
                jnp.sum(jnp.where(lane == t, first, 0)) // _LANES,
                (jnp.sum(jnp.where(lane == t, last, 0)) + _LANES - 1)
                // _LANES, group,
                (jnp.zeros((1, _LANES), f32), jnp.zeros((1, _LANES), f32),
                 jnp.zeros((_LANES, _LANES), f32)))
            in_lane = jnp.sum((counts + kept_before <= slot).astype(f32),
                              axis=0, keepdims=True)
            o_ref[0, pl.ds(t, 1), :] = (chunk * _LANES + in_lane).astype(i32)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def topk_by_count(scores: jax.Array, total_lens: jax.Array, *, topk: int,
                  interpret: bool = False) -> jax.Array:
    """The positions of each row's ``topk`` best of its first
    ``total_lens`` float32 ``scores [rows, keys]``, ascending, as int32
    ``[rows, topk]``: the set ``jax.lax.top_k`` picks (scores compared as
    their bits in the floats' order, so ``+0.0`` lies above ``-0.0``; of
    equal scores the lower position first), found by counting. A row of at
    most ``topk`` keys gets ``arange(topk)``.

    Grid (row). A program copies its row's live blocks of 1024 scores from
    HBM once, orders them as ``_ordered_bits`` does and runs
    ``kth_largest``'s 32 steps on the copy in VMEM, then as many steps
    again over the positions of the scores tied with the threshold if
    there are more of those than fit. Prefix sums of what is kept (0/1
    triangles on the MXU) say how many keys are kept before each chunk of
    128 keys and up to each lane inside it; slot s then is the key with s
    kept before it, found a tile of 128 slots at a time: which chunk owns
    each slot is a 0/1 matrix over a group of 128 chunks, one product with
    the group's counts brings every slot its chunk's, and a compare and a
    count give the lane. A tile looks at the groups its slots' chunks lie
    in, one or two as a rule: ``topk`` / 128 + chunks / 128 products a row
    at most, where one product a chunk took twice the time at 33 k keys
    (``hack/bench_dsa_select.py``). A row of at most ``topk`` keys copies
    and counts nothing."""
    rows, keys = scores.shape
    tiles = -(-topk // _LANES)
    if tiles > _LANES:  # the kernel holds a tile's two edges in one lane
        raise ValueError(f"topk {topk} is more than {_LANES * _LANES}")
    padded = -(-keys // _BLOCK) * _BLOCK
    if padded != keys:
        scores = jnp.pad(scores, [(0, 0), (0, padded - keys)])
    chunks = -(-padded // _LANES // _LANES) * _LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tiles, _LANES), lambda b, *_p: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((chunks, _LANES), jnp.float32),
                        pltpu.VMEM((chunks, _LANES), jnp.int32),
                        pltpu.VMEM((chunks, _LANES), jnp.float32),
                        pltpu.VMEM((chunks, _LANES), jnp.float32),
                        pltpu.VMEM((chunks // _LANES, _LANES, _LANES),
                                   jnp.bfloat16),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    picked = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        out_shape=jax.ShapeDtypeStruct((rows, tiles, _LANES), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(total_lens.astype(jnp.int32),
      scores.astype(jnp.float32).reshape(rows, padded // _LANES, _LANES))
    return picked.reshape(rows, tiles * _LANES)[:, :topk]


def select_topk(scores: jax.Array, total_lens: jax.Array, topk: int
                ) -> tuple[jax.Array, jax.Array]:
    """A decode row's selection: ``(positions [batch, topk], count
    [batch])``. The first ``count = min(total_lens, topk)`` positions are
    the row's ``topk`` best-scoring keys among its ``total_lens``
    (ascending; attention does not care); a row of at most ``topk`` keys
    gets all of them whatever ``scores`` holds for it. ``topk_by_count``,
    through the interpreter where the program is not lowered for a TPU."""
    picked = jax.lax.platform_dependent(
        scores, total_lens,
        tpu=functools.partial(topk_by_count, topk=topk),
        default=functools.partial(topk_by_count, topk=topk, interpret=True))
    return picked, jnp.minimum(total_lens, topk)


# Slots of the chosen pool a product fills at a time: one MXU pass wide.
_SLOT_TILE = 128


def _gather_kernel(table_ref, count_ref, layer_ref, pos_ref, k_hbm, o_hbm,
                   landed, chosen, slot_pos, sem, *, page_size, kpb):
    # table_ref [rows, pages a row], count_ref [rows], layer_ref [1] (SMEM);
    # pos_ref [1, tiles, 128]: slot s of the row's selection at [s // 128,
    # s % 128]; k_hbm the pool [layers, pages, 1, page_size, width] and
    # o_hbm the chosen one [rows * pages, 1, page_size, width], both left
    # in HBM; landed [2, kpb, page_size, width]: a round of the row's own
    # pages, twice; chosen [pages rounded up to tiles, page_size, width]:
    # the row's slots as they fill; slot_pos [tiles * 128, 128]: slot s's
    # position all along line s.
    b = pl.program_id(0)
    count = count_ref[b]
    layer = layer_ref[0]
    tiles = pos_ref.shape[1]
    pages = o_hbm.shape[0] // table_ref.shape[0]
    per_tile = _SLOT_TILE // page_size
    width = chosen.shape[-1]
    keys = kpb * page_size
    i32 = jnp.int32

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(i32, shape, dim)

    slot = iota((tiles, _SLOT_TILE), 0) * _SLOT_TILE + iota(
        (tiles, _SLOT_TILE), 1)
    pos = jnp.where(slot < count, pos_ref[0], -1)
    reach = jnp.max(pos)  # the row's last chosen position; -1: a padded row
    # Ascending and distinct, so they are the row's first ``count`` keys
    # where the last of them is key ``count - 1``: whole pages then.
    leading = reach == count - 1

    def page_copy(j):
        return pltpu.make_async_copy(
            k_hbm.at[layer, table_ref[b, j], 0],
            o_hbm.at[b * pages + j, 0], sem.at[0, 0])

    @pl.when((count > 0) & leading)
    def _():
        whole = (count + page_size - 1) // page_size

        @pl.loop(0, whole)
        def _(j):
            page_copy(j).start()

        @pl.loop(0, whole)
        def _(j):
            page_copy(j).wait()

    @pl.when((count > 0) & ~leading)
    def _():
        last_page = reach // page_size

        # A product may not meet what VMEM held before: past the row's
        # last page the copies bring that page again (no slot's).
        copies = _round_copies(k_hbm, layer, table_ref, b, last_page,
                               landed, sem)

        for c in copies(0, 0):
            c.start()
        chosen[...] = jnp.zeros_like(chosen)
        for t in range(tiles):
            slot_pos[t * _SLOT_TILE:(t + 1) * _SLOT_TILE, :] = jnp.transpose(
                jnp.broadcast_to(pos[t:t + 1], (_SLOT_TILE, _SLOT_TILE)))

        def a_round(r, lo):
            buf = r % 2

            @pl.when(r < last_page // kpb)
            def _():
                for c in copies(1 - buf, r + 1):
                    c.start()

            for c in copies(buf, r):
                c.wait()
            hi = jnp.sum(((pos >= 0) & (pos < (r + 1) * keys)).astype(i32))
            latents = landed[buf].reshape(keys, width)
            key = r * keys + iota((_SLOT_TILE, keys), 1)

            # The round's keys are slots [lo, hi), ``hi`` of the row's
            # chosen positions lying below its end: a tile of 128 slots at
            # a time, which of the round's keys each slot is as a 0/1
            # matrix, times the round (one 1 a line, float32 sums: exact).
            @pl.loop(lo // _SLOT_TILE, (hi + _SLOT_TILE - 1) // _SLOT_TILE)
            def _(t):
                lines = pl.ds(pl.multiple_of(t * _SLOT_TILE, _SLOT_TILE),
                              _SLOT_TILE)
                is_key = key == slot_pos[lines, :][:, :1]
                got = jnp.dot(is_key.astype(latents.dtype), latents,
                              preferred_element_type=jnp.float32)
                at = pl.ds(t * per_tile, per_tile)
                chosen[at] = chosen[at] + got.astype(chosen.dtype).reshape(
                    per_tile, page_size, width)

            return hi

        jax.lax.fori_loop(0, last_page // kpb + 1, a_round, jnp.int32(0))
        out = pltpu.make_async_copy(
            chosen.at[pl.ds(0, pages)], o_hbm.at[pl.ds(b * pages, pages), 0],
            sem.at[0, 0])
        out.start()
        out.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_by_product(k_stack: jax.Array, layer_idx, page_table: jax.Array,
                      positions: jax.Array, count: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """``gather_selected`` as one kernel, grid (row), that reads what a
    row holds and no more.

    A row of ``count`` 0 (every padded row) does nothing: its pages of the
    result are never written, and the decode kernel loads none of them for
    a row of no keys. A row whose chosen positions are its first ``count``
    keys (its last chosen position is ``count - 1``: what ``select_topk``
    gives a row of at most ``topk`` keys) has its first ``ceil(count /
    page_size)`` pages copied whole, pool to result. Any other row streams
    its own pages up to its last chosen position through VMEM, 1024 keys a
    round, twice buffered, looking each page up in its line of the page
    table on the way, and compacts them on the MXU: a round's keys are a
    run of the row's slots (the positions ascend), and for each tile of 128
    slots the run touches, which of the round's keys each slot is, as a 0/1
    matrix, times the round. One 1 a line and float32 sums, so a slot gets
    its cache row's values exactly (a ``-0.0`` comes out ``+0.0``, and what
    the MXU flushes, flushed); a product also multiplies the round's other
    keys by 0, so the pool's pages have to be finite, as every kernel that
    masks by score already needs them. The row's ``pages`` pages are then
    written once, zero past ``count``: all a decode kernel may load for it.
    Positions have to ascend within a row's first ``count``."""
    batch, n = positions.shape
    page_size, width = k_stack.shape[-2:]
    if _SLOT_TILE % page_size:
        raise ValueError(f"a page of {page_size} keys does not divide "
                         f"{_SLOT_TILE}")
    pages = -(-n // page_size)
    tiles = -(-n // _SLOT_TILE)
    if tiles * _SLOT_TILE != n:
        positions = jnp.pad(positions, [(0, 0), (0, tiles * _SLOT_TILE - n)])
    kpb = min(_ROUND_KEYS // page_size, page_table.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, tiles, _SLOT_TILE),
                               lambda b, *_p: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, kpb, page_size, width), k_stack.dtype),
            pltpu.VMEM((tiles * (_SLOT_TILE // page_size), page_size, width),
                       k_stack.dtype),
            pltpu.VMEM((tiles * _SLOT_TILE, _SLOT_TILE), jnp.int32),
            pltpu.SemaphoreType.DMA((2, kpb))],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, page_size=page_size, kpb=kpb),
        out_shape=jax.ShapeDtypeStruct((batch * pages, 1, page_size, width),
                                       k_stack.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(page_table.astype(jnp.int32), count.astype(jnp.int32),
      jnp.asarray(layer_idx, jnp.int32).reshape(1),
      positions.astype(jnp.int32).reshape(batch, tiles, _SLOT_TILE), k_stack)


def _gather_by_index(k_stack, layer_idx, page_table, positions, count):
    """``gather_selected`` in ``jax.numpy``: every slot of every row."""
    del count
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


def gather_selected(k_stack: jax.Array, layer_idx, page_table: jax.Array,
                    positions: jax.Array, count: jax.Array) -> jax.Array:
    """The cache rows at the first ``count [batch]`` of ``positions
    [batch, n]`` of each row's own pages, as a pool of their own: ``[batch
    * pages, 1, page_size, width]`` with ``pages = ceil(n / page_size)``
    pages a row, row ``b``'s at ``b * pages``. The decode kernel then
    streams them as it streams any pool, ``count`` keys of each row; what
    lies past a row's ``count`` is whatever the form that ran left there
    (``gather_by_product`` on a TPU: finite where a decode kernel may load
    it; off the chip the ``jax.numpy`` gather of every slot, which traces
    faster than the kernel interprets)."""
    return jax.lax.platform_dependent(
        k_stack, jnp.asarray(layer_idx, jnp.int32), page_table, positions,
        count, tpu=gather_by_product, default=_gather_by_index)
