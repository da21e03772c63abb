"""Pallas TPU kernel: a prefill chunk of a latent-attention layer, per head.

The absorbed form (``q @ W_UK^T`` against the cached latent, the context
``@ W_UV`` afterwards: ``pallas_paged_prefill_attention`` with
``shared_kv``) makes every query head multiply the page's whole width on
the way in and again on the way out: ``4 * width`` FLOPs a query, key and
head (640 lanes at the published widths). That is the right trade for a
decode step's one query. A prefill chunk brings hundreds of queries a key,
and then the model's own prefill form is cheaper: expand each head's key
(``nope`` lanes, its rope lanes as they lie in the page) and value from
the latent **once a head and key for all the chunk's queries**, and attend
at the heads' own widths. The expansion costs ``2 * rank * (nope + v_dim)``
a key and head whatever the chunk holds, so it pays from
``per_head_min_queries`` queries on.

Same mathematics, same operand precision (operands in the cache's dtype,
float32 accumulation and softmax state); the two forms differ in where a
rounding to the cache's dtype falls (the absorbed query ``q @ W_UK^T``
there, the expanded key ``latent @ W_UK`` here).

Grid ``(row, group of heads)``; a program holds the chunk's queries whole.
The row's latent pages stream HBM→VMEM in superblocks of 1024 keys, double
buffered, once a program (``_superblock_streamer``, the streamer of the
other prefill kernels); a selection (learned sparse attention) arrives as
the float32 bias those kernels read, copied in a superblock at a time
beside the pages. Each head of the group then expands the superblock and
folds it into its own online softmax, whose state lies in scratch between
superblocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import (
    _NEG_INF, _check_head_dim_alignment, _compiler_params, _layer_operand,
    _superblock_streamer)

# The kernel's name as a device trace has it: its jitted wrapper's
# ``__name__`` (see ``pallas_paged_attention.KERNEL_PREFILL``).
KERNEL_PER_HEAD_PREFILL = "pallas_per_head_prefill_attention"

# Keys a superblock, heads a program (v5e, one layer of 128 heads, 512
# queries x 25,088 keys under a selection; PERF.md §6, PR 47 and PR 46: 512
# keys a superblock are 1.65x the absorbed kernel, 1024 2.05x, 2048 1.9x).
_SUPERBLOCK_KEYS = 1024
_HEAD_GROUP = 16
# How far past the FLOPs' break-even a chunk has to be before the form
# changes: the expansion's matmuls are narrower than the absorbed form's.
_MARGIN = 1.3


def per_head_min_queries(width: int, rank: int, nope_dim: int,
                         v_dim: int) -> float:
    """The fewest queries of a chunk (its padded length) from which the
    per-head form is taken, from the widths alone: ``_MARGIN`` times the
    break-even of the two forms' FLOPs a key and head. Absorbed: scores
    and values at the page's ``width``, ``4 * width`` a query. Per head:
    ``2 * rank * (nope_dim + v_dim)`` for the expansion, then ``2 *
    (nope_dim + rope lanes + v_dim)`` a query, the rope lanes read as the
    page has them behind the latent (``width - rank``). ``math.inf`` where
    the absorbed form is never the dearer one.

    191 at latent 512 + rope 64 padded to 640, heads of 128: an engine's
    chunks of 256 and 512 go per head, 128 and below stay absorbed."""
    saved = 4 * width - 2 * (nope_dim + width - rank + v_dim)
    if saved <= 0:
        return math.inf
    return math.ceil(_MARGIN * 2 * rank * (nope_dim + v_dim) / saved)


def _superblock_pages(page_size: int, pages_per_seq: int) -> int:
    return max(1, min(_SUPERBLOCK_KEYS // page_size, pages_per_seq))


def per_head_expanded_keys(total_len: int, page_size: int,
                           pages_per_seq: int) -> int:
    """The key positions one head expands, a layer, for a chunk that ends
    at ``total_len`` tokens of its row: whole superblocks, as the kernel
    copies them in. Host arithmetic (the ``expanded_keys`` attribute of a
    prefill chunk's ``step.dispatch``)."""
    keys = _superblock_pages(page_size, pages_per_seq) * page_size
    return -(-total_len // keys) * keys


def _per_head_kernel(
    # scalar prefetch
    page_table_ref,  # [batch, pages_per_seq] int32
    ctx_lens_ref,  # [batch] int32 (tokens cached BEFORE the chunk's)
    total_lens_ref,  # [batch] int32 (ctx + the chunk's real tokens)
    layer_ref,  # [1] int32: layer of a stacked cache, else unused
    # inputs
    qn_ref,  # [1, heads, q_seq, nope] (scaled)
    qr_ref,  # [1, heads, q_seq, width - rank] (scaled; 0 past the rope)
    wuk_ref,  # [heads, rank, nope]
    wuv_ref,  # [heads, rank, v_dim]
    lat_hbm,  # the latent pages
    # then: [bias_hbm,] o_ref, lat, sem, [bias_buf, bias_sem,] pen, m, l, acc
    *refs,
    page_size: int,
    pages_per_block: int,
    rank: int,
    stacked: bool,
    has_bias: bool,
):
    if has_bias:
        (bias_hbm, o_ref, lat, sem, bias_buf, bias_sem,
         pen_ref, m_ref, l_ref, acc_ref) = refs
    else:
        o_ref, lat, sem, pen_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    heads, q_seq = qn_ref.shape[1], qn_ref.shape[2]
    kpb = pages_per_block
    keys = kpb * page_size
    width = lat.shape[-1]

    ctx_len = ctx_lens_ref[b]
    total_len = total_lens_ref[b]
    # The chunk is one tile of queries: it needs the row's keys up to its
    # own last token.
    num_pages = (total_len + page_size - 1) // page_size
    num_sb = (num_pages + kpb - 1) // kpb
    zero = jnp.int32(0)
    positions, page_copies = _superblock_streamer(
        page_table_ref, b, 0, lat_hbm, None, lat, None, sem, kpb=kpb,
        num_iters=num_pages, first_window=zero, sink_pages=zero, sinks=0,
        shared_kv=True, layer_idx=layer_ref[0] if stacked else None)

    def copies(slot, sb):
        """Superblock ``sb``'s pages, and its slice of the selection."""
        out = page_copies(slot, sb)
        if has_bias:
            out.append(pltpu.make_async_copy(
                bias_hbm.at[b, :, pl.ds(pl.multiple_of(sb * keys, keys),
                                        keys)],
                bias_buf.at[slot], bias_sem.at[slot]))
        return out

    # A head's softmax state. The running maximum starts above the mask's
    # -1e30, so a key masked for a query adds exp(-1e30 - m) = 0 whether
    # or not the query has met a key it keeps.
    m_ref[...] = jnp.full(m_ref.shape, 0.5 * _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    q_pos = ctx_len + jax.lax.broadcasted_iota(jnp.int32, (q_seq, 1), 0)

    @pl.when(num_sb > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def superblock(sb, carry):
        slot = sb % 2

        @pl.when(sb + 1 < num_sb)
        def _():
            for c in copies(1 - slot, sb + 1):
                c.start()

        for c in copies(slot, sb):
            c.wait()

        rows = lat[slot].reshape(keys, width)
        c_kv, k_rope = rows[:, :rank], rows[:, rank:]
        # What every head adds to its scores: 0 for a key the query sees
        # (causal, inside the row, and kept by its selection), else -1e30.
        # Sub-pages past the row's pages park at total_len.
        k_pos = positions(sb, total_len, page_size)
        seen = (k_pos <= q_pos) & (k_pos < total_len)  # [q_seq, keys]
        pen_ref[...] = jnp.where(
            seen, bias_buf[slot] if has_bias else 0.0, _NEG_INF)

        def head(h, carry):
            # This head's keys and values of the superblock, in the
            # cache's dtype as the model's own prefill form has them.
            k = jnp.dot(c_kv, wuk_ref[h],
                        preferred_element_type=jnp.float32).astype(rows.dtype)
            v = jnp.dot(c_kv, wuv_ref[h],
                        preferred_element_type=jnp.float32).astype(rows.dtype)
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(qn_ref[0, h], k, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[0, h], k_rope, nt,
                                       preferred_element_type=jnp.float32)
                 + pen_ref[...])
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            return carry

        return jax.lax.fori_loop(0, heads, head, carry)

    jax.lax.fori_loop(0, num_sb, superblock, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "head_group", "pages_per_block",
                                    "interpret"))
def pallas_per_head_prefill_attention(
    q_nope: jax.Array,  # [batch, q_seq, heads, nope] (the chunk, padded)
    q_rope: jax.Array,  # [batch, q_seq, heads, rope], rotated
    w_uk: jax.Array,  # [heads, rank, nope]
    w_uv: jax.Array,  # [heads, rank, v_dim]
    latent_pages: jax.Array,  # [num_pages, 1, page_size, width]
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    ctx_lens: jax.Array,  # [batch] cached tokens before the chunk's
    total_lens: jax.Array,  # [batch] ctx + the chunk's real tokens
    *,
    scale: float,
    layer_idx: jax.Array | int | None = None,
    bias: jax.Array | None = None,  # [batch, q_seq, keys] float32
    head_group: int | None = None,
    pages_per_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of a prefill chunk over its row's latent pages (the
    chunk's own latents already written), each head on keys and values it
    expands from the latent: ``k = [latent[:rank] @ w_uk[h] ; the page's
    rope lanes]``, ``v = latent[:rank] @ w_uv[h]``. Returns the heads'
    values ``[batch, q_seq, heads, v_dim]``: what the absorbed form gives
    after its ``@ w_uv``.

    A page's row is ``[latent (rank) ; rope key ; zero pad]``; ``scale`` is
    the softmax scale of the heads' own width (``(nope + rope) ** -0.5``
    times a model's multiplier), applied to the queries. ``layer_idx``
    reads a stacked cache ``[layers, pages, ...]`` in the kernel. ``bias``
    is a query's selection among its keys, as
    ``pallas_paged_prefill_attention`` takes it. Padded queries (past
    ``total_lens``) come back finite and mean nothing. ``head_group``
    (default: the largest divisor of ``heads`` up to 16) and
    ``pages_per_block`` (default: 1024 keys) are for tests.
    """
    batch, q_seq, heads, nope = q_nope.shape
    rank, v_dim = w_uv.shape[1:]
    cache_dims = (latent_pages.shape[1:] if layer_idx is not None
                  else latent_pages.shape)
    _, _, page_size, width = cache_dims
    dtype = latent_pages.dtype
    _check_head_dim_alignment(width, interpret)
    if head_group is None:
        head_group = max(g for g in range(1, _HEAD_GROUP + 1)
                         if heads % g == 0)
    assert heads % head_group == 0, "head_group must divide the heads"
    if pages_per_block is None:
        pages_per_block = _superblock_pages(page_size, page_table.shape[1])
    keys = pages_per_block * page_size

    def heads_first(q, lanes):
        """``q`` scaled (one rounding, as the absorbed form scales its
        query), zero-padded to ``lanes``, ``[batch, heads, q_seq, lanes]``."""
        q = (q.astype(jnp.float32) * scale).astype(dtype)
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, lanes - q.shape[-1])])
        return q.transpose(0, 2, 1, 3)

    # The rope lanes are read with whatever pad lies behind them in the
    # page: the query is zero there.
    rope_lanes = width - rank
    operands = [heads_first(q_nope, nope), heads_first(q_rope, rope_lanes),
                w_uk.astype(dtype), w_uv.astype(dtype), latent_pages]

    def per_group(lanes):
        return pl.BlockSpec((1, head_group, q_seq, lanes),
                            lambda b, g, *_p: (b, g, 0, 0))

    def weights(lanes):
        return pl.BlockSpec((head_group, rank, lanes),
                            lambda b, g, *_p: (g, 0, 0))

    in_specs = [per_group(nope), per_group(rope_lanes), weights(nope),
                weights(v_dim), pl.BlockSpec(memory_space=pl.ANY)]
    bias_scratch = []
    if bias is not None:
        # Whole superblocks: the last one is copied to its end.
        n_keys = -(-page_table.shape[1] // pages_per_block) * keys
        operands.append(jnp.pad(
            bias.astype(jnp.float32),
            [(0, 0), (0, 0), (0, n_keys - bias.shape[2])],
            constant_values=_NEG_INF))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        bias_scratch = [pltpu.VMEM((2, q_seq, keys), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, heads // head_group),
        in_specs=in_specs,
        out_specs=per_group(v_dim),
        scratch_shapes=[
            pltpu.VMEM((2, pages_per_block, page_size, width), dtype),
            pltpu.SemaphoreType.DMA((2, pages_per_block, 2)),
            *bias_scratch,
            pltpu.VMEM((q_seq, keys), jnp.float32),
            pltpu.VMEM((head_group, q_seq, 1), jnp.float32),
            pltpu.VMEM((head_group, q_seq, 1), jnp.float32),
            pltpu.VMEM((head_group, q_seq, v_dim), jnp.float32),
        ],
    )

    # Per program: the queries', weights' and output's blocks (double
    # buffered by the pipeline), the page and bias slots, the mask, the
    # heads' state (m and l take a 128-lane tile a row), and a head's
    # float32 scores, their exponentials and the cache-dtype probabilities
    # and expanded keys.
    item = dtype.itemsize
    rows = head_group * q_seq
    vmem_bytes = (
        2 * rows * (nope + rope_lanes + v_dim) * item
        + 2 * head_group * rank * (nope + v_dim) * item
        + 2 * keys * width * item
        + (3 if bias is not None else 1) * q_seq * keys * 4
        + rows * (2 * 128 + v_dim) * 4
        + q_seq * keys * (4 + 4 + item)
        + keys * (nope + v_dim) * (4 + item))

    out = pl.pallas_call(
        functools.partial(
            _per_head_kernel, page_size=page_size,
            pages_per_block=pages_per_block, rank=rank,
            stacked=layer_idx is not None, has_bias=bias is not None),
        out_shape=jax.ShapeDtypeStruct((batch, heads, q_seq, v_dim), dtype),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(vmem_bytes),
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      total_lens.astype(jnp.int32), _layer_operand(layer_idx), *operands)
    return out.transpose(0, 2, 1, 3)
