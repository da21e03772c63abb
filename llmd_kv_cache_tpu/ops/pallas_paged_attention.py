"""Pallas TPU kernels: flash attention over a paged KV cache.

Instead of materializing each sequence's gathered KV **and the fp32
attention probs** in HBM (which ``ops.paged_attention`` does — the
dominant excess HBM traffic of the XLA prefill path), each
(batch, kv_head[, q_tile]) program streams the sequence's pages HBM→VMEM
with double-buffered async DMA and folds them into an online softmax — the
ragged-paged-attention recipe.

Pages stream in **superblocks** of ``pages_per_block`` pages (default
targets 1024 keys): a superblock is one batch of DMAs in flight, and an
online-softmax round over it is a full-width MXU matmul instead of one
page_size-wide sliver per page. The prefill and ragged kernels stream and
fold every superblock whole. The decode kernels stream and fold a row's
*live* keys: of a row's last superblock only the 128-key granules that
hold keys of the row are copied, waited for and folded, so a row of 330
keys under a 1024-key superblock pays for 384 (``_live_granules``).
Matmul operands stay in the cache dtype (bf16×bf16, fp32 accumulate — the
MXU fast path) with the softmax scale applied to the fp32 scores,
matching the XLA reference's numerics.

Grid: ``(batch, kv_heads)`` for decode, ``(batch, kv_heads, q_blocks)``
for prefill. Scalar-prefetched page table + context lengths drive the DMA
indices (``PrefetchScalarGridSpec``). GQA: each program serves its kv
head's whole query group; absorbed MLA is the kv_heads=1 multi-query
case. SWA skips out-of-window pages; StreamingLLM sinks stream the first
pages too via a loop-counter→page-index remap.

The jnp reference path remains the fallback (CPU tests run these kernels
in interpreter mode against it).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# The kernels' names as a device trace has them: each kernel's custom call
# is named after the jitted wrapper below that makes it
# (``pallas_paged_decode_attention.<n>``, one ``<n>`` per call site), so
# these are the wrappers' ``__name__``. Readers of traces match them;
# tests/test_model.py pins both sides. ``pallas_call(name=...)`` is left
# unset on purpose: it names the Mosaic kernel inside the custom call, and
# changing that changes the compiled program and its compile-cache key.
KERNEL_DECODE = "pallas_paged_decode_attention"
KERNEL_PREFILL = "pallas_paged_prefill_attention"
KERNEL_RAGGED = "pallas_paged_ragged_attention"


def head_dim_supported(head_dim: int) -> bool:
    """Whether these kernels can compile on real TPU for this head size.

    Mosaic requires per-(page, head) DMA slices to be 128-aligned along
    the lane (head_dim) axis; sub-128 head dims fail to compile (measured
    v5e: "Slice shape along dimension 3 must be aligned to tiling (128)").
    Interpreter mode has no such restriction — this predicate gates the
    compiled path only (the engine's backend selection and the kernels'
    own guard both use it, so the rule cannot drift between them)."""
    return head_dim % 128 == 0


# Mosaic's default scoped-VMEM budget on a v5e; a kernel that needs more
# says so through ``vmem_limit_bytes`` (the chip has 128 MiB).
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _compiler_params(vmem_bytes: int):
    """None while the kernel's estimated VMEM footprint fits the default
    budget — the served GQA shapes compile exactly as before — and a
    raised limit with 2x headroom beyond it. Wide latent heads need it:
    at 16 heads x 640 the prefill kernel's blocks, state and scores come
    to ~31 MiB and the compiler otherwise refuses ("Ran out of memory in
    memory space vmem", v5e, PR 22)."""
    if vmem_bytes <= _DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(100 * 2 ** 20, 2 * vmem_bytes))


def _layer_operand(layer_idx) -> jax.Array:
    """``layer_idx`` as the kernels' [1] int32 scalar-prefetch operand. It
    is a traced value, not a static one: the 28 attention calls of a
    28-layer forward are then one jitted function called 28 times — traced
    and lowered once — instead of 28 kernels that differ in a constant.
    On a v5e host that lowering costs 7-9 s per kernel (PERF.md, PR 22).
    None (an unstacked cache) sends a placeholder the kernel never reads."""
    return jnp.asarray(0 if layer_idx is None else layer_idx,
                       jnp.int32).reshape(1)


def _check_head_dim_alignment(head_dim: int, interpret: bool) -> None:
    if not interpret and not head_dim_supported(head_dim) and (
            jax.devices()[0].platform == "tpu"):
        raise ValueError(
            f"Pallas paged attention needs head_dim % 128 == 0 on TPU "
            f"(got {head_dim}); use the XLA paged-attention fallback "
            f"(ops.paged_attention) for this model")


def _superblock_streamer(page_table_ref, b, h, k_hbm, v_hbm, k_scratch,
                         v_scratch, sem, *, kpb, num_iters, first_window,
                         sink_pages, sinks, shared_kv=False,
                         layer_idx=None, row=None):
    """Shared page remap + superblock DMA for the decode/prefill kernels.

    ``page_for`` (internal) maps a loop counter to a page-table index —
    sink pages ([0, sink_pages)) first, then window pages
    ([first_window, …)) — with DMA-safe clamping for sub-pages past
    ``num_iters``: they copy the row's last page again and are masked
    out by position.
    Returns ``(positions, sb_dma)``: the flat-lane key-position builder
    for the mask and the double-buffered DMA batch. Both take the whole
    superblock by default, which is what the prefill and ragged kernels
    stream and fold every round; the decode kernels ask for one granule
    of it at a time (``_live_granules``) and leave what lies past the
    row's keys alone. One definition for every kernel so the clamp/remap
    subtleties cannot drift between them.

    ``shared_kv`` (absorbed MLA: values ARE the latent keys) streams each
    page ONCE into the K scratch and skips the V stream entirely —
    halving the attention's HBM traffic, which is the point of caching
    only the latent.

    ``row``: multi-row decode programs (``batch_rows > 1``) stage each
    batch row in its own scratch slice ``[slot, row, t]`` / semaphore
    plane; ``None`` keeps the single-row ``[slot, t]`` layout."""
    pp_seq = page_table_ref.shape[1]

    def dst(buf, slot, t):
        return buf.at[slot, t] if row is None else buf.at[slot, row, t]

    def dsem(slot, t, s):
        return (sem.at[slot, t, s] if row is None
                else sem.at[slot, row, t, s])

    def page_for(j):
        j = jnp.minimum(j, jnp.maximum(num_iters - 1, 0))  # DMA-safe clamp
        if not sinks:
            idx = first_window + j
        else:
            idx = jnp.where(j < sink_pages, j,
                            first_window + (j - sink_pages))
        return jnp.minimum(idx, pp_seq - 1)

    def page_src(hbm, page):
        # layer_idx (a scalar read from SMEM, so every layer of a model
        # shares one traced and lowered kernel): the operand is the
        # engine's full [layers, pages, …] stack and the kernel indexes
        # the layer itself — slicing the
        # stack OUTSIDE a pallas_call materializes a full per-layer copy
        # at the custom-call boundary (XLA cannot fuse a producer slice
        # into a custom call; measured ~0.9 ms/layer/step in the decode
        # burst). h=None: merged-heads mode — one whole-page copy
        # carries every kv head, cutting the DMA count by kv_heads×.
        src = hbm if layer_idx is None else hbm.at[layer_idx]
        return src.at[page] if h is None else src.at[page, h]

    def sb_dma(slot, sb, pages=range(kpb)):
        """The K (and V) copies of superblock ``sb``'s sub-pages ``pages``
        into staging slot ``slot``; a sub-page may be a traced index."""
        copies = []
        for t in pages:
            page = page_table_ref[b, page_for(sb * kpb + t)]
            copies.append(pltpu.make_async_copy(
                page_src(k_hbm, page), dst(k_scratch, slot, t),
                dsem(slot, t, 0)
            ))
            if not shared_kv:
                copies.append(pltpu.make_async_copy(
                    page_src(v_hbm, page), dst(v_scratch, slot, t),
                    dsem(slot, t, 1)
                ))
        return copies

    def positions(sb, park, page_size, first=0, pages=kpb):
        """Key positions of ``pages`` sub-pages of superblock ``sb`` from
        its sub-page ``first`` on (the whole superblock by default), as
        [1, pages*page_size] i32.

        Built directly in the flat lane layout — Mosaic's
        infer-vector-layout rejects the (kpb, page_size) →
        (1, kpb*page_size) shape cast (sublane→lane collapse) — by
        deriving sub-page index and in-page offset from one lane iota.
        Sub-pages past ``num_iters`` park at ``park`` (a position every
        mask term rejects: ctx_len for decode, total_len for prefill).
        """
        j = jax.lax.broadcasted_iota(jnp.int32, (1, pages * page_size), 1)
        jp = j // page_size
        sub = sb * kpb + first + jp
        pos = page_for(sub) * page_size + (j - jp * page_size)
        return jnp.where(sub < num_iters, pos, park)

    return positions, sb_dma


def _decode_stream_bounds(ctx_len, q_end, page_size, sliding_window, sinks):
    """(first_window, sink_pages, num_iters) for a decode stream over
    keys [0, ctx_len). One definition for the per-head and merged decode
    kernels so the window/sink page arithmetic cannot drift between
    them (same rationale as ``_superblock_streamer``). SWA skips pages
    wholly before q_end - window (``q_end`` is the exclusive query
    position bound: ctx_len for a decode row); sinks keep the first
    ceil(S/page_size) pages streamed via the loop-counter remap."""
    num_pages = (ctx_len + page_size - 1) // page_size
    if sliding_window is not None:
        first_window = jnp.minimum(
            jnp.maximum(q_end - sliding_window, 0) // page_size, num_pages)
    else:
        first_window = jnp.int32(0)
    if sinks:
        sink_pages = jnp.minimum(
            (sinks + page_size - 1) // page_size, num_pages)
        first_window = jnp.maximum(first_window, sink_pages)
    else:
        sink_pages = jnp.int32(0)
    num_iters = sink_pages + num_pages - first_window
    return first_window, sink_pages, num_iters


def _decode_mask(positions, ctx_len, sliding_window, sinks, back=None,
                 first_key=0):
    """Attendability of decode key ``positions``: in-bounds (< ctx_len),
    and inside the sliding window of the query at position ``ctx_len - 1``
    unless a sink position. Shared between the per-head and merged
    decode kernels.

    ``back`` ([query rows, 1], rows of more than one position): how many
    positions before the row's last a query row stands, and so how many
    keys fewer it sees; the mask is then ``[query rows, keys]``.
    ``first_key``: the keys below it are nobody's (a prediction module's
    layer holds nothing at slot 0)."""
    if back is not None:
        ctx_len = ctx_len - back
    in_bounds = positions < ctx_len
    if first_key:
        in_bounds = in_bounds & (positions >= first_key)
    if sliding_window is not None:
        in_window = positions >= ctx_len - sliding_window
        if sinks:
            in_window = in_window | (positions < sinks)
        in_bounds = in_bounds & in_window
    return in_bounds


# Keys of a decode granule: the width of one MXU pass on a v5e, and eight
# pages of 16 tokens.
_GRANULE_KEYS = 128


def _granule_pages(pages_per_block: int, page_size: int) -> int:
    """Pages a decode granule holds: ``_GRANULE_KEYS`` keys where the
    superblock is a whole number of such granules, else the superblock
    (one narrower than a granule, or an explicit ``pages_per_block`` that
    granules do not divide)."""
    pages = max(1, _GRANULE_KEYS // page_size)
    return pages if pages_per_block % pages == 0 else pages_per_block


class _LiveGranules(NamedTuple):
    """What a decode kernel asks of one row's stream (``_live_granules``)."""
    num_sb: jax.Array  # rounds the row takes
    start: Callable  # (slot, sb): start round sb's live copies, if any
    fold_round: Callable  # (slot, sb, state, fold) -> state after round sb
    staged: Callable  # (scratch, slot, g) -> ref of the pages staged there
    mask: Callable  # (sb, g) -> [1, keys] attendability of those pages' keys


def _live_granules(page_table_ref, b, h, k_hbm, v_hbm, k_scratch, v_scratch,
                   sem, *, ctx_len, page_size, kpb, sliding_window,
                   sinks, shared_kv, shared_copy, layer_idx, row=None,
                   back=None, first_key=0):
    """One decode row's stream over keys [0, ctx_len), cut to the keys it
    has. The superblock stays the DMA batch (``kpb`` pages a round, double
    buffered); inside it a *granule* of ``_granule_pages`` pages is the
    unit that is copied, waited for and folded. Granule ``g`` of round
    ``sb`` is live while ``sb*kpb + g*pages < num_iters``. A live granule
    is streamed whole (its sub-pages past ``num_iters`` are the streamer's
    clamped copies, so every key a fold reads was written by a copy of
    this round); a dead one is neither started, nor waited for, nor
    folded: ``live(sb)`` is the one bound of all three. A row of 330 keys
    therefore moves and multiplies 384, not the 1024 of its superblock,
    and what an earlier row left in the rest of the scratch is never
    read. Past a row's last round ``live`` is 0, which is the per-row
    guard of a multi-row program: the row's state passes through.

    ``fold_round`` is the schedule. A round whose granules are all live
    (every round but the last of a long row) waits for the batch and
    folds it as one ``kpb*page_size``-wide round, as it always did: one
    wide fold costs less per key than eight narrow ones (v5e, PR 33:
    8.2 against 11.2 us per 1024 keys of 8 kv heads). A round cut short
    folds its live granules one by one in a ``fori_loop`` — lowered once,
    whatever the count — waiting for each as it comes, so a fold runs
    under the copies of the granules behind it. ``fold(state, g)`` is the
    kernel's: fold granule ``g``, or the whole superblock when ``g`` is
    None, read through ``staged`` and ``mask``.

    With ``shared_copy`` what has landed is mirrored from the K scratch
    into the V scratch before its fold (absorbed MLA measured 2x SLOWER
    with v aliased to k at b8/ctx4k, July 2026, ROADMAP D3: one buffer
    feeding a head_dim-contraction and a key-contraction forces Mosaic
    into per-round relayouts; a local VMEM->VMEM copy gives each matmul
    its own buffer while HBM still sees ONE latent read)."""
    pages = _granule_pages(kpb, page_size)
    per_round = kpb // pages
    first_window, sink_pages, num_iters = _decode_stream_bounds(
        ctx_len, ctx_len, page_size, sliding_window, sinks)
    positions, sb_dma = _superblock_streamer(
        page_table_ref, b, h, k_hbm, v_hbm, k_scratch, v_scratch, sem,
        kpb=kpb, num_iters=num_iters, first_window=first_window,
        sink_pages=sink_pages, sinks=sinks, shared_kv=shared_kv,
        layer_idx=layer_idx, row=row)

    def live(sb):
        return jnp.clip((num_iters - sb * kpb + pages - 1) // pages,
                        0, per_round)

    def copies(slot, sb, g):
        return sb_dma(slot, sb, [g * pages + t for t in range(pages)])

    def start(slot, sb):
        def granule(g, carry):
            for c in copies(slot, sb, g):
                c.start()
            return carry

        jax.lax.fori_loop(0, live(sb), granule, None)

    def staged(buf, slot, g):
        view = buf.at[slot] if row is None else buf.at[slot, row]
        return view if g is None else view.at[pl.ds(g * pages, pages)]

    def mirror(slot, g):
        if shared_copy:
            # The V semaphore of the first page mirrored: the V stream is
            # skipped, so nothing else signals it.
            done = sem.at[slot] if row is None else sem.at[slot, row]
            cp = pltpu.make_async_copy(
                staged(k_scratch, slot, g), staged(v_scratch, slot, g),
                done.at[0 if g is None else g * pages, 1])
            cp.start()
            cp.wait()

    def mask(sb, g):
        # The pages may straddle the sink→window jump; per-sub-page
        # positions keep the mask exact. Sub-pages past num_iters park at
        # ctx_len so every mask term rejects them.
        first, n = (0, kpb) if g is None else (g * pages, pages)
        return _decode_mask(
            positions(sb, ctx_len, page_size, first=first, pages=n),
            ctx_len, sliding_window, sinks, back, first_key)

    def fold_round(slot, sb, state, fold):
        n = live(sb)

        def landed(g):
            for c in copies(slot, sb, g):
                c.wait()

        def granule(g, state):
            landed(g)
            mirror(slot, g)
            return fold(state, g)

        def cut(state):
            return jax.lax.fori_loop(0, n, granule, state)

        def whole(state):
            jax.lax.fori_loop(0, per_round, lambda g, _: landed(g), None)
            mirror(slot, None)
            return fold(state, None)

        if per_round == 1:  # the granule is the superblock
            return cut(state)
        return jax.lax.cond(n == per_round, whole, cut, state)

    return _LiveGranules((num_iters + kpb - 1) // kpb, start, fold_round,
                         staged, mask)


def _fold(q_h, k, v, ok, m, l, acc, *, scale):
    """One online-softmax round of one head: fold keys ``k`` / values
    ``v`` [keys, head_dim], attendable where ``ok`` [1, keys], into the
    state of queries ``q_h`` [group, head_dim]. Operands stay in the
    cache dtype with the scale applied to the fp32 scores after the
    matmul: bf16×bf16 + fp32 accumulate is the MXU fast path and matches
    the XLA reference's numerics. At least one key of a first fold must
    be attendable (an all-masked round with m still at -inf would turn
    exp(scores - m) into exp(0) garbage): every page a row streams holds
    one."""
    scores = jax.lax.dot_general(
        q_h, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [group, keys]
    scores = jnp.where(ok, scores, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _fold_state(group, head_dim):
    """(m, l, acc) of one head before its first fold."""
    return (jnp.full((group, 1), _NEG_INF, jnp.float32),
            jnp.zeros((group, 1), jnp.float32),
            jnp.zeros((group, head_dim), jnp.float32))


def _decode_kernel(
    # scalar prefetch
    page_table_ref,  # [batch, pages_per_seq] int32 (SMEM)
    ctx_lens_ref,  # [batch] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM): layer of a stacked cache, else unused
    # inputs
    q_ref,  # [1, 1, group, head_dim] VMEM block for (b, h)
    k_hbm,  # [num_pages, kv_heads, page_size, head_dim] (ANY/HBM)
    v_hbm,  # same
    # output
    o_ref,  # [1, 1, group, head_dim] VMEM block
    # scratch
    k_scratch,  # [2, pages_per_block, page_size, head_dim] VMEM
    v_scratch,  # same
    sem,  # DMA semaphores [2, pages_per_block, 2]
    *,
    page_size: int,
    scale: float,
    sliding_window: int | None,
    sinks: int,
    pages_per_block: int,
    shared_kv: bool,
    shared_copy: bool,
    stacked: bool,
    q_positions: int = 1,
    first_key: int = 0,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    group, head_dim = q_ref.shape[2], q_ref.shape[3]

    ctx_len = ctx_lens_ref[b]
    back = None
    if q_positions > 1:
        # The block's rows are the row's positions, each with its heads
        # (``group`` is both): position j sees q_positions - 1 - j keys
        # fewer than the last.
        back = (q_positions - 1) - jax.lax.broadcasted_iota(
            jnp.int32, (group, 1), 0) // (group // q_positions)
    # SWA: pages entirely outside the window are skipped, so long contexts
    # stream only ~window/page_size pages. Attention sinks (StreamingLLM,
    # reference events.go:40 sink_full_attention) additionally stream the
    # first ceil(S/page_size) pages: the loop counter j is remapped to a
    # page index — sink pages [0, sink_pages) first, then window pages
    # [first_window, num_pages) — so the double-buffered DMA pipeline is
    # unchanged and the skipped middle costs nothing.
    #
    # Pages stream in superblocks of ``pages_per_block``: a round is one
    # batch of in-flight DMAs (4 KB single-page transfers underuse HBM
    # bandwidth) and feeds the MXU a [head_dim, kpb·page_size] operand
    # instead of a page_size-wide sliver; a round the row does not fill
    # is cut to its live 128-key granules (``_live_granules``).
    st = _live_granules(
        page_table_ref, b, h, k_hbm, v_hbm, k_scratch, v_scratch, sem,
        ctx_len=ctx_len, page_size=page_size,
        kpb=pages_per_block, sliding_window=sliding_window, sinks=sinks,
        shared_kv=shared_kv, shared_copy=shared_copy,
        layer_idx=layer_ref[0] if stacked else None, back=back,
        first_key=first_key)

    st.start(0, 0)
    q = q_ref[0, 0]  # [group, head_dim], cache dtype (see ``_fold``)

    def body(sb, state):
        slot = sb % 2
        st.start((sb + 1) % 2, sb + 1)  # nothing past the last round

        def fold(state, g):
            # [pages, page_size, head_dim] → leading-collapse reshape
            # (lane dim unchanged).
            k = st.staged(k_scratch, slot, g)[...].reshape(-1, head_dim)
            if shared_kv and not shared_copy:
                v = k
            else:
                v = st.staged(v_scratch, slot, g)[...].reshape(-1, head_dim)
            return _fold(q, k, v, st.mask(sb, g), *state, scale=scale)

        return st.fold_round(slot, sb, state, fold)

    m_fin, l_fin, acc = jax.lax.fori_loop(
        0, st.num_sb, body, _fold_state(group, head_dim))
    out = acc / jnp.maximum(l_fin, 1e-30)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_kernel_merged(
    # scalar prefetch
    page_table_ref,  # [batch, pages_per_seq] int32 (SMEM)
    ctx_lens_ref,  # [batch] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM): layer of a stacked cache, else unused
    # inputs
    q_ref,  # [1, kv_heads, group, head_dim] VMEM block for (b,)
    k_hbm,  # [num_pages, kv_heads, page_size, head_dim] (ANY/HBM)
    v_hbm,  # same
    # output
    o_ref,  # [1, kv_heads, group, head_dim] VMEM block
    # scratch
    k_scratch,  # [2, pages_per_block, kv_heads, page_size, head_dim] VMEM
    v_scratch,  # same
    sem,  # DMA semaphores [2, pages_per_block, 2]
    *,
    page_size: int,
    scale: float,
    sliding_window: int | None,
    sinks: int,
    pages_per_block: int,
    shared_kv: bool,
    shared_copy: bool,
    stacked: bool,
    quant: bool = False,
):
    """Decode with every kv head — and up to ``batch_rows`` batch items —
    in ONE program.

    ``quant``: the cache operands/scratch hold 1-byte (fp8 e4m3) pages
    in the flat whole-page layout ``[.., kv_heads*page_size, head_dim]``
    (see the wrapper's quant arm); each fold upcasts the staged granule
    to the query dtype once and the head loop slices the upcast value —
    HBM moved half the bytes, the MXU still sees bf16.

    The per-head grid (``_decode_kernel``) pays pipeline fill/drain and
    per-page 4 KB DMAs once per (batch, head) program. Merging heads
    makes each sub-page copy one whole-page transfer carrying all kv
    heads (DMA count ÷ kv_heads), computes the position mask once per
    granule instead of per head, and amortizes the program overhead over
    kv_heads× more work. The head loop is a static Python unroll of
    per-head [group, head_dim]×[head_dim, keys] matmuls over the shared
    streamed granule; the loop over a round's live granules is a
    ``fori_loop`` (``_live_granules``), so the body is lowered once.

    ``batch_rows > 1`` additionally co-schedules several batch items per
    program: each round issues every row's live copies together (more
    copies in flight against the same HBM latency) and the pipeline
    fills/drains once per program instead of once per batch item. A row
    out of rounds has no live granule: it starts, waits and folds
    nothing and its state passes through; ragged contexts therefore cost
    bandwidth only up to each row's own length. VMEM budgeting in the
    wrapper divides the superblock across rows, so keys-per-round
    shrinks as rows grow — the on-chip sweep picks the operating point.
    """
    b0 = pl.program_id(0)
    rows, kv_heads, group = q_ref.shape[0], q_ref.shape[1], q_ref.shape[2]
    head_dim = q_ref.shape[3]

    streams = []
    for r in range(rows):
        b = b0 * rows + r
        streams.append(_live_granules(
            page_table_ref, b, None, k_hbm, v_hbm, k_scratch, v_scratch,
            sem, ctx_len=ctx_lens_ref[b],
            page_size=page_size, kpb=pages_per_block,
            sliding_window=sliding_window, sinks=sinks, shared_kv=shared_kv,
            shared_copy=shared_copy,
            layer_idx=layer_ref[0] if stacked else None,
            row=r if rows > 1 else None))
    num_sb = functools.reduce(jnp.maximum, [st.num_sb for st in streams])

    def start_round(slot, sb):
        for st in streams:
            st.start(slot, sb)

    start_round(0, 0)
    # qs[r][h]: [group, head_dim]
    qs = [[q_ref[r, h] for h in range(kv_heads)] for r in range(rows)]

    def body(sb, carry):
        slot = sb % 2
        start_round((sb + 1) % 2, sb + 1)  # nothing past a last round

        def fold(state, g, r, st):
            # Shared mask for every head: positions depend only on the
            # row's pages — the per-head grid recomputed this kv_heads×.
            ok = st.mask(sb, g)
            k_g = st.staged(k_scratch, slot, g)
            v_g = k_g if shared_kv and not shared_copy else st.staged(
                v_scratch, slot, g)
            if quant:
                # One upcast of what was staged (the fp8→bf16 convert is
                # exact); every head slices the same value.
                k_g = k_g[...].astype(q_ref.dtype)
                v_g = v_g[...].astype(q_ref.dtype)

            def head(staged, h):
                # This head's [pages, page_size, head_dim] → leading-
                # collapse reshape (lane dim unchanged).
                x = (staged[:, h * page_size:(h + 1) * page_size] if quant
                     else staged[:, h])
                return x.reshape(-1, head_dim)

            new = []
            for h in range(kv_heads):
                k = head(k_g, h)
                v = k if v_g is k_g else head(v_g, h)
                new.append(_fold(qs[r][h], k, v, ok, *state[h], scale=scale))
            return tuple(new)

        return tuple(
            st.fold_round(slot, sb, carry[r],
                          functools.partial(fold, r=r, st=st))
            for r, st in enumerate(streams))

    # state[r][h]: (m, l, acc)
    state = jax.lax.fori_loop(
        0, num_sb, body,
        tuple(tuple(_fold_state(group, head_dim) for _ in range(kv_heads))
              for _ in range(rows)))

    for r in range(rows):
        for h in range(kv_heads):
            m_fin, l_fin, acc = state[r][h]
            out = acc / jnp.maximum(l_fin, 1e-30)
            o_ref[r, h] = out.astype(o_ref.dtype)


def _prefill_kernel(
    # scalar prefetch
    page_table_ref,  # [batch, pages_per_seq] int32
    ctx_lens_ref,  # [batch] int32 (tokens already cached BEFORE the new ones)
    total_lens_ref,  # [batch] int32 (ctx + new)
    layer_ref,  # [1] int32: layer of a stacked cache, else unused
    # inputs
    q_ref,  # [1, q_tile, heads_group, head_dim] block for (b, h, qt)
    k_hbm,
    v_hbm,
    # then: [bias_ref,] o_ref, k_scratch, v_scratch, sem
    *refs,
    page_size: int,
    q_tile: int,
    scale: float,
    sliding_window: int | None,
    sinks: int,
    pages_per_block: int,
    shared_kv: bool,
    stacked: bool,
    has_bias: bool = False,
):
    # bias_ref: [1, q_tile, keys] float32 added to the tile's scaled scores,
    # key position = lane (0 keeps a key, -1e30 drops it: a query's learned
    # selection). Scratch: k [2, pages_per_block, page_size, head_dim], v
    # likewise, sem [2, pages_per_block, 2].
    bias_ref = refs[0] if has_bias else None
    o_ref, k_scratch, v_scratch, sem = refs[1:] if has_bias else refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    qt = pl.program_id(2)
    # q_ref block: [1, 1, q_tile, 1, group, head_dim]
    group, head_dim = q_ref.shape[4], q_ref.shape[5]
    kpb = pages_per_block

    ctx_len = ctx_lens_ref[b]
    total_len = total_lens_ref[b]
    # Query rows in this tile sit at logical positions ctx_len + qt*q_tile + i.
    q_start = ctx_len + qt * q_tile
    # Causality: this tile needs keys up to position q_start + q_tile - 1.
    max_key = jnp.minimum(q_start + q_tile, total_len)
    num_pages = (max_key + page_size - 1) // page_size
    # SWA: the earliest key any query in this tile can see is
    # q_start - W + 1 (XLA convention: q_pos - k_pos < W), so pages wholly
    # before it are never streamed — long contexts cost ~W/page_size pages
    # per tile, matching the decode kernel's page skipping. Sinks keep the
    # first ceil(S/page_size) pages streamed too, via the same loop-counter
    # → page-index remap as the decode kernel.
    if sliding_window is not None:
        first_window = jnp.maximum(q_start - sliding_window + 1, 0) // page_size
    else:
        first_window = jnp.int32(0)
    if sinks:
        sink_pages = jnp.minimum(
            (sinks + page_size - 1) // page_size, num_pages)
        first_window = jnp.maximum(first_window, sink_pages)
    else:
        sink_pages = jnp.int32(0)
    num_iters = sink_pages + num_pages - jnp.minimum(first_window, num_pages)
    # MXU utilization: pages stream in superblocks of ``kpb`` pages, so
    # each online-softmax round multiplies [group·q_tile, head_dim] by
    # [head_dim, kpb·page_size] — full 128-wide MXU tiles instead of one
    # page_size-wide sliver per round (the round-2 kernel's 12×-slower
    # root cause). A superblock may
    # straddle the sink→window jump: each sub-page's positions come from
    # its own remapped index, so masking stays exact.
    num_sb = (num_iters + kpb - 1) // kpb

    sb_positions, sb_dma = _superblock_streamer(
        page_table_ref, b, h, k_hbm, v_hbm, k_scratch, v_scratch, sem,
        kpb=kpb, num_iters=num_iters, first_window=first_window,
        sink_pages=sink_pages, sinks=sinks, shared_kv=shared_kv,
        layer_idx=layer_ref[0] if stacked else None)

    @pl.when(num_sb > 0)
    def _():
        for c in sb_dma(0, 0):
            c.start()

    # Keep q in the cache dtype and scale AFTER the QK^T matmul (fp32
    # scores): bf16×bf16 with fp32 accumulation is the MXU fast path, and
    # it matches the XLA reference's numerics (paged_attention scales the
    # fp32 einsum output).
    q = q_ref[0, 0, :, 0]  # [q_tile, group, head_dim]
    q2d = q.transpose(1, 0, 2)  # [group, q_tile, head_dim]
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_tile, 1), 0)

    def body(sb, carry):
        m_prev, l_prev, acc_prev = carry
        slot = sb % 2
        next_slot = (sb + 1) % 2

        @pl.when(sb + 1 < num_sb)
        def _():
            for c in sb_dma(next_slot, sb + 1):
                c.start()

        for c in sb_dma(slot, sb):
            c.wait()

        k = k_scratch[slot].reshape(kpb * page_size, head_dim)
        v = k if shared_kv else v_scratch[slot].reshape(
            kpb * page_size, head_dim)

        # [group, q_tile, kpb*page_size], fp32 accumulate off bf16 operands
        scores = jax.lax.dot_general(
            q2d, k, dimension_numbers=(((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        # Per-sub-page key positions (each from its own remapped page
        # index); sub-pages past num_iters park at total_len so every
        # mask term rejects them.
        k_pos = sb_positions(sb, total_len, page_size)
        mask = (k_pos <= q_pos) & (k_pos < total_len)  # [q_tile, kpb*ps]
        if sliding_window is not None:
            in_window = q_pos - k_pos < sliding_window
            if sinks:
                in_window = in_window | (k_pos < sinks)
            mask = mask & in_window
        if has_bias:
            # No window, no sinks (the wrapper checks): superblock sb holds
            # the keys at [sb * keys, (sb + 1) * keys).
            keys = kpb * page_size
            bias = bias_ref[0, :, pl.ds(pl.multiple_of(sb * keys, keys),
                                        keys)]
            mask = mask & (bias > 0.5 * _NEG_INF)
        scores = jnp.where(mask[None], scores, _NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        if has_bias:
            # A superblock in which a query keeps nothing must add nothing
            # (without a selection a streamed superblock always holds a
            # key the query sees, so exp(-1e30 - m) is 0 there by itself).
            p = jnp.where(mask[None], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((group, q_tile, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((group, q_tile, 1), jnp.float32)
    acc0 = jnp.zeros((group, q_tile, head_dim), jnp.float32)
    _m, l_fin, acc = jax.lax.fori_loop(0, num_sb, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l_fin, 1e-30)  # [group, q_tile, head_dim]
    o_ref[0, 0, :, 0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


def _ragged_kernel(
    # scalar prefetch
    page_table_ref,  # [rows, pages_per_seq] int32 (SMEM)
    row_starts_ref,  # [rows+1] int32: per-row flat-token prefix sums
    ctx_lens_ref,  # [rows] int32 (tokens cached BEFORE each row's new ones)
    block_first_ref,  # [num_q_blocks] int32: first row touching each block
    block_rows_ref,  # [num_q_blocks] int32: rows touching each block
    layer_ref,  # [1] int32: layer of a stacked cache, else unused
    # inputs
    q_ref,  # [1, q_tile, kv_heads, group, head_dim] VMEM block for (g,)
    k_hbm,  # [num_pages, kv_heads, page_size, head_dim] (ANY/HBM)
    v_hbm,  # same
    # output
    o_ref,  # [1, q_tile, kv_heads, group, head_dim] VMEM block
    # scratch
    k_scratch,  # [2, pages_per_block, kv_heads, page_size, head_dim] VMEM
    v_scratch,  # same
    sem,  # DMA semaphores [2, pages_per_block, 2]
    *,
    page_size: int,
    scale: float,
    q_tile: int,
    sliding_window: int | None,
    sinks: int,
    pages_per_block: int,
    shared_kv: bool,
    shared_copy: bool,
    stacked: bool,
    quant: bool = False,
):
    """One grid over a ragged mixed prefill+decode batch.

    The batch is a FLAT token axis: row r's new tokens occupy flat slots
    ``[row_starts[r], row_starts[r+1])`` at logical positions
    ``ctx_lens[r] + i`` — a 1-token decode row and a 512-token prefill
    chunk are just rows of different lengths, with zero per-sequence
    padding (only the axis tail pads to a ``q_tile`` multiple). The grid
    is BLOCK-centric — one program per aligned q block, all kv heads
    merged (whole-page DMAs carry every head, as in
    ``_decode_kernel_merged``) — so a block's output is owned by exactly
    one program and rows straddling a block boundary cannot race. Rows
    intersecting the block are walked by a dynamic ``fori_loop`` off the
    prefix-summed metadata; each row streams its own page window through
    ``_superblock_streamer`` with ``_decode_stream_bounds`` arithmetic
    (``q_end`` = its first in-block query position + 1 reproduces the
    prefill kernel's ``max(q_first - W + 1, 0) // page_size`` window
    start), and its q rows are committed into the block state with a
    per-row liveness select — the ragged analogue of the merged decode
    kernel's live guard (a foreign row's all-masked scores would
    otherwise poison m/l/acc).

    ``quant``: fp8 (1-byte) pages in the flat whole-page layout with a
    per-round upcast, exactly the merged decode kernel's operand mode.
    """
    g = pl.program_id(0)
    kv_heads, group = q_ref.shape[2], q_ref.shape[3]
    head_dim = q_ref.shape[4]
    kpb = pages_per_block
    blk_start = g * q_tile

    first_row = block_first_ref[g]
    n_rows = block_rows_ref[g]

    # qs[h]: [group, q_tile, head_dim] (cache dtype; fp32 scores after the
    # matmul — the MXU fast path, same numerics as the other kernels).
    qs = [q_ref[0, :, h].transpose(1, 0, 2) for h in range(kv_heads)]
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_tile, 1), 0)

    def row_body(ri, state):
        r = first_row + ri
        row_start = row_starts_ref[r]
        row_end = row_starts_ref[r + 1]
        ctx_len = ctx_lens_ref[r]

        flat = blk_start + qi  # [q_tile, 1] flat token index of each q row
        q_live = (flat >= row_start) & (flat < row_end)
        # Logical query positions as if every q row belonged to row r —
        # garbage for foreign rows, discarded by the liveness select.
        q_pos = ctx_len + flat - row_start
        # Keys this block needs from row r: up to its last in-block query
        # (causal; the new tokens' KV is already scattered, so kv_limit
        # includes them), starting from the first in-block query's window.
        kv_limit = (ctx_len - row_start
                    + jnp.minimum(row_end, blk_start + q_tile))
        q_first = ctx_len + jnp.maximum(row_start, blk_start) - row_start
        fw, sp, ni = _decode_stream_bounds(
            kv_limit, q_first + 1, page_size, sliding_window, sinks)
        num_sb = (ni + kpb - 1) // kpb
        sb_positions, sb_dma = _superblock_streamer(
            page_table_ref, r, None, k_hbm, v_hbm, k_scratch, v_scratch,
            sem, kpb=kpb, num_iters=ni, first_window=fw, sink_pages=sp,
            sinks=sinks, shared_kv=shared_kv,
            layer_idx=layer_ref[0] if stacked else None)

        @pl.when(num_sb > 0)
        def _():
            for c in sb_dma(0, 0):
                c.start()

        def body(sb, carry):
            ms, ls, accs = carry
            slot = sb % 2
            next_slot = (sb + 1) % 2

            @pl.when(sb + 1 < num_sb)
            def _():
                for c in sb_dma(next_slot, sb + 1):
                    c.start()

            for c in sb_dma(slot, sb):
                c.wait()
            if shared_copy:
                # Same rationale as the decode kernels: mirror the K
                # superblock into the V scratch locally so each matmul
                # gets its own buffer (one HBM read).
                cp = pltpu.make_async_copy(
                    k_scratch.at[slot], v_scratch.at[slot],
                    sem.at[slot, 0, 1])
                cp.start()
                cp.wait()

            # Shared mask for every head; park at kv_limit so parked
            # sub-pages are rejected by the in-bounds term.
            k_pos = sb_positions(sb, kv_limit, page_size)  # [1, kpb*ps]
            mask = (k_pos <= q_pos) & (k_pos < kv_limit)  # [q_tile, K]
            if sliding_window is not None:
                in_window = q_pos - k_pos < sliding_window
                if sinks:
                    in_window = in_window | (k_pos < sinks)
                mask = mask & in_window

            if quant:
                # One upcast of the staged superblock (fp8→bf16 exact);
                # every head slices the same value.
                kq = k_scratch[slot].astype(q_ref.dtype)
                vq = v_scratch[slot].astype(q_ref.dtype)

            new_ms, new_ls, new_accs = [], [], []
            for h in range(kv_heads):
                if quant:
                    k = kq[:, h * page_size:(h + 1) * page_size, :].reshape(
                        kpb * page_size, head_dim)
                    v = vq[:, h * page_size:(h + 1) * page_size, :].reshape(
                        kpb * page_size, head_dim)
                else:
                    k = k_scratch[slot, :, h].reshape(
                        kpb * page_size, head_dim)
                    if shared_kv:
                        v = (v_scratch[slot, :, h].reshape(
                            kpb * page_size, head_dim) if shared_copy else k)
                    else:
                        v = v_scratch[slot, :, h].reshape(
                            kpb * page_size, head_dim)
                scores = jax.lax.dot_general(
                    qs[h], k, dimension_numbers=(((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [group, q_tile, kpb*page_size]
                scores = jnp.where(mask[None], scores, _NEG_INF)

                m_cur = jnp.max(scores, axis=-1, keepdims=True)
                m_new = jnp.maximum(ms[h], m_cur)
                p = jnp.exp(scores - m_new)
                alpha = jnp.exp(ms[h] - m_new)
                l_new = ls[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc_new = accs[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v,
                    dimension_numbers=(((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                new_ms.append(m_new)
                new_ls.append(l_new)
                new_accs.append(acc_new)
            return tuple(new_ms), tuple(new_ls), tuple(new_accs)

        m0 = tuple(jnp.full((group, q_tile, 1), _NEG_INF, jnp.float32)
                   for _ in range(kv_heads))
        l0 = tuple(jnp.zeros((group, q_tile, 1), jnp.float32)
                   for _ in range(kv_heads))
        a0 = tuple(jnp.zeros((group, q_tile, head_dim), jnp.float32)
                   for _ in range(kv_heads))
        m_r, l_r, acc_r = jax.lax.fori_loop(0, num_sb, body, (m0, l0, a0))

        # Commit row r's q rows into the block state; foreign rows keep
        # theirs (the merged decode kernel's live guard, per q row).
        ms, ls, accs = state
        sel = q_live[None]  # [1, q_tile, 1] broadcasts over group/head_dim
        return (
            tuple(jnp.where(sel, m_r[h], ms[h]) for h in range(kv_heads)),
            tuple(jnp.where(sel, l_r[h], ls[h]) for h in range(kv_heads)),
            tuple(jnp.where(sel, acc_r[h], accs[h])
                  for h in range(kv_heads)),
        )

    m0 = tuple(jnp.full((group, q_tile, 1), _NEG_INF, jnp.float32)
               for _ in range(kv_heads))
    l0 = tuple(jnp.zeros((group, q_tile, 1), jnp.float32)
               for _ in range(kv_heads))
    a0 = tuple(jnp.zeros((group, q_tile, head_dim), jnp.float32)
               for _ in range(kv_heads))
    ms, ls, accs = jax.lax.fori_loop(0, n_rows, row_body, (m0, l0, a0))
    for h in range(kv_heads):
        # Pure-padding blocks (n_rows == 0) write zeros (l stays 0).
        out = accs[h] / jnp.maximum(ls[h], 1e-30)  # [group, q_tile, hd]
        o_ref[0, :, h] = out.transpose(1, 0, 2).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("q_tile", "sliding_window", "sinks",
                                    "pages_per_block", "shared_kv",
                                    "shared_stream", "interpret"))
def pallas_paged_ragged_attention(
    q: jax.Array,  # [total_q, q_heads, head_dim] flat mixed batch
    k_cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    v_cache: jax.Array,
    page_table: jax.Array,  # [rows, pages_per_seq] int32
    row_starts: jax.Array,  # [rows+1] int32 flat-token prefix sums
    ctx_lens: jax.Array,  # [rows] cached tokens before each row's new ones
    *,
    q_tile: int = 8,
    sliding_window: int | None = None,
    sinks: int | None = None,
    pages_per_block: int | None = None,
    shared_kv: bool = False,
    shared_stream: str = "copy",
    layer_idx: jax.Array | int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-kernel flash attention over a ragged mixed batch.

    Row r's new tokens occupy flat q slots ``[row_starts[r],
    row_starts[r+1])`` at logical positions ``ctx_lens[r] + i`` and attend
    causally over that row's paged KV (the new tokens' KV already
    scattered, as in the prefill wrapper). Decode rows are length-1 rows;
    prefill chunks are longer rows — one dispatch serves both with no
    per-sequence padding (``total_q`` pads only to a ``q_tile`` multiple;
    slots at and past ``row_starts[-1]`` return unspecified values).
    Returns ``[total_q, q_heads, head_dim]``.

    ``sliding_window``/``sinks`` follow the prefill wrapper's semantics;
    ``shared_kv``/``shared_stream`` the decode wrapper's (absorbed MLA).
    A 1-byte (fp8 e4m3) cache takes the merged decode kernel's quantized
    operand mode: whole flat pages DMA'd at 1 byte/element and upcast
    once per round (needs ``kv_heads * page_size % 32 == 0`` on real
    TPU, merged layout only — same rules as decode).
    """
    total_q, q_heads, head_dim = q.shape
    # layer_idx: stacked caches, in-kernel layer indexing (see the other
    # wrappers — no per-layer slice copy at the custom-call boundary).
    cache_dims = k_cache.shape[1:] if layer_idx is not None else k_cache.shape
    _, kv_heads, page_size, _ = cache_dims
    group = q_heads // kv_heads
    rows = page_table.shape[0]
    assert total_q % q_tile == 0, "pad total_q to a q_tile multiple"
    if sliding_window is None:
        sinks = None  # no-op without a window (see the prefill wrapper)
    _check_head_dim_alignment(head_dim, interpret)
    if shared_stream not in ("copy", "reuse"):
        raise ValueError(
            f"shared_stream must be 'copy' or 'reuse', got {shared_stream!r}")

    num_blocks = total_q // q_tile
    row_starts = row_starts.astype(jnp.int32)
    ctx_lens = ctx_lens.astype(jnp.int32)

    # Block→row intersection metadata, prefix-sum arithmetic on the traced
    # row_starts (searchsorted 'right' minus one lands on the covering row
    # and naturally skips empty rows). Pure-padding blocks (start at or
    # past row_starts[-1]) get zero rows; the kernel writes zeros there.
    blk_starts = jnp.arange(num_blocks, dtype=jnp.int32) * q_tile
    total_real = row_starts[-1]
    first = jnp.clip(
        jnp.searchsorted(row_starts, blk_starts, side="right") - 1,
        0, rows - 1)
    last_tok = jnp.minimum(blk_starts + q_tile, total_real) - 1
    last = jnp.clip(
        jnp.searchsorted(row_starts, last_tok, side="right") - 1,
        0, rows - 1)
    block_first = first.astype(jnp.int32)
    block_rows = jnp.where(blk_starts < total_real,
                           last - first + 1, 0).astype(jnp.int32)

    if pages_per_block is None:
        # Merged-heads VMEM budget (see the decode wrapper) combined with
        # the prefill wrapper's fp32-scores clamp [group, q_tile, keys].
        kv_streams = 1 if shared_kv else 2
        budget = (8 * 2 ** 20) // (
            2 * kv_heads * head_dim
            * max(k_cache.dtype.itemsize, 2) * kv_streams)
        max_keys = max(128, (4 * 2 ** 20) // (4 * group * q_tile))
        keys = min(1024, max_keys, max(page_size, budget))
        pages_per_block = max(1, min(keys // page_size,
                                     page_table.shape[1]))

    # Quantized (fp8 e4m3) cache arm — the merged decode kernel's operand
    # mode carried over verbatim: flat whole-page view, 1-byte DMAs,
    # per-round upcast.
    quant = k_cache.dtype.itemsize == 1
    if quant:
        if shared_kv:
            raise ValueError(
                "quantized (fp8) caches are not supported for shared-kv "
                "(MLA latent) pools")
        if (kv_heads * page_size) % 32 and not interpret:
            raise ValueError(
                f"fp8 pages need kv_heads*page_size % 32 == 0 for "
                f"Mosaic's 8-bit tiling (got {kv_heads}*{page_size})")
        flat = (kv_heads * page_size, head_dim)
        k_cache = k_cache.reshape(k_cache.shape[:-3] + flat)
        v_cache = v_cache.reshape(v_cache.shape[:-3] + flat)

    q_blocked = q.reshape(num_blocks, q_tile, kv_heads, group, head_dim)

    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, scale=head_dim ** -0.5,
        q_tile=q_tile, sliding_window=sliding_window, sinks=int(sinks or 0),
        pages_per_block=pages_per_block, shared_kv=shared_kv,
        shared_copy=shared_kv and shared_stream == "copy",
        stacked=layer_idx is not None, quant=quant,
    )

    if quant:
        k_scr = (2, pages_per_block, kv_heads * page_size, head_dim)
    else:
        k_scr = (2, pages_per_block, kv_heads, page_size, head_dim)
    v_scr = (((1,) * len(k_scr))
             if shared_kv and shared_stream != "copy" else k_scr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec(
                (1, q_tile, kv_heads, group, head_dim),
                lambda g, *_prefetch: (g, 0, 0, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, q_tile, kv_heads, group, head_dim),
            lambda g, *_prefetch: (g, 0, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM(k_scr, k_cache.dtype),
            pltpu.VMEM(v_scr, k_cache.dtype),
            pltpu.SemaphoreType.DMA((2, pages_per_block, 2)),
        ],
    )

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (num_blocks, q_tile, kv_heads, group, head_dim), q.dtype
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), row_starts, ctx_lens,
      block_first, block_rows, _layer_operand(layer_idx), q_blocked,
      k_cache, v_cache)

    return out.reshape(total_q, q_heads, head_dim)


@functools.partial(jax.jit,
                   static_argnames=("q_tile", "sliding_window", "sinks",
                                    "pages_per_block", "shared_kv",
                                    "interpret"))
def pallas_paged_prefill_attention(
    q: jax.Array,  # [batch, q_seq, q_heads, head_dim] (new tokens, padded)
    k_cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    ctx_lens: jax.Array,  # [batch] cached tokens before the new ones
    total_lens: jax.Array,  # [batch] ctx + valid new tokens
    *,
    q_tile: int = 16,
    sliding_window: int | None = None,
    sinks: int | None = None,
    pages_per_block: int | None = None,
    shared_kv: bool = False,
    layer_idx: jax.Array | int | None = None,
    bias: jax.Array | None = None,  # [batch, q_seq, keys] float32
    interpret: bool = False,
) -> jax.Array:
    """Flash prefill over paged KV (new tokens' KV already scattered).

    Queries attend causally over cached prefix + themselves, streaming
    page superblocks HBM→VMEM per (batch, kv_head, q_tile) program.
    Returns ``[batch, q_seq, q_heads, head_dim]``. ``q_seq`` must divide
    by ``q_tile`` (callers pad; padded rows are masked out by
    total_lens). ``sliding_window=W`` restricts each query to the last W
    keys and skips pages wholly out of window; ``sinks=S`` keeps the
    first S positions attendable past the window (StreamingLLM; needs a
    window). ``pages_per_block`` sets the keys per online-softmax round
    (``pages_per_block * page_size``); the default targets 1024 keys per
    round (wider rounds re-stream the queries fewer times), clamped so
    the fp32 scores tile [group, q_tile, keys] stays within a few MB of
    VMEM. Every accepted cell runs this default; the ledger has no pair
    across round widths.

    ``bias`` (learned sparse attention: a query's selection among its
    keys) is float32 ``[batch, q_seq, keys]`` over key positions ``[0,
    pages_per_seq * page_size)``: a key whose entry is ``-1e30`` is dropped
    for that query, any other kept. The causal mask still applies. Not
    with a sliding window.
    """
    batch, q_seq, q_heads, head_dim = q.shape
    # layer_idx: caches are the engine's full [layers, pages, …] stack and
    # the kernel DMAs from [layer_idx, page, …] directly — slicing the
    # stack outside the pallas_call would materialize a per-layer copy at
    # the custom-call boundary.
    cache_dims = k_cache.shape[1:] if layer_idx is not None else k_cache.shape
    _, kv_heads, page_size, _ = cache_dims
    group = q_heads // kv_heads
    assert q_seq % q_tile == 0, "pad q_seq to a q_tile multiple"
    if sliding_window is None:
        # Without a window every position is causally attendable anyway —
        # the sink mask is a semantic no-op, so callers can pass a model's
        # sinks unconditionally (full-attention layers included).
        sinks = None
    _check_head_dim_alignment(head_dim, interpret)
    if pages_per_block is None:
        max_keys = max(128, (4 * 2 ** 20) // (4 * group * q_tile))
        pages_per_block = max(1, min(min(1024, max_keys) // page_size,
                                     page_table.shape[1]))

    # [batch, q_blocks, q_tile, kv_heads, group, head_dim] view via reshape:
    q_blocked = q.reshape(batch, q_seq // q_tile, q_tile, kv_heads, group, head_dim)

    kernel = functools.partial(
        _prefill_kernel, page_size=page_size, q_tile=q_tile,
        scale=head_dim ** -0.5, sliding_window=sliding_window,
        sinks=int(sinks or 0), pages_per_block=pages_per_block,
        shared_kv=shared_kv, stacked=layer_idx is not None,
        **({"has_bias": True} if bias is not None else {}),
    )
    bias_operand, bias_spec, bias_bytes = (), [], 0
    if bias is not None:
        if sliding_window is not None:
            raise ValueError("a selection bias needs full attention: the "
                             "kernel reads it by superblock, in key order")
        # Whole superblocks: the last one is read to its end.
        keys = pages_per_block * page_size
        n_keys = -(-page_table.shape[1] // pages_per_block) * keys
        bias = jnp.pad(bias.astype(jnp.float32),
                       [(0, 0), (0, 0), (0, n_keys - bias.shape[2])],
                       constant_values=_NEG_INF)
        bias_operand = (bias,)
        bias_spec = [pl.BlockSpec((1, q_tile, n_keys),
                                  lambda b, h, qt, *_p: (b, qt, 0))]
        bias_bytes = 2 * q_tile * n_keys * 4

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, kv_heads, q_seq // q_tile),
        in_specs=[
            pl.BlockSpec(
                (1, 1, q_tile, 1, group, head_dim),
                lambda b, h, qt, *_p: (b, qt, 0, h, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            *bias_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, q_tile, 1, group, head_dim),
            lambda b, h, qt, *_p: (b, qt, 0, h, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pages_per_block, page_size, head_dim),
                       k_cache.dtype),
            # shared_kv (absorbed MLA): the V stream is skipped, so its
            # scratch shrinks to a placeholder allocation.
            pltpu.VMEM((1, 1, 1, 1) if shared_kv else
                       (2, pages_per_block, page_size, head_dim),
                       k_cache.dtype),
            pltpu.SemaphoreType.DMA((2, pages_per_block, 2)),
        ],
    )

    # Per program: q and out blocks (double-buffered by the pipeline), the
    # K/V staging slots, the online-softmax state and its update, and the
    # fp32 scores, their exponentials and the cache-dtype probabilities.
    item = k_cache.dtype.itemsize
    keys = pages_per_block * page_size
    rows = group * q_tile
    vmem_bytes = (
        4 * rows * head_dim * q.dtype.itemsize
        + (1 if shared_kv else 2) * 2 * keys * head_dim * item
        + 2 * rows * (head_dim + 2) * 4
        + rows * keys * (4 + 4 + item)
        + bias_bytes)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (batch, q_seq // q_tile, q_tile, kv_heads, group, head_dim), q.dtype
        ),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(vmem_bytes),
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      total_lens.astype(jnp.int32), _layer_operand(layer_idx),
      q_blocked, k_cache, v_cache, *bias_operand)

    return out.reshape(batch, q_seq, q_heads, head_dim)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "sliding_window", "sinks",
                                    "pages_per_block", "shared_kv",
                                    "shared_stream", "merge_heads",
                                    "batch_rows", "first_key"))
def pallas_paged_decode_attention(
    q: jax.Array,  # [batch, q_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    v_cache: jax.Array,  # same
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    ctx_lens: jax.Array,  # [batch] int32 (keys to attend per sequence)
    *,
    sliding_window: int | None = None,
    sinks: int | None = None,
    pages_per_block: int | None = None,
    shared_kv: bool = False,
    shared_stream: str = "copy",
    merge_heads: bool | None = None,
    layer_idx: jax.Array | int | None = None,
    batch_rows: int = 1,
    first_key: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over paged KV. Returns ``[batch, q_heads, head_dim]``.

    ``q`` may be ``[batch, positions, q_heads, head_dim]`` (a step that
    verifies a draft: the row's last ``positions`` tokens at once; the
    result has that shape too). ``ctx_lens`` then counts the keys of the
    row's LAST position and position ``j`` attends ``positions - 1 - j``
    fewer: the positions fold in beside the heads (``positions * group``
    query rows a kv head) under ``_decode_mask``'s ``back``, so the row's
    pages are streamed once for all of them. The per-head grid only (one
    kv head: a latent pool, where the fold is a plain reshape).
    ``first_key``: keys below it are attended by nobody.

    The page size is the cache's native page dimension — the DMA tiles and
    mask arithmetic are derived from it, so no override is offered.
    ``sinks=S`` (StreamingLLM) keeps the first S positions attendable past
    the sliding window; their pages are streamed in addition to the
    window's. MLA's absorbed multi-query form is the ``kv_heads == 1``
    case: one shared latent 'head' serves every query head as one group.

    ``shared_stream`` picks how the ``shared_kv`` latent feeds the two
    matmuls: ``"copy"`` (default) DMAs each page from HBM once and
    locally mirrors it into the V scratch — HBM traffic stays halved
    but each matmul gets its own buffer; ``"reuse"`` aliases V to the K
    scratch (no copy, but the one buffer serves a head_dim-contraction
    and a key-contraction, which measured 2x slower at b8/ctx4k on a
    real v5e — ROADMAP D3). Ignored without ``shared_kv``.

    ``merge_heads`` (default: on when ``kv_heads > 1``) runs every kv
    head of a batch item in one program — whole-page DMAs carry all
    heads, the position mask is computed once per round, and program
    count drops kv_heads× (see ``_decode_kernel_merged``). The per-head
    grid remains for kv_heads == 1 (identical work) and as an escape
    hatch.

    ``batch_rows`` (merged path only) co-schedules that many batch items
    per program: per-round DMAs issue for every row together (more
    copies in flight) and pipeline fill/drain amortizes across rows.
    The VMEM superblock budget is divided across rows, so keys-per-round
    shrinks accordingly; the batch is zero-padded to a multiple (padded
    rows stream nothing and their outputs are sliced off).
    """
    q_positions = 1
    if q.ndim == 4:
        q_positions = q.shape[1]
        q = q.reshape(q.shape[0], -1, q.shape[-1])
    batch, q_heads, head_dim = q.shape
    # layer_idx: see the prefill wrapper — stacked caches, in-kernel
    # layer indexing, no per-layer slice copy at the custom-call boundary.
    cache_dims = k_cache.shape[1:] if layer_idx is not None else k_cache.shape
    num_pages_total, kv_heads, page_size, _ = cache_dims
    group = q_heads // kv_heads
    if sliding_window is None:
        sinks = None  # no-op without a window (see the prefill wrapper)
    _check_head_dim_alignment(head_dim, interpret)
    if merge_heads is None:
        merge_heads = kv_heads > 1
    if (q_positions > 1 or first_key) and (merge_heads or kv_heads > 1):
        raise NotImplementedError(
            "rows of more than one position (and first_key) are built for "
            "the per-head grid over one kv head: a latent pool")
    if shared_stream not in ("copy", "reuse"):
        raise ValueError(
            f"shared_stream must be 'copy' or 'reuse', got {shared_stream!r}")
    if batch_rows > 1 and not merge_heads:
        raise ValueError("batch_rows > 1 requires the merged-heads kernel")
    batch_rows = max(1, min(batch_rows, batch))
    if pages_per_block is None:
        # ~1024 keys per DMA batch: a long row's round has that many keys
        # in flight behind the one being folded. What a row pays follows
        # its own keys, not this width: the kernels copy and fold a
        # round's live 128-key granules only (``_live_granules``), so a
        # short row under a wide superblock costs what it holds. Every
        # accepted cell runs this default; the ledger has no pair across
        # widths. The merged kernel's scratch carries every head per
        # key, so its keys/round are clamped to keep the double-buffered
        # K+V staging ≤ ~8 MB of VMEM; clamped to the table's static
        # page capacity (no row has more to stream), and to a whole
        # number of granules.
        keys = 1024
        if merge_heads:
            kv_streams = 1 if shared_kv else 2
            # Quantized caches stage 1-byte pages but the per-granule
            # upcast materializes bf16 values, so budget as if 2-byte —
            # the explicit pages_per_block knob (and the on-chip sweep)
            # can still push wider.
            budget = (8 * 2 ** 20) // (
                2 * batch_rows * kv_heads * head_dim
                * max(k_cache.dtype.itemsize, 2) * kv_streams)
            keys = min(keys, max(page_size, budget))
        pages_per_block = max(1, min(keys // page_size,
                                     page_table.shape[1]))
        granule = max(1, _GRANULE_KEYS // page_size)
        if pages_per_block > granule:
            pages_per_block -= pages_per_block % granule

    q_blocked = q.reshape(batch, kv_heads, group, head_dim)

    # Multi-row programs: zero-pad the batch to a row multiple. Padded
    # rows have ctx_len 0 → no rounds, no DMAs; their outputs are 0 and
    # sliced off below.
    out_batch = batch
    if batch % batch_rows:
        pad = batch_rows - batch % batch_rows
        bpad = [(0, pad)] + [(0, 0)] * 3
        q_blocked = jnp.pad(q_blocked, bpad)
        page_table = jnp.pad(page_table, [(0, pad), (0, 0)])
        ctx_lens = jnp.pad(ctx_lens, (0, pad))
        batch += pad

    # Quantized (fp8 e4m3) cache arm: DMA the 1-byte pages — the whole
    # point, half the HBM read bytes — and upcast in VMEM before the
    # matmuls. Mosaic's 8-bit tiling is (32, 128), so the per-head
    # [page_size, head_dim] sub-slices the bf16 path copies are
    # misaligned at page_size 16; instead the cache is viewed as
    # contiguous whole pages [.., kv_heads*page_size, head_dim] (a free
    # reshape) and each DMA moves one full page for every head, which is
    # aligned whenever kv_heads*page_size % 32 == 0. Merged-heads only
    # (the per-head grid would need the misaligned sub-slice).
    quant = k_cache.dtype.itemsize == 1
    if quant:
        if shared_kv:
            raise ValueError(
                "quantized (fp8) caches are not supported for shared-kv "
                "(MLA latent) pools")
        if not merge_heads:
            raise ValueError(
                "quantized (fp8) caches need the merged-heads decode "
                "kernel (merge_heads=True)")
        if (kv_heads * page_size) % 32 and not interpret:
            raise ValueError(
                f"fp8 pages need kv_heads*page_size % 32 == 0 for "
                f"Mosaic's 8-bit tiling (got {kv_heads}*{page_size})")
        flat = (kv_heads * page_size, head_dim)
        k_cache = k_cache.reshape(k_cache.shape[:-3] + flat)
        v_cache = v_cache.reshape(v_cache.shape[:-3] + flat)

    if merge_heads:
        rr = batch_rows
        kernel = functools.partial(
            _decode_kernel_merged, page_size=page_size,
            scale=head_dim ** -0.5, sliding_window=sliding_window,
            sinks=int(sinks or 0), pages_per_block=pages_per_block,
            shared_kv=shared_kv,
            shared_copy=shared_kv and shared_stream == "copy",
            stacked=layer_idx is not None, quant=quant,
        )
        if quant:
            k_scr = ((2, pages_per_block, kv_heads * page_size, head_dim)
                     if rr == 1 else
                     (2, rr, pages_per_block, kv_heads * page_size,
                      head_dim))
        else:
            k_scr = ((2, pages_per_block, kv_heads, page_size, head_dim)
                     if rr == 1 else
                     (2, rr, pages_per_block, kv_heads, page_size,
                      head_dim))
        v_scr = (((1,) * len(k_scr))
                 if shared_kv and shared_stream != "copy" else k_scr)
        sem_shape = ((2, pages_per_block, 2) if rr == 1
                     else (2, rr, pages_per_block, 2))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch // rr,),
            in_specs=[
                pl.BlockSpec(
                    (rr, kv_heads, group, head_dim),
                    lambda b, *_prefetch: (b, 0, 0, 0),
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (rr, kv_heads, group, head_dim),
                lambda b, *_prefetch: (b, 0, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM(k_scr, k_cache.dtype),
                pltpu.VMEM(v_scr, k_cache.dtype),
                pltpu.SemaphoreType.DMA(sem_shape),
            ],
        )
    else:
        kernel = functools.partial(
            _decode_kernel, page_size=page_size, scale=head_dim ** -0.5,
            sliding_window=sliding_window, sinks=int(sinks or 0),
            pages_per_block=pages_per_block, shared_kv=shared_kv,
            shared_copy=shared_kv and shared_stream == "copy",
            stacked=layer_idx is not None, q_positions=q_positions,
            first_key=first_key,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, kv_heads),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, head_dim),
                    # scalar-prefetch refs are appended to index_map args
                    lambda b, h, *_prefetch: (b, h, 0, 0),
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, head_dim),
                lambda b, h, *_prefetch: (b, h, 0, 0),
            ),
            scratch_shapes=[
                # DMA staging must match the cache dtype; upcast after load.
                pltpu.VMEM((2, pages_per_block, page_size, head_dim),
                           k_cache.dtype),
                # shared_kv: V stream skipped. "copy" mirrors K into a
                # full V scratch locally (one HBM read, two buffers);
                # "reuse" needs only a placeholder.
                pltpu.VMEM((1, 1, 1, 1)
                           if shared_kv and shared_stream != "copy" else
                           (2, pages_per_block, page_size, head_dim),
                           k_cache.dtype),
                pltpu.SemaphoreType.DMA((2, pages_per_block, 2)),
            ],
        )

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (batch, kv_heads, group, head_dim), q.dtype
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      _layer_operand(layer_idx), q_blocked, k_cache, v_cache)

    if q_positions > 1:
        return out.reshape(batch, q_positions, -1, head_dim)[:out_batch]
    return out.reshape(batch, q_heads, head_dim)[:out_batch]


def _kv_pool_spec(k_cache, stacked=False):
    """Cache PartitionSpec under tp: kv-heads axis sharded, except the
    single-shared-head (MQA/absorbed-MLA) pool, which replicates — a
    width-1 axis cannot shard, and replicating the latent is what lets
    each shard attend its local query heads with zero cross-shard traffic
    (matches ``parallel.serve.shard_kv_pool`` placement). ``stacked``:
    the operand is the full [layers, pages, kvh, ps, hd] stack (kernel
    indexes the layer in-DMA)."""
    from jax.sharding import PartitionSpec as P

    kvh_axis = 2 if stacked else 1
    if k_cache.shape[kvh_axis] == 1:
        return P()
    if stacked:
        return P(None, None, "tp", None, None)
    return P(None, "tp", None, None)


def sharded_paged_decode_attention(
    mesh, q, k_cache, v_cache, page_table, ctx_lens, *,
    sliding_window=None, sinks=None, pages_per_block=None, shared_kv=False,
    shared_stream="copy", merge_heads=None, layer_idx=None,
    interpret=False,
):
    """Flash-decode over a tp-sharded paged KV cache.

    ``pallas_call`` cannot consume sharded operands directly, so each tp
    shard runs the kernel on its local kv heads under ``shard_map``.
    Heads stay shard-local either way the local kernel grids them (one
    program per (batch, local head), or the merged-heads default's one
    program per batch item covering every local head — kv_heads× larger
    scratch per program), so sharding the kv-heads axis needs no
    cross-shard communication at all (the per-block all-reduce happens
    later, at the wo projection). Page tables and lengths are replicated
    control state.

    Shapes are global: q [batch, q_heads, hd] (heads sharded over tp),
    caches [pages, kv_heads, ps, hd] (kv heads sharded over tp; a
    single-head MQA/MLA pool replicates and each shard runs its local
    query heads as one group against the full pool).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(q_, k_, v_, t_, l_):
        return pallas_paged_decode_attention(
            q_, k_, v_, t_, l_, sliding_window=sliding_window, sinks=sinks,
            pages_per_block=pages_per_block, shared_kv=shared_kv,
            shared_stream=shared_stream, merge_heads=merge_heads,
            layer_idx=layer_idx, interpret=interpret,
        )

    kv_spec = _kv_pool_spec(k_cache, stacked=layer_idx is not None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "tp", None), kv_spec, kv_spec,
                  P(None, None), P(None)),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )(q, k_cache, v_cache, page_table, ctx_lens)


def sharded_paged_prefill_attention(
    mesh, q, k_cache, v_cache, page_table, ctx_lens, total_lens, *,
    q_tile=16, sliding_window=None, sinks=None, pages_per_block=None,
    shared_kv=False, layer_idx=None, interpret=False,
):
    """Flash-prefill over a tp-sharded paged KV cache (see the decode
    wrapper's rationale). q: [batch, q_seq, q_heads, hd], heads sharded."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(q_, k_, v_, t_, cl_, tl_):
        return pallas_paged_prefill_attention(
            q_, k_, v_, t_, cl_, tl_, q_tile=q_tile,
            sliding_window=sliding_window, sinks=sinks,
            pages_per_block=pages_per_block, shared_kv=shared_kv,
            layer_idx=layer_idx, interpret=interpret,
        )

    kv_spec = _kv_pool_spec(k_cache, stacked=layer_idx is not None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, "tp", None), kv_spec, kv_spec,
                  P(None, None), P(None), P(None)),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )(q, k_cache, v_cache, page_table, ctx_lens, total_lens)
