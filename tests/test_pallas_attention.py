"""Pallas flash-decode kernel vs the jnp paged-attention reference.

Runs in Pallas interpreter mode on the CPU backend; on TPU the same kernel
compiles to Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops.kv_pages import scatter_kv_pages
from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
    pallas_paged_decode_attention,
)


def build_case(batch=2, ctx=13, q_heads=4, kv_heads=2, head_dim=8,
               page_size=4, num_pages=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pages_per_seq = 4
    k_cache = jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype)
    v_cache = jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype)
    # distinct physical pages per sequence
    table = jnp.asarray(
        1 + np.arange(batch * pages_per_seq).reshape(batch, pages_per_seq),
        jnp.int32,
    )
    ctx_lens = jnp.asarray([ctx, ctx - 5], jnp.int32)[:batch]

    # populate the context KV
    max_ctx = pages_per_seq * page_size
    k_ctx = jnp.asarray(rng.normal(size=(batch, max_ctx, kv_heads, head_dim)), dtype)
    v_ctx = jnp.asarray(rng.normal(size=(batch, max_ctx, kv_heads, head_dim)), dtype)
    positions = jnp.arange(max_ctx)[None, :].repeat(batch, 0)
    valid = positions < ctx_lens[:, None]
    k_cache = scatter_kv_pages(k_cache, k_ctx, table, positions, valid)
    v_cache = scatter_kv_pages(v_cache, v_ctx, table, positions, valid)

    q = jnp.asarray(rng.normal(size=(batch, q_heads, head_dim)), dtype)
    return q, k_cache, v_cache, table, ctx_lens


@pytest.mark.parametrize("ctx", [1, 4, 13, 16])
def test_matches_jnp_reference(ctx):
    q, k_cache, v_cache, table, ctx_lens = build_case(ctx=max(ctx, 6))
    ctx_lens = jnp.asarray([ctx, max(ctx - 1, 1)], jnp.int32)

    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True
    )

    # jnp reference: decode = query at position ctx_len-1 over ctx_len keys
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table,
        (ctx_lens - 1)[:, None], ctx_lens,
    )[:, 0]

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_groups():
    q, k_cache, v_cache, table, ctx_lens = build_case(q_heads=8, kv_heads=2)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None], ctx_lens
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bfloat16_cache():
    q, k_cache, v_cache, table, ctx_lens = build_case(dtype=jnp.bfloat16)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None], ctx_lens
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.parametrize("ctx", [3, 7, 11, 16])
def test_sliding_window(ctx):
    """Kernel-level SWA parity: window masking + out-of-window page skip."""
    q, k_cache, v_cache, table, _ = build_case(ctx=16)
    ctx_lens = jnp.asarray([ctx, max(ctx - 2, 1)], jnp.int32)
    window = 6
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        interpret=True,
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=window,
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ctx,sinks", [(3, 4), (7, 4), (11, 4), (16, 4),
                                       (16, 1), (13, 5)])
def test_attention_sinks(ctx, sinks):
    """StreamingLLM sink mask in-kernel: first-S positions stay attendable
    past the window, their pages streamed via the loop-counter remap —
    parity with the XLA mask across window/sink page overlaps (reference
    spec kind sink_full_attention, events.go:40)."""
    q, k_cache, v_cache, table, _ = build_case(ctx=16)
    ctx_lens = jnp.asarray([ctx, max(ctx - 2, 1)], jnp.int32)
    window = 6
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, interpret=True,
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=window, attention_sinks=sinks,
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sinks_without_window_are_noop():
    """Without a window the causal mask already attends every position, so
    sinks normalize away — callers pass a model's sinks unconditionally
    (full-attention layers included)."""
    q, k_cache, v_cache, table, ctx_lens = build_case()
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sinks=4, interpret=True)
    ref = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_sinks_match_reference():
    """The sink mask survives the shard_map plumbing: tp-sharded
    flash-decode over a sink model's window matches the XLA mask (the old
    NotImplementedError guard existed to prevent exactly a silent
    window-only-masked regression here)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh
    from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
        sharded_paged_decode_attention,
    )

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    q, k_cache, v_cache, table, _ = build_case(ctx=16)
    ctx_lens = jnp.asarray([13, 9], jnp.int32)
    out = sharded_paged_decode_attention(
        mesh, q, k_cache, v_cache, table, ctx_lens, sliding_window=6,
        sinks=4, interpret=True,
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=6, attention_sinks=4,
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("head_dim", [24, 128])
def test_multi_query_single_kv_head(head_dim):
    """kv_heads=1 multi-query — absorbed MLA's attention core: every query
    head is one group over the single shared latent 'head' (wide head_dim
    = rank + rope (+ pad); 128 is the aligned on-chip case)."""
    q, k_cache, v_cache, table, ctx_lens = build_case(
        q_heads=8, kv_heads=1, head_dim=head_dim)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None], ctx_lens
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multi_query_shared_kv_operand():
    """MLA passes the latent pool as BOTH K and V (values are the latent);
    the kernel must tolerate aliased k/v operands."""
    q, k_cache, _v, table, ctx_lens = build_case(
        q_heads=4, kv_heads=1, head_dim=24)
    out = pallas_paged_decode_attention(
        q, k_cache, k_cache, table, ctx_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, k_cache, table, (ctx_lens - 1)[:, None], ctx_lens
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kpb", [1, 3])
@pytest.mark.parametrize("stream", ["reuse", "copy"])
def test_shared_kv_single_stream(kpb, stream):
    """shared_kv=True streams each page once (no V DMA) — bit-identical
    to the double-stream aliased path in both latent feeds: "reuse"
    (V aliased to the K scratch) and "copy" (local VMEM mirror, the
    engine default after the r5 on-chip probe measured reuse 2x slower
    at b8/ctx4k). This is absorbed MLA's decode fast path: half the
    HBM traffic either way."""
    q, k_cache, _v, table, ctx_lens = build_case(
        q_heads=8, kv_heads=1, head_dim=24)
    ref = pallas_paged_decode_attention(
        q, k_cache, k_cache, table, ctx_lens, pages_per_block=kpb,
        interpret=True)
    out = pallas_paged_decode_attention(
        q, k_cache, k_cache, table, ctx_lens, pages_per_block=kpb,
        shared_kv=True, shared_stream=stream, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("kpb", [1, 2, 3])
def test_pages_per_block_variants(kpb):
    """Superblock streaming (kpb pages per online-softmax round) is
    numerics-identical across block sizes, including partial trailing
    superblocks (ctx=13 → 4 pages, kpb=3 → one full + one partial)."""
    q, k_cache, v_cache, table, ctx_lens = build_case(ctx=13)
    ref = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, pages_per_block=1,
        interpret=True)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, pages_per_block=kpb,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kpb", [2, 3])
def test_pages_per_block_with_sinks(kpb):
    """A superblock straddling the sink→window page jump masks each
    sub-page by its own remapped position."""
    q, k_cache, v_cache, table, _ = build_case(ctx=16)
    ctx_lens = jnp.asarray([16, 11], jnp.int32)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=6, sinks=4,
        pages_per_block=kpb, interpret=True)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=6, attention_sinks=4,
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,sinks", [(None, None), (6, None), (6, 4)])
def test_merged_vs_per_head_parity(window, sinks):
    """The merged-heads kernel (default for kv_heads > 1) and the
    per-head escape hatch (merge_heads=False) are numerics-identical —
    including windows and sinks, whose mask is computed once per round
    in the merged kernel instead of per head."""
    q, k_cache, v_cache, table, _ = build_case(q_heads=8, kv_heads=2, ctx=16)
    ctx_lens = jnp.asarray([16, 11], jnp.int32)
    outs = {}
    for mh in (False, True):
        outs[mh] = pallas_paged_decode_attention(
            q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
            sinks=sinks, merge_heads=mh, interpret=True)
    np.testing.assert_allclose(np.asarray(outs[True]),
                               np.asarray(outs[False]),
                               rtol=2e-5, atol=2e-5)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=window, attention_sinks=sinks,
    )[:, 0]
    np.testing.assert_allclose(np.asarray(outs[True]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,sinks", [(None, None), (12, None), (12, 4)])
def test_burst_tail_matches_scattered_reference(window, sinks):
    """The dense burst-local KV tail (fused-decode path: base cache
    frozen, burst tokens in a small carried tail) must equal scattering
    the valid tail tokens into the cache and attending normally — for
    the XLA path and both kernel grids, across window/sink configs."""
    # Table capacity is 16 tokens (4 pages x 4): ctx + T must fit so the
    # scattered reference is faithful.
    T = 6
    q, k_cache, v_cache, table, _ = build_case(q_heads=8, kv_heads=2, ctx=10)
    rng = np.random.default_rng(3)
    B = q.shape[0]
    ctx_lens = jnp.asarray([10, 7], jnp.int32)
    tail_lens = jnp.asarray([5, 1], jnp.int32)
    tail_k = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)
    tail_v = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)

    tpos = ctx_lens[:, None] + jnp.arange(T)[None, :]
    tvalid = jnp.arange(T)[None, :] < tail_lens[:, None]
    k_full = scatter_kv_pages(k_cache, tail_k, table, tpos, tvalid)
    v_full = scatter_kv_pages(v_cache, tail_v, table, tpos, tvalid)
    total = ctx_lens + tail_lens
    ref = paged_attention(q[:, None], k_full, v_full, table,
                          (total - 1)[:, None], total,
                          sliding_window=window, attention_sinks=sinks)[:, 0]

    got_xla = paged_attention(q[:, None], k_cache, v_cache, table,
                              (total - 1)[:, None], ctx_lens,
                              sliding_window=window, attention_sinks=sinks,
                              tail_k=tail_k, tail_v=tail_v,
                              tail_lens=tail_lens)[:, 0]
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for mh in (False, True):
        got = pallas_paged_decode_attention(
            q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
            sinks=sinks, merge_heads=mh, tail_k=tail_k, tail_v=tail_v,
            tail_lens=tail_lens, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_burst_tail_sink_positions():
    """Torture case: a request enters the burst with ctx_base < sinks, so
    some TAIL slots sit at sink positions — they must stay attendable
    once the burst outruns the window (the XLA reference keeps them via
    the concatenated-position mask; the kernels' tail fold must agree)."""
    T = 8
    q, k_cache, v_cache, table, _ = build_case(q_heads=8, kv_heads=2, ctx=2)
    rng = np.random.default_rng(6)
    B = q.shape[0]
    ctx_lens = jnp.asarray([2, 1], jnp.int32)
    tail_lens = jnp.asarray([8, 6], jnp.int32)  # burst outran window=3
    tail_k = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)
    tail_v = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)
    window, sinks = 3, 4

    tpos = ctx_lens[:, None] + jnp.arange(T)[None, :]
    tvalid = jnp.arange(T)[None, :] < tail_lens[:, None]
    k_full = scatter_kv_pages(k_cache, tail_k, table, tpos, tvalid)
    v_full = scatter_kv_pages(v_cache, tail_v, table, tpos, tvalid)
    total = ctx_lens + tail_lens
    ref = paged_attention(q[:, None], k_full, v_full, table,
                          (total - 1)[:, None], total,
                          sliding_window=window, attention_sinks=sinks)[:, 0]
    got_xla = paged_attention(q[:, None], k_cache, v_cache, table,
                              (total - 1)[:, None], ctx_lens,
                              sliding_window=window, attention_sinks=sinks,
                              tail_k=tail_k, tail_v=tail_v,
                              tail_lens=tail_lens)[:, 0]
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for mh in (False, True):
        got = pallas_paged_decode_attention(
            q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
            sinks=sinks, merge_heads=mh, tail_k=tail_k, tail_v=tail_v,
            tail_lens=tail_lens, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_burst_tail_shared_kv():
    """Absorbed-MLA form: the latent tail is both K and V (single-stream),
    with the value read being the same latent the key matched."""
    T = 4
    q, k_cache, _v, table, _ = build_case(q_heads=8, kv_heads=1, ctx=12)
    rng = np.random.default_rng(4)
    B = q.shape[0]
    ctx_lens = jnp.asarray([12, 9], jnp.int32)
    tail_lens = jnp.asarray([3, 1], jnp.int32)
    tail_k = jnp.asarray(rng.normal(size=(B, T, 1, 8)), jnp.float32)

    tpos = ctx_lens[:, None] + jnp.arange(T)[None, :]
    tvalid = jnp.arange(T)[None, :] < tail_lens[:, None]
    k_full = scatter_kv_pages(k_cache, tail_k, table, tpos, tvalid)
    total = ctx_lens + tail_lens
    ref = paged_attention(q[:, None], k_full, k_full, table,
                          (total - 1)[:, None], total)[:, 0]
    for mh in (False, True):
        got = pallas_paged_decode_attention(
            q, k_cache, k_cache, table, ctx_lens, shared_kv=True,
            merge_heads=mh, tail_k=tail_k, tail_lens=tail_lens,
            interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_stacked_cache_layer_idx():
    """layer_idx mode: the kernel DMAs from the full [layers, pages, …]
    stack (slicing outside the pallas_call would materialize a per-layer
    copy at the custom-call boundary) and must equal attention over the
    slice."""
    L = 3
    q, k_cache, v_cache, table, ctx_lens = build_case(ctx=13)
    rng = np.random.default_rng(5)
    kstack = jnp.stack([k_cache] + [
        jnp.asarray(rng.normal(size=k_cache.shape), jnp.float32)
        for _ in range(L - 1)])
    vstack = jnp.stack([v_cache] + [
        jnp.asarray(rng.normal(size=v_cache.shape), jnp.float32)
        for _ in range(L - 1)])
    for li in range(L):
        ref = paged_attention(q[:, None], kstack[li], vstack[li], table,
                              (ctx_lens - 1)[:, None], ctx_lens)[:, 0]
        for mh in (False, True):
            got = pallas_paged_decode_attention(
                q, kstack, vstack, table, ctx_lens, merge_heads=mh,
                layer_idx=li, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)


def test_head_dim_alignment_guard(monkeypatch):
    """On real TPU, sub-128 head dims must raise a clear error instead of
    a Mosaic internal failure (lane tiling is 128; measured on v5e)."""
    import pytest

    from llmd_kv_cache_tpu.ops import pallas_paged_attention as mod

    class _FakeDev:
        platform = "tpu"

    monkeypatch.setattr(mod.jax, "devices", lambda *a, **k: [_FakeDev()])
    with pytest.raises(ValueError, match="head_dim % 128"):
        mod._check_head_dim_alignment(64, interpret=False)
    # interpreter mode and 128-multiples are unrestricted
    mod._check_head_dim_alignment(64, interpret=True)
    mod._check_head_dim_alignment(256, interpret=False)


@pytest.mark.parametrize("rows", [2, 3, 4])
def test_batch_rows_parity(rows):
    """Multi-row programs (batch_rows) must be numerics-identical to the
    single-row merged kernel — including ragged contexts (rows finish
    their rounds at different superblocks and must carry state through)
    and a batch that does not divide the row count (zero-padded rows)."""
    # Built directly (build_case fixes ctx_lens at 2 rows): 4 ragged
    # rows over distinct pages.
    batch, kvh, hd, ps = 4, 2, 8, 4
    rng = np.random.default_rng(7)
    k_cache = jnp.zeros((64, kvh, ps, hd), jnp.float32)
    v_cache = jnp.zeros((64, kvh, ps, hd), jnp.float32)
    table = jnp.asarray(1 + np.arange(batch * 4).reshape(batch, 4),
                        jnp.int32)
    max_ctx = 16
    k_ctx = jnp.asarray(rng.normal(size=(batch, max_ctx, kvh, hd)),
                        jnp.float32)
    v_ctx = jnp.asarray(rng.normal(size=(batch, max_ctx, kvh, hd)),
                        jnp.float32)
    positions = jnp.arange(max_ctx)[None, :].repeat(batch, 0)
    ctx_lens = jnp.asarray([16, 3, 9, 1], jnp.int32)
    valid = positions < ctx_lens[:, None]
    k_cache = scatter_kv_pages(k_cache, k_ctx, table, positions, valid)
    v_cache = scatter_kv_pages(v_cache, v_ctx, table, positions, valid)
    q = jnp.asarray(rng.normal(size=(batch, 8, hd)), jnp.float32)

    base = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True)
    multi = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, batch_rows=rows,
        interpret=True)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,sinks", [(None, None), (12, 4)])
def test_batch_rows_with_tail_and_windows(window, sinks):
    """batch_rows composed with the burst tail, sliding windows, and
    sinks — the full fused-decode feature set in one multi-row program."""
    T = 6
    q, k_cache, v_cache, table, _ = build_case(q_heads=8, kv_heads=2, ctx=10)
    rng = np.random.default_rng(3)
    B = q.shape[0]
    ctx_lens = jnp.asarray([10, 7], jnp.int32)
    tail_lens = jnp.asarray([5, 1], jnp.int32)
    tail_k = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)
    tail_v = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)

    base = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, tail_k=tail_k, tail_v=tail_v, tail_lens=tail_lens,
        interpret=True)
    multi = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, tail_k=tail_k, tail_v=tail_v, tail_lens=tail_lens,
        batch_rows=2, interpret=True)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_batch_rows_shared_kv():
    """batch_rows on the single-stream (absorbed-MLA shared_kv) path."""
    q, k_cache, v_cache, table, ctx_lens = build_case(
        q_heads=8, kv_heads=2, ctx=14)
    base = pallas_paged_decode_attention(
        q, k_cache, k_cache, table, ctx_lens, shared_kv=True,
        interpret=True)
    multi = pallas_paged_decode_attention(
        q, k_cache, k_cache, table, ctx_lens, shared_kv=True,
        batch_rows=2, interpret=True)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_batch_rows_requires_merged():
    q, k_cache, v_cache, table, ctx_lens = build_case()
    with pytest.raises(ValueError, match="merged-heads"):
        pallas_paged_decode_attention(
            q, k_cache, v_cache, table, ctx_lens, merge_heads=False,
            batch_rows=2, interpret=True)


# ---- live granules: a row's copies and folds follow its own keys ----------
#
# At pages of 16 a granule is 8 pages (128 keys) and the default superblock
# 64 pages, so these lengths sit on every edge of the guard: one page, a
# page boundary, a granule boundary, a superblock boundary, and a row of
# three rounds whose last is cut short.
GRANULE_CTX = (1, 15, 16, 127, 128, 129, 1023, 1024, 1025, 2500)
GRANULE_PS = 16


def granule_case(ctx_lens, kv_heads=2, q_heads=4, head_dim=16, seed=11,
                 width=160):
    """Rows of ``ctx_lens`` keys over distinct, shuffled pages of 16."""
    rng = np.random.default_rng(seed)
    batch = len(ctx_lens)
    pages = [-(-c // GRANULE_PS) for c in ctx_lens]
    num_pages = sum(pages) + 1
    order = 1 + rng.permutation(num_pages - 1)
    table = np.zeros((batch, width), np.int32)
    at = 0
    for b, n in enumerate(pages):
        table[b, :n] = order[at:at + n]
        at += n
    shape = (num_pages, kv_heads, GRANULE_PS, head_dim)
    k_cache = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, q_heads, head_dim)), jnp.float32)
    return (q, k_cache, v_cache, jnp.asarray(table),
            jnp.asarray(ctx_lens, jnp.int32))


# name -> (kv_heads, keyword arguments of the kernel)
GRANULE_ARMS = {
    "merged": (2, {}),
    "rows4": (2, dict(batch_rows=4)),
    "per_head": (2, dict(merge_heads=False)),
    "shared_copy": (1, dict(shared_kv=True, shared_stream="copy")),
    "shared_reuse": (1, dict(shared_kv=True, shared_stream="reuse")),
    "shared_copy_merged": (2, dict(shared_kv=True, shared_stream="copy")),
    "tail": (2, {}),
    "tail_rows4": (2, dict(batch_rows=4)),
    "kpb4": (2, dict(pages_per_block=4)),  # the granule is the superblock
    "kpb12": (2, dict(pages_per_block=12)),  # no whole number of granules
    "kpb16": (2, dict(pages_per_block=16)),  # two granules a round
    "kpb16_per_head": (2, dict(pages_per_block=16, merge_heads=False)),
}


@pytest.mark.parametrize("window,sinks",
                         [(None, None), (300, None), (1500, 20)])
@pytest.mark.parametrize("arm", list(GRANULE_ARMS))
def test_live_granules_match_reference(arm, window, sinks):
    """Every edge of the live-granule guard in one batch, against the XLA
    reference: whatever the superblock, the grid, the stream or the
    window, a row attends its own keys and nothing else."""
    kv_heads, kw = GRANULE_ARMS[arm]
    q, k_cache, v_cache, table, ctx_lens = granule_case(
        GRANULE_CTX, kv_heads=kv_heads)
    if kw.get("shared_kv"):
        v_cache = k_cache
    ref_kw, tail = {}, {}
    q_pos = ctx_lens - 1
    if arm.startswith("tail"):
        rng = np.random.default_rng(5)
        T = 6
        shape = (len(GRANULE_CTX), T, kv_heads, q.shape[-1])
        tail = dict(tail_k=jnp.asarray(rng.normal(size=shape), jnp.float32),
                    tail_v=jnp.asarray(rng.normal(size=shape), jnp.float32),
                    tail_lens=jnp.asarray(
                        rng.integers(1, T + 1, len(GRANULE_CTX)), jnp.int32))
        q_pos = ctx_lens + tail["tail_lens"] - 1
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, interpret=True, **kw, **tail)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, q_pos[:, None], ctx_lens,
        sliding_window=window, attention_sinks=sinks, **tail)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arm", ["merged", "per_head", "rows2", "kpb16"])
def test_dead_granules_are_not_folded(arm):
    """A short row runs after long rows whose V pages hold ``inf``: the
    staging scratch past the short row's one live granule still holds
    them, and a fold of it through a mask would give 0 x inf. The short
    rows come out finite and equal to the reference."""
    kw = {"merged": {}, "per_head": dict(merge_heads=False),
          "rows2": dict(batch_rows=2),
          "kpb16": dict(pages_per_block=16)}[arm]
    ctx = (1025, 1030, 20, 129)
    q, k_cache, v_cache, table, ctx_lens = granule_case(ctx, width=72)
    long_pages = np.asarray(table)[:2].ravel()
    v_cache = v_cache.at[long_pages[long_pages > 0]].set(jnp.inf)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True, **kw)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens)[:, 0]
    assert not np.isfinite(np.asarray(out[:2])).any()  # the test's premise
    assert np.isfinite(np.asarray(out[2:])).all()
    np.testing.assert_allclose(np.asarray(out[2:]), np.asarray(ref[2:]),
                               rtol=2e-5, atol=2e-5)
