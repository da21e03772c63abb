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


@pytest.mark.parametrize("window,sinks", [(None, None), (6, 4)])
def test_batch_rows_with_windows(window, sinks):
    """batch_rows composed with sliding windows and sinks in one
    multi-row program (rows of 16 and 11 keys: the longer skips a page
    between its sinks and its window)."""
    q, k_cache, v_cache, table, ctx_lens = build_case(
        q_heads=8, kv_heads=2, ctx=16)

    base = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, interpret=True)
    multi = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, batch_rows=2, interpret=True)
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
