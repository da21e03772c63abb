"""Ragged single-kernel paged attention vs the padded per-row reference.

One Pallas program serves a mixed prefill+decode batch described by ragged
metadata (per-row ``q_start/q_len/ctx_len`` prefix-summed into a flat token
axis). Every case here runs in interpreter mode on the CPU backend and
checks the ragged kernel row-by-row against the XLA ``paged_attention``
reference, across the fallback-matrix axes: sliding window, attention
sinks, fp8 (e4m3) pages, MLA shared-latent streaming, and flat-axis
padding. The final test drives the engine end-to-end:
``ragged_attention=True`` must emit token streams identical to the padded
two-kernel fallback on a mixed continuous-batching workload.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops.kv_pages import scatter_kv_pages
from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
    pallas_paged_ragged_attention,
)


def run_case(q_lens, ctx_lens, q_heads=4, kv_heads=2, head_dim=8,
             page_size=4, num_pages=64, q_tile=8, sliding_window=None,
             sinks=None, dtype=jnp.float32, cache_dtype=None,
             shared_kv=False, shared_stream="copy", seed=0):
    """Build a ragged batch, run the kernel, assert per-row vs reference.

    Scatter-then-attend semantics: all ``ctx + q_len`` keys are already
    in the pages and queries sit at ``ctx .. ctx+q_len-1``.
    """
    rows = len(q_lens)
    pages_per_seq = 8
    rng = np.random.RandomState(seed)
    table = 1 + np.arange(rows * pages_per_seq).reshape(rows, pages_per_seq)
    table = jnp.asarray(table, jnp.int32)
    cache_dtype = cache_dtype or dtype

    total_lens = [c + q for c, q in zip(ctx_lens, q_lens)]
    max_total = max(total_lens)

    k_cache = jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype)
    v_cache = jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype)
    full_k = jnp.asarray(rng.randn(rows, max_total, kv_heads, head_dim),
                         dtype)
    full_v = (full_k if shared_kv else jnp.asarray(
        rng.randn(rows, max_total, kv_heads, head_dim), dtype))
    positions = jnp.broadcast_to(jnp.arange(max_total), (rows, max_total))
    valid = positions < jnp.asarray(total_lens)[:, None]
    k_cache = scatter_kv_pages(k_cache, full_k, table, positions, valid)
    v_cache = (k_cache if shared_kv else scatter_kv_pages(
        v_cache, full_v, table, positions, valid))
    k_cache = k_cache.astype(cache_dtype)
    v_cache = k_cache if shared_kv else v_cache.astype(cache_dtype)

    total_q = sum(q_lens)
    pad = (-total_q) % q_tile
    q_flat = jnp.asarray(rng.randn(total_q + pad, q_heads, head_dim), dtype)
    row_starts = jnp.asarray(
        np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)

    out = pallas_paged_ragged_attention(
        q_flat, k_cache, v_cache, table, row_starts,
        jnp.asarray(ctx_lens, jnp.int32),
        q_tile=q_tile, sliding_window=sliding_window, sinks=sinks,
        shared_kv=shared_kv, shared_stream=shared_stream,
        interpret=True)

    for r in range(rows):
        qs, qe = int(row_starts[r]), int(row_starts[r + 1])
        q_r = q_flat[qs:qe][None]  # [1, q_len, qh, hd]
        q_pos = jnp.arange(ctx_lens[r], total_lens[r])[None]
        ref = paged_attention(
            q_r, k_cache, v_cache, table[r:r + 1], q_pos,
            jnp.asarray([total_lens[r]], jnp.int32),
            sliding_window=sliding_window,
            attention_sinks=sinks or 0)[0]
        tol = 2e-5 if (dtype == jnp.float32
                       and cache_dtype == jnp.float32) else 5e-2
        np.testing.assert_allclose(
            np.asarray(out[qs:qe], np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
            err_msg=f"row {r} q_lens={q_lens} ctx={ctx_lens} "
                    f"w={sliding_window} s={sinks}")


def test_mixed_batch_straddles_q_tiles():
    """Decode rows + prefill chunks crossing q-tile boundaries."""
    run_case([1, 5, 1, 9], [13, 0, 27, 4])


def test_pure_decode_rows():
    run_case([1, 1, 1], [9, 17, 3])


def test_pure_prefill_row():
    run_case([16], [0], q_tile=8)


def test_prefill_continuation_chunk():
    """A chunked-prefill row resuming mid-prompt (ctx > 0, q_len > 1)."""
    run_case([6, 1], [10, 21])


@pytest.mark.parametrize("sinks", [None, 2])
def test_sliding_window(sinks):
    run_case([1, 6, 1], [21, 7, 15], sliding_window=8, sinks=sinks)


def test_flat_axis_padding():
    """total_q not a q_tile multiple: the pad tail stays inert."""
    run_case([1, 2], [5, 9])


def test_gqa_group_8():
    run_case([1, 5, 1], [13, 0, 27], q_heads=8, kv_heads=2)


def test_bf16_cache():
    run_case([1, 5, 1], [13, 0, 27], dtype=jnp.bfloat16)


def test_fp8_cache():
    """e4m3 pages ride the quant arm: flat 1-byte DMAs, upcast on read."""
    run_case([1, 5, 1, 9], [13, 0, 27, 4], cache_dtype=jnp.float8_e4m3fn)


@pytest.mark.parametrize("stream", ["copy", "reuse"])
def test_mla_shared_latent(stream):
    """MLA absorbed form: one shared latent 'head' (kv_heads=1, wide
    head_dim) feeds both matmuls via the shared-KV stream."""
    run_case([1, 5, 1], [13, 0, 27], q_heads=4, kv_heads=1, head_dim=32,
             shared_kv=True, shared_stream=stream)


def test_rejects_bad_metadata():
    q = jnp.zeros((8, 4, 8), jnp.float32)
    kc = jnp.zeros((8, 2, 4, 8), jnp.float32)
    table = jnp.zeros((1, 4), jnp.int32)
    starts = jnp.asarray([0, 8], jnp.int32)
    ctx = jnp.zeros((1,), jnp.int32)
    with pytest.raises(AssertionError):
        pallas_paged_ragged_attention(
            q, kc, kc, table, starts, ctx, q_tile=3, interpret=True)
    with pytest.raises(ValueError):
        pallas_paged_ragged_attention(
            q, kc, kc, table, starts, ctx, shared_kv=True,
            shared_stream="bogus", interpret=True)


def _serve(engine, prompts, max_new):
    reqs = {rid: engine.enqueue(rid, p, max_new_tokens=max_new)
            for rid, p in prompts.items()}
    steps = 0
    while not all(r.done for r in reqs.values()):
        engine.step()
        steps += 1
        assert steps < 500
    return {rid: list(r.output) for rid, r in reqs.items()}


@pytest.mark.slow
def test_engine_mixed_batch_matches_padded_path():
    """Continuous batching end to end: the ragged scheduler must emit
    exactly the padded two-kernel fallback's greedy streams (fp32 model —
    bf16 hits top-2 logit ties that flip on program-level rounding).

    ~50 s of jit compiles (both dispatch programs at fp32), so it is
    marked slow and tier-1 does not run it."""
    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
    from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = {f"r{i}": rng.integers(1, 250, int(n)).tolist()
               for i, n in enumerate([11, 5, 17, 3])}

    streams = {}
    for ragged in (False, True):
        eng = MiniEngine(
            EngineConfig(model=cfg, num_pages=128, max_pages_per_seq=16,
                         max_batch=2,  # < n_requests: multi-chunk decode
                         model_name="t", pod_identifier="p",
                         ragged_attention=ragged),
            params=params, seed=0)
        if ragged:
            assert eng._ragged, "ragged path did not engage on CPU"
        streams[ragged] = _serve(eng, prompts, max_new=4)
    assert streams[True] == streams[False]
