"""Paged attention over a page-table-addressed KV cache.

XLA-level implementation: gathers each sequence's pages into logical order
and runs masked multi-head attention. Shapes are static; ragged sequence
lengths are handled with masks, so the whole op stays inside one jit and
XLA tiles the matmuls onto the MXU. Works for both prefill (seq > 1,
queries appended after a cached prefix) and decode (seq == 1).

A Pallas flash-decode kernel (``pallas_paged_attention``, double-buffered
page DMA + online softmax) is the TPU fast path for long contexts where
materializing the gathered KV would be HBM-wasteful.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kv_pages import gather_kv_pages

_NEG_INF = -1e30


def paged_attention(
    q: jax.Array,  # [batch, q_seq, q_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    v_cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    q_positions: jax.Array,  # [batch, q_seq] logical position of each query
    total_lens: jax.Array,  # [batch] total tokens (context + new) per sequence
    scale: float | None = None,
    sliding_window: int | None = None,
    attention_sinks: int | None = None,
    layer_idx: int | None = None,
    keep: jax.Array | None = None,  # [batch, q_seq, kv_len] bool
) -> jax.Array:
    """Causal attention of new queries against paged KV (cached + new).

    The KV for the new tokens must already be scattered into the cache.
    ``sliding_window=W`` restricts each query to the last W keys (SWA
    layers of hybrid-attention models); ``attention_sinks=S`` additionally
    keeps the first S positions attendable past the window (StreamingLLM
    sinks — the reference's ``sink_full_attention`` spec kind,
    ``events.go:40``). Returns ``[batch, q_seq, q_heads, head_dim]`` in
    the query dtype.

    ``layer_idx`` makes ``k_cache``/``v_cache`` the ``[layers, num_pages,
    ...]`` stacks: the page gather takes the layer as one more index, so
    no layer of a pool is ever sliced out.

    ``keep`` (learned sparse attention) further restricts each query to
    the keys it selected, by position in the row's pages.
    """
    batch, q_seq, q_heads, head_dim = q.shape
    kv_heads = k_cache.shape[-3]
    if scale is None:
        scale = head_dim ** -0.5
    group = q_heads // kv_heads

    k = gather_kv_pages(k_cache, page_table, layer_idx)  # [b, kv_len, kvh, hd]
    v = gather_kv_pages(v_cache, page_table, layer_idx)
    if k.dtype.itemsize == 1:
        # Quantized (fp8 e4m3) cache: the HBM read above moved 1-byte
        # elements — the bandwidth/capacity win — and the upcast to the
        # query dtype happens on the gathered values so the matmuls run
        # the same bf16 MXU path as an unquantized cache. (bf16 caches
        # deliberately skip this: see the numerics note below.)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    kv_len = k.shape[1]

    k_pos = jnp.broadcast_to(jnp.arange(kv_len)[None], (batch, kv_len))
    k_valid = k_pos < total_lens[:, None]

    # MXU-friendly numerics: feed the matmuls bf16 operands with fp32
    # accumulation (bf16·bf16 products are exact in fp32) instead of
    # upcasting K/V first — upcasting halves MXU throughput and doubles
    # the HBM traffic of the gathered KV. Softmax stays fp32. GQA is a
    # grouped einsum over [b, q, kvh, group, hd] so KV heads are never
    # materialized ``group``× (the repeat would burn HBM bandwidth).
    qg = q.reshape(batch, q_seq, kv_heads, group, head_dim)
    # [b, kvh, group, q_seq, kv_len], fp32
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale

    k_pos = k_pos[:, None, None, None, :]
    q_pos = q_positions[:, None, None, :, None]
    mask = (k_pos <= q_pos) & k_valid[:, None, None, None, :]
    if sliding_window is not None:
        in_window = q_pos - k_pos < sliding_window
        if attention_sinks:
            in_window = in_window | (k_pos < attention_sinks)
        mask = mask & in_window
    if keep is not None:
        mask = mask & keep[:, None, None, :, :]
    logits = jnp.where(mask, logits, _NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(batch, q_seq, q_heads, head_dim).astype(q.dtype)
