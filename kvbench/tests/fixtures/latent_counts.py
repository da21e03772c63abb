"""A test fixture's counts: what a dense decoder with multi-head latent
attention needs, from shapes alone, in the interface a configuration's
counts have (``prefill_flops``, ``decode_attention_bytes``).

The cache is ONE stream: a token costs a layer ``kv_lora_rank +
qk_rope_head_dim + latent_pad`` lanes (``cfg.kv_cache_head_dim``), shared by
all heads, and there is no V. Projections as ``llama.init_params`` makes
them: the query direct from the hidden state, the two down-projections,
``w_uk`` and ``w_uv`` once a token (folded into the query and the output
when served, applied to the latent in the textbook form: the same count),
the output projection and a SwiGLU MLP. Attention is counted in the cheaper,
textbook form (a head's query is nope + rope wide, its value ``head_dim``):
the served, absorbed form does more work a pair of tokens, so a share of
the peak computed from this can only be understated.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    r, dr, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_heads
    attn = (h * heads * (hd + dr)      # wq
            + h * (r + dr)             # w_dkv, w_kr
            + 2 * heads * r * hd       # w_uk, w_uv
            + heads * hd * h)          # wo
    return 2.0 * cfg.num_layers * (attn + 3 * h * cfg.intermediate_size)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    pair = 2.0 * cfg.num_heads * (2 * cfg.head_dim + cfg.qk_rope_head_dim)
    return (n * flops_per_token(cfg)
            + cfg.num_layers * pair * keys_attended(pos, n)
            + head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of latent one decode step must read for rows that attend
    ``keys`` cached keys in all, over all layers."""
    return float(cfg.num_layers * cfg.kv_cache_head_dim * kv_itemsize * keys)
