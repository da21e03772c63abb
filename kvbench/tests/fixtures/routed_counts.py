"""A test fixture's counts: what a decoder with latent attention and routed
experts needs, from shapes alone, in the interface a configuration's counts
have (``prefill_flops``, ``decode_attention_bytes``).

Attention and the cache as ``latent_counts.py`` has them (one stream of
``cfg.kv_cache_head_dim`` lanes a token and layer, no V). A dense layer is a
SwiGLU MLP of ``intermediate_size``; a routed layer is the router, the
shared expert and the ``num_experts_per_token`` experts a token is sent to,
each of ``moe_intermediate_size``: what the model needs, not what
``moe_dispatch="dense"`` spends (every expert over every token), so a share
of the peak computed from this can only be understated. How many experts a
step touches, and with that the bytes of weights it must read, depends on
the tokens: such a count has to come from the program as a counter, through
``metrics/_read.py: phase_events``.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    r, dr, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_heads
    attn = (h * heads * (hd + dr)      # wq
            + h * (r + dr)             # w_dkv, w_kr
            + 2 * heads * r * hd       # w_uk, w_uv
            + heads * hd * h)          # wo
    routed = len(cfg.moe_layers)
    inter = cfg.moe_intermediate_size
    expert_layer = (h * cfg.num_experts                     # router
                    + 3 * h * inter * (cfg.num_experts_per_token
                                       + max(cfg.n_shared_experts, 1)))
    return 2.0 * (cfg.num_layers * attn
                  + (cfg.num_layers - routed) * 3 * h * cfg.intermediate_size
                  + routed * expert_layer)


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
