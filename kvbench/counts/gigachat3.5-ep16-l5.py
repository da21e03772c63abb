"""What ``gigachat3.5-ep16-l5`` needs, from shapes alone: latent attention
with q-LoRA and an output gate in the layers that attend
(``cfg.page_layers``), Gated DeltaNet in the others (``cfg.linear_layers``),
one dense layer and routed layers of which this chip holds
``cfg.num_experts_held`` experts. What the algorithm needs and no more, so
a share of a peak computed from this can only be understated.

``prefill_flops``: per token the matmuls (each mixer's projections, the
dense or shared feed-forward, the router at its whole width, and the routed
experts a token is sent to HERE, ``k * held / experts`` on average); the
linear layers' recurrence as the definition runs it, a token at a time
(``gdn_scan_flops``); per pair of query and key, attention in its textbook
form in the layers that attend.

``gdn_scan_flops`` / ``gdn_scan_bytes``: a value head's token decays the
state, reads it with its key, writes an outer product and reads it with its
query: 7 ``key_dim x value_dim`` operations (three multiply-adds and the
decay); the served scan folds a block's tokens into matrix products and
does about twice that. Its bytes are q, k, v in and the outputs back, 2 B a
value; the states it loads and stores a chunk are not counted.

``gdn_step_bytes``: a decode step reads and writes every row's state in
every linear layer: ``rows x layers x value heads x key_dim x value_dim x
4 B x 2``.

``decode_attention_bytes``: the latents of the rows' keys in the layers
that attend.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def _linear(cfg) -> tuple:
    la = cfg.linear
    return (len(cfg.linear_layers), la.key_heads * la.key_dim,
            la.value_heads * la.value_dim,
            la.value_heads * la.key_dim * la.value_dim)


def gdn_scan_flops(cfg, tokens: int) -> float:
    layers, _, _, state = _linear(cfg)
    return 7.0 * layers * state * tokens


def gdn_scan_bytes(cfg, tokens: int, itemsize: int = 2) -> float:
    layers, qk, v, _ = _linear(cfg)
    return float(layers * tokens * itemsize * (2 * qk + 2 * v))


def gdn_step_bytes(cfg, rows: int) -> float:
    layers, _, _, state = _linear(cfg)
    return 2.0 * 4 * layers * state * rows


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    r, dr, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_heads
    qr = cfg.q_lora_rank
    attn = (h * qr + qr * heads * (hd + dr)     # w_dq, wq
            + h * (r + dr)                      # w_dkv, w_kr
            + 2 * heads * r * hd                # w_uk, w_uv
            + 2 * heads * hd * h)               # the output gate, wo
    layers, qk, v, _ = _linear(cfg)
    linear = (h * (2 * qk + 2 * v)              # w_qkvz
              + h * 2 * cfg.linear.value_heads  # w_ba
              + cfg.linear.conv_kernel * (2 * qk + v)
              + v * h)                          # wo
    routed = len(cfg.moe_layers)
    inter = cfg.moe_intermediate_size
    sent_here = (cfg.num_experts_per_token * cfg.num_experts_held
                 / max(cfg.num_experts, 1))
    expert_layer = (h * cfg.num_experts
                    + 3 * h * inter * (max(cfg.n_shared_experts, 1)
                                       + sent_here))
    return 2.0 * (len(cfg.page_layers) * attn + layers * linear
                  + (cfg.num_layers - routed) * 3 * h * cfg.intermediate_size
                  + routed * expert_layer)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    attend_pair = 2.0 * cfg.num_heads * (2 * cfg.head_dim
                                         + cfg.qk_rope_head_dim)
    return (n * flops_per_token(cfg) + gdn_scan_flops(cfg, n)
            + len(cfg.page_layers) * attend_pair * keys_attended(pos, n)
            + head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached latents (rank + rope + pad lanes) in the
    layers that keep pages."""
    return float(len(cfg.page_layers) * cfg.kv_cache_head_dim * kv_itemsize
                 * keys)
