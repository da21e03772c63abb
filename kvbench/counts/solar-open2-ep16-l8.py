"""What ``solar-open2-ep16-l8`` needs, from shapes alone: GQA without
positional encoding and an output gate in the layers that attend
(``cfg.page_layers``), Kimi-style delta attention (a decay for every key
channel) in the others (``cfg.linear_layers``), every feed-forward routed,
of which this chip holds ``cfg.num_experts_held`` experts. What the
algorithm needs and no more, so a share of a peak computed from this can
only be understated.

``prefill_flops``: per token the matmuls (each mixer's projections, the
low-rank ones too, the shared expert, the router at its whole width, and
the routed experts a token is sent to HERE, ``k * held / experts`` on
average); the linear layers' recurrence as the definition runs it, a token
at a time (``kda_scan_flops``); per pair of query and key, attention in
its textbook form in the layers that attend.

``kda_scan_flops`` / ``kda_scan_bytes``: a head's token decays the state,
reads it with its key, writes an outer product and reads it with its
query: 7 ``key_dim x value_dim`` operations (three multiply-adds and the
decay), counted from the recurrence and not from how a kernel blocks it.
Its bytes are q, k, v and the per-channel log-decay in and the outputs
back, 2 B a value as the model's type would hold them (the served scan
takes q, k and the decay in float32: its own choice); the states it loads
and stores a chunk are not counted.

``kda_step_bytes``: a decode step reads and writes every row's state in
every linear layer: ``rows x layers x heads x key_dim x value_dim x 4 B x
2``.

``decode_attention_bytes``: the keys and values of the rows' tokens in the
layers that attend.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def _linear(cfg) -> tuple:
    la = cfg.linear
    return (len(cfg.linear_layers), la.key_heads * la.key_dim,
            la.value_heads * la.value_dim,
            la.value_heads * la.key_dim * la.value_dim)


def kda_scan_flops(cfg, tokens: int) -> float:
    layers, _, _, state = _linear(cfg)
    return 7.0 * layers * state * tokens


def kda_scan_bytes(cfg, tokens: int, itemsize: int = 2) -> float:
    layers, qk, v, _ = _linear(cfg)
    return float(layers * tokens * itemsize * (3 * qk + 2 * v))


def kda_step_bytes(cfg, rows: int) -> float:
    layers, _, _, state = _linear(cfg)
    return 2.0 * 4 * layers * state * rows


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    attn = (h * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd    # wq, wk, wv
            + 2 * cfg.num_heads * hd * h)                  # the gate, wo
    layers, qk, v, _ = _linear(cfg)
    la = cfg.linear
    linear = (h * (2 * qk + v)                             # w_conv_in
              + la.gate_rank * (2 * h + qk + v)            # W_f, W_g
              + h * la.value_heads                         # w_beta
              + la.conv_kernel * (2 * qk + v)
              + v * h)                                     # wo
    inter = cfg.moe_intermediate_size
    sent_here = (cfg.num_experts_per_token * cfg.num_experts_held
                 / max(cfg.num_experts, 1))
    expert_layer = (h * cfg.num_experts
                    + 3 * h * inter * (max(cfg.n_shared_experts, 1)
                                       + sent_here))
    return 2.0 * (len(cfg.page_layers) * attn + layers * linear
                  + len(cfg.moe_layers) * expert_layer)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    attend_pair = 4.0 * cfg.num_heads * cfg.head_dim
    return (n * flops_per_token(cfg) + kda_scan_flops(cfg, n)
            + len(cfg.page_layers) * attend_pair * keys_attended(pos, n)
            + head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached tokens' keys and values in the layers that
    keep pages."""
    return float(2 * len(cfg.page_layers) * cfg.num_kv_heads * cfg.head_dim
                 * kv_itemsize * keys)
