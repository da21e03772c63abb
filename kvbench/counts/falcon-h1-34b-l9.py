"""What ``falcon-h1-34b-l9`` needs, from shapes alone: in every layer a
Mamba-2 mixer (``cfg.linear_layers``) AND rotary GQA (``cfg.page_layers``:
the same layers) from one normed input, then a dense SwiGLU. What the
algorithm needs and no more, so a share of a peak computed from this can
only be understated. The names and definitions are
``granite-4.0-h-small-ep2-l10``'s, so the accepted ``mamba2_*`` readers find
here what they read there.

``prefill_flops``: per token the matmuls (both mixers' projections and the
MLP); the Mamba-2 mixers' recurrence as the definition runs it, a token at
a time (``mamba2_scan_flops``); per pair of query and key, attention in its
textbook form, in every layer.

``mamba2_scan_flops`` / ``mamba2_scan_bytes``: a head's token decays the
state, writes an outer product into it and reads it with ``C``: 5
operations a state element (a multiply for the decay, a multiply-add for
the write, a multiply-add for the read), counted from the recurrence and
not from how a kernel blocks it. Its bytes are x, B, C and the step in and
the outputs back, 2 B a value as the model's type would hold them; the
states it loads and stores a chunk are not counted.

``mamba2_step_bytes``: a decode step reads and writes every row's state and
conv tail in every layer: ``rows x layers x (heads x head_dim x state x 4 B
+ (taps - 1) x conv channels x 2 B) x 2``.

``decode_attention_bytes``: the keys and values of the rows' tokens in
every layer.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def _mamba(cfg) -> tuple:
    """(layers, inner channels, conv channels, a state's elements)."""
    la = cfg.linear
    return (len(cfg.linear_layers), la.inner, la.conv_channels,
            la.inner * la.key_dim)


def mamba2_scan_flops(cfg, tokens: int) -> float:
    layers, _, _, state = _mamba(cfg)
    return 5.0 * layers * state * tokens


def mamba2_scan_bytes(cfg, tokens: int, itemsize: int = 2) -> float:
    layers, inner, conv, _ = _mamba(cfg)
    return float(layers * tokens * itemsize
                 * (conv + cfg.linear.value_heads + inner))


def mamba2_step_bytes(cfg, rows: int) -> float:
    layers, _, conv, state = _mamba(cfg)
    tail = (cfg.linear.conv_kernel - 1) * conv * 2
    return 2.0 * layers * (4 * state + tail) * rows


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    attn = (h * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd    # wq, wk, wv
            + cfg.num_heads * hd * h)                          # wo
    _, inner, conv, _ = _mamba(cfg)
    la = cfg.linear
    mamba = (h * (inner + conv + la.value_heads)               # w_in
             + la.conv_kernel * conv
             + inner * h)                                      # w_ssm_out
    mlp = 3 * h * cfg.intermediate_size
    return 2.0 * cfg.num_layers * (attn + mamba + mlp)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    attend_pair = 4.0 * cfg.num_heads * cfg.head_dim
    return (n * flops_per_token(cfg) + mamba2_scan_flops(cfg, n)
            + len(cfg.page_layers) * attend_pair * keys_attended(pos, n)
            + head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached tokens' keys and values in the layers that
    keep pages."""
    return float(2 * len(cfg.page_layers) * cfg.num_kv_heads * cfg.head_dim
                 * kv_itemsize * keys)
