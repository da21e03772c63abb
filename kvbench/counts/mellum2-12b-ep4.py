"""What ``mellum2-12b-ep4`` needs, from shapes alone: GQA in every layer,
over a window of ``cfg.sliding_window`` keys in the window layers
(``cfg.swa_layers``) and over every key in the others; every feed-forward
routed, of which this chip holds ``cfg.num_experts_held`` experts and no
shared one. What the algorithm needs and no more, so a share of a peak
computed from this can only be understated.

``prefill_flops``: per token the matmuls (attention's projections, the
router at its whole width, and the routed experts a token is sent to HERE,
``k * held / experts`` on average); per pair of query and key, attention in
its textbook form: the window layers at ``keys_attended(pos, n, window)``,
the full layers at ``keys_attended(pos, n)``.

``pools_decode_attention_bytes(cfg, full_keys, window_keys)``: the keys and
values a decode step's kernels must read from the two pools, from the
engine's own two sums over the step's live rows (``full_keys``: each row's
context; ``window_keys``: each row's context capped at the window):
``(full layers x full_keys + window layers x window_keys) x kv heads x
head_dim x 2 (K and V) x 2 B``.

``decode_attention_bytes(cfg, keys)``: what the harness's list-less
``attn_decode_roofline`` divides by. **A floor in this cell, never the
kernels' need**: the harness caps every row's ``decode_ctx`` at
``cfg.sliding_window`` before it sums them (``harness/loop.py``), so
``keys`` is ``window_keys`` and nothing here can know what the full layers
read; the capped keys are counted in all layers, which understates the
full layers' bytes by ``context / window`` and keeps that share under
``attn_pools_decode_roofline``'s, which reads the engine's two sums.

``moe_flops`` / ``moe_weight_bytes``: a routed layer's grouped matmuls from
the device's own counters, as ``granite-4.0-h-small-ep2-l10``'s counts.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def _layers(cfg) -> tuple:
    """(window layers, full layers)."""
    window = len(cfg.group_layers(1)) if cfg.is_hybrid else 0
    return window, cfg.num_layers - window


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    attn = (h * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd    # wq, wk, wv
            + cfg.num_heads * hd * h)                          # wo
    sent_here = (cfg.num_experts_per_token * cfg.num_experts_held
                 / max(cfg.num_experts, 1))
    routed = (h * cfg.num_experts
              + 3 * h * cfg.moe_intermediate_size * sent_here)
    return 2.0 * cfg.num_layers * (attn + routed)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    window, full = _layers(cfg)
    attend_pair = 4.0 * cfg.num_heads * cfg.head_dim
    return (n * flops_per_token(cfg)
            + attend_pair * (
                window * keys_attended(pos, n, cfg.sliding_window)
                + full * keys_attended(pos, n))
            + head_flops(cfg))


def _key_bytes(cfg, kv_itemsize: int) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg.num_kv_heads * cfg.head_dim * kv_itemsize


def pools_decode_attention_bytes(cfg, full_keys: int, window_keys: int,
                                 kv_itemsize: int = 2) -> float:
    window, full = _layers(cfg)
    return float(_key_bytes(cfg, kv_itemsize)
                 * (full * full_keys + window * window_keys))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """``keys`` window-capped by the harness: a floor (the module's text)."""
    return float(cfg.num_layers * _key_bytes(cfg, kv_itemsize) * keys)


def moe_flops(cfg, assignments_held: int) -> float:
    """Gate, up and down of every assignment that fell to an expert held."""
    return 6.0 * cfg.hidden_size * cfg.moe_intermediate_size * assignments_held


def moe_weight_bytes(cfg, experts_touched: int, itemsize: int = 2) -> float:
    """The three matrices of every expert a step touched, read once."""
    return (3.0 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize
            * experts_touched)
