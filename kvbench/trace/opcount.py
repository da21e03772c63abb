"""Operations and bytes a dense GQA decoder needs, from shapes alone, and
the table of peaks.

What the algorithm requires, not what a kernel happens to do: padded rows
and padded tokens do no useful work and are not counted, so a share of a
peak computed from these can only be lowered by padding, never raised.

These are the counts of a configuration that names none
(``harness/names.py: counts``): ``prefill_flops`` and
``decode_attention_bytes`` are what the roofline readers call, and they
refuse a model they would count wrong.
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {Path(__file__).with_name('peaks.json')}"
                       f" (it has: {sorted(table)})")
    return table[device_kind]


def rehearsal_peaks() -> dict:
    """For the CPU walk-through only, so that the readers' arithmetic
    runs: the first chip of the table. Nothing a rehearsal prints is a
    measurement, and its line says ``platform: "cpu"``."""
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    return next(iter(table.values()))


def _dense_gqa_only(cfg) -> None:
    if cfg.num_experts or cfg.is_mla or cfg.is_hybrid or cfg.rope_scaling:
        raise NotImplementedError(
            "the plain counts cover dense GQA models with plain RoPE "
            "and at most a uniform window; a configuration beyond that "
            "brings its own counts")


def dense_flops_per_token(cfg) -> float:
    """Multiply-adds x 2 of one token through every layer's projections
    and MLP, plus nothing for the head (counted per sequence, below)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    qkv = h * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd
    out = cfg.num_heads * hd * h
    mlp = 3 * h * cfg.intermediate_size
    return 2.0 * cfg.num_layers * (qkv + out + mlp)


def head_flops(cfg) -> float:
    """The output head on one position (prefill computes the last only)."""
    return 2.0 * cfg.hidden_size * cfg.vocab_size


def keys_attended(pos: int, n: int, window=None) -> int:
    """Sum over ``n`` new tokens after ``pos`` cached ones of the keys each
    attends (causal, window-capped)."""
    if n <= 0:
        return 0
    first, last = pos + 1, pos + n
    if window is None or last <= window:
        return (first + last) * n // 2
    if first >= window:
        return window * n
    ramp = window - first  # tokens still under the window
    return (first + window - 1) * ramp // 2 + window * (n - ramp)


def attention_flops(cfg, pos: int, n: int) -> float:
    """QK^T and PV for ``n`` new tokens after ``pos`` cached ones."""
    window = cfg.sliding_window if cfg.swa_layers else None
    return (4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim
            * keys_attended(pos, n, window))


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    _dense_gqa_only(cfg)
    if n <= 0:
        return 0.0
    return (n * dense_flops_per_token(cfg) + attention_flops(cfg, pos, n)
            + head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of K and V one decode step must read for rows that attend
    ``keys`` cached keys in all (already window-capped), over all layers."""
    _dense_gqa_only(cfg)
    return (2.0 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
            * kv_itemsize * keys)
