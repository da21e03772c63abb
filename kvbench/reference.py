"""The plain reference: one dense decoder forward in float32.

RMSNorm, rotary embedding (rotate-half), grouped-query attention with an
optional per-head RMSNorm on queries and keys (Qwen3) and an optional
uniform sliding window (Mistral), SwiGLU, as the models' papers and their
``transformers`` implementations give them. No kernels, no cache, no
batching; ``jax.default_matmul_precision("highest")`` so a TPU multiplies in
float32. The weights are the engine's own bf16 tree, upcast layer by layer
so the reference fits beside the served model; that tree is the only thing
taken from the program, in either of its two layouts (``wq/wk/wv`` and
``w_gate/w_up``, or fused ``w_qkv`` and ``w_gate_up`` in that column order).

Tolerance. The served model keeps activations in bf16 (8 bits of mantissa,
rounding error 2^-9 per operation) through ~10 operations a layer, and the
errors add like a random walk over the layers. Measured on one v5e against
this reference, as the largest logit difference over the reference's largest
logit (PR 24): 2.2-2.4% at Qwen3-1.7B's 28 layers of hidden 2048, 3.1% at
Mistral-7B's 16 layers of hidden 4096 (wider sums), 0.5-0.6% at the toy
widths of the CPU tests. ``TOLERANCE`` allows 5%. An 8-bit float anywhere on
the path rounds 32 times more coarsely (2^-4 per operation) and would miss
it severalfold; logits of a wrong page or a wrong position differ by the
logits' own size (``tests/test_model.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.05


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, cos, sin):
    """x: [s, heads, hd]; cos, sin: [s, 1, hd/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "window", "inter"))
def _layer(x, layer, cos, sin, *, heads, kv_heads, head_dim, eps, window,
           inter):
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    s = x.shape[0]
    h = _rms_norm(x, f32(layer["attn_norm"]), eps)
    nq, nk = heads * head_dim, kv_heads * head_dim
    if "w_qkv" in layer:
        qkv = h @ f32(layer["w_qkv"])
        q, k, v = qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:]
    else:
        q, k, v = (h @ f32(layer[n]) for n in ("wq", "wk", "wv"))
    q = q.reshape(s, heads, head_dim)
    k = k.reshape(s, kv_heads, head_dim)
    v = v.reshape(s, kv_heads, head_dim)
    if "q_norm" in layer:
        q = _rms_norm(q, f32(layer["q_norm"]), eps)
        k = _rms_norm(k, f32(layer["k_norm"]), eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    group = heads // kv_heads
    q = q.reshape(s, kv_heads, group, head_dim)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / np.sqrt(head_dim)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = ki <= qi
    if window is not None:
        mask = mask & (qi - ki < window)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(s, nq)
    x = x + attn @ f32(layer["wo"])
    h = _rms_norm(x, f32(layer["mlp_norm"]), eps)
    if "w_gate_up" in layer:
        gu = h @ f32(layer["w_gate_up"])
        gate, up = gu[:, :inter], gu[:, inter:]
    else:
        gate, up = h @ f32(layer["w_gate"]), h @ f32(layer["w_up"])
    return x + (jax.nn.silu(gate) * up) @ f32(layer["w_down"])


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(
        jnp.float32)


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions."""
    if cfg.num_experts or cfg.is_mla or cfg.is_hybrid or cfg.rope_scaling:
        raise NotImplementedError(
            "the plain reference covers dense GQA models with plain RoPE "
            "and at most a uniform window; a configuration beyond that "
            "brings its own reference")
    window = cfg.sliding_window if cfg.swa_layers else None
    tokens = jnp.asarray(tokens, jnp.int32)
    half = cfg.head_dim // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(half, dtype=np.float64) / half))
    angles = np.arange(tokens.shape[0], dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles)[:, None, :], jnp.float32)
    sin = jnp.asarray(np.sin(angles)[:, None, :], jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(x, layer, cos, sin, heads=cfg.num_heads,
                       kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                       eps=cfg.norm_eps, window=window,
                       inter=cfg.intermediate_size)
        out = _head(x[jnp.asarray(positions)], params["final_norm"],
                    params["lm_head"], cfg.norm_eps)
    return np.asarray(out, np.float32)
