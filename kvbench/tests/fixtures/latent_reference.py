"""A test fixture's plain reference: multi-head latent attention as the
DeepSeek-V2 paper writes it (section 2.1), not absorbed, in float32.

Per layer: the hidden state is projected down to one latent ``c_kv`` of
``kv_lora_rank`` and to one rotary key of ``qk_rope_head_dim`` shared by all
heads; every head's keys and values are materialised from the latent
(``w_uk``, ``w_uv``), the rotary key is appended to each head's key, and
attention is plain causal multi-head attention scaled by the width of a
head's query (nope + rope). The query is projected directly, or, where the
tree holds ``w_dq``, down to a latent, RMS-normed and up (q-LoRA); a
``latent_norm`` (``kv_a_layernorm``) is applied where the tree holds one.
Then SwiGLU. No kernels, no cache, no batching;
``jax.default_matmul_precision("highest")``. The served model keeps only
the latent and folds ``w_uk`` and ``w_uv`` into the query and the output,
so this checks the absorption and the paged latent pool, not a copy of
them. The weights are the engine's own tree, upcast layer by layer; nothing
else is taken from the program. Copied from ``tests/test_mla.py:
naive_mla_logits`` (which stays) into the interface a configuration's
reference has: ``logits_at`` and ``TOLERANCE``.

Tolerance: as ``kvbench/reference.py`` reasons for bf16 activations; at
these toy widths the probe reads well under 1%.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.05


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * jnp.asarray(w, jnp.float32)


def _rope(x, cos, sin):
    """x: [s, heads, d]; cos, sin: [s, 1, d/2] (rotate-half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, layer, cos, sin, cfg):
    def f32(name):
        return jnp.asarray(layer[name], jnp.float32)

    s = x.shape[0]
    heads, hd, dr = cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q_in = h
    if "w_dq" in layer:
        q_in = _rms_norm(h @ f32("w_dq"), layer["q_latent_norm"],
                         cfg.norm_eps)
    q = (q_in @ f32("wq")).reshape(s, heads, hd + dr)
    q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], cos, sin)], -1)
    c_kv = h @ f32("w_dkv")                                   # [s, r]
    if "latent_norm" in layer:
        c_kv = _rms_norm(c_kv, layer["latent_norm"], cfg.norm_eps)
    k_rope = _rope((h @ f32("w_kr"))[:, None, :], cos, sin)   # [s, 1, dr]
    k_nope = jnp.einsum("sr,hrd->shd", c_kv, f32("w_uk"))
    v = jnp.einsum("sr,hrv->shv", c_kv, f32("w_uv"))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (s, heads, dr))], -1)
    scores = (jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd + dr)
              * cfg.softmax_scale_mult)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khv->qhv", probs, v).reshape(s, heads * hd)
    x = x + attn @ f32("wo")
    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ f32("w_gate")) * (h @ f32("w_up"))
                ) @ f32("w_down")


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions."""
    if not cfg.is_mla or cfg.num_experts or cfg.rope_scaling:
        raise NotImplementedError(
            "this fixture covers dense latent-attention models with plain "
            "RoPE")
    if "w_mla_in" in params["layers"][0]:
        raise NotImplementedError("this fixture reads the unfused tree")
    tokens = jnp.asarray(tokens, jnp.int32)
    half = cfg.qk_rope_head_dim // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(half, dtype=np.float64) / half))
    angles = np.arange(tokens.shape[0], dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles)[:, None, :], jnp.float32)
    sin = jnp.asarray(np.sin(angles)[:, None, :], jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(x, layer, cos, sin, cfg)
        x = _rms_norm(x[jnp.asarray(positions)], params["final_norm"],
                      cfg.norm_eps)
        out = x @ params["lm_head"].astype(jnp.float32)
    return np.asarray(out, np.float32)
