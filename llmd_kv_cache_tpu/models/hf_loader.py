"""HuggingFace checkpoint loading: serve real Llama-family weights.

Maps a ``transformers`` Llama / Mistral / Mixtral / Qwen2 / Qwen3 /
Qwen3-MoE / DeepSeek-architecture state dict (or a checkpoint
directory) onto this repo's parameter pytree, so the paged
serving engine runs real checkpoints instead of random init. The mapping
is validated end-to-end by logits parity against the authoritative HF
implementation (``tests/test_hf_loader.py`` builds a random-init HF model
and requires our forward to reproduce its logits) — the model family is
pinned to the upstream reference implementation, not just internal
oracles.

Conventions handled:
- ``nn.Linear`` stores ``[out_features, in_features]``; this repo's
  matmuls are activation-major (``x @ W`` with ``W [in, out]``) → every
  projection transposes.
- HF rotary is the half-split ``rotate_half`` form — identical to
  ``llama._rope`` (verified by the parity test), so Q/K need no
  permutation.
- ``tie_word_embeddings`` reuses the embedding matrix as ``lm_head``.

Reference analog: the reference serves through external engines and ships
no loader; this is part of the in-tree serving engine
(PARITY.md "Additions beyond the reference").
"""

from __future__ import annotations

import dataclasses
import math

from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from .llama import LinearAttention, LlamaConfig, Params


def _yarn_get_mscale(scale: float, m: float = 1.0) -> float:
    """transformers' yarn_get_mscale: 0.1·m·ln(scale)+1 (1.0 for ≤1)."""
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _convert_rope_scaling(hf_cfg: Any) -> tuple:
    """Map HF ``rope_scaling`` to ``LlamaConfig.rope_scaling``.

    ``llama3`` (the Llama-3.1+ frequency-band NTK scheme) is implemented
    by ``llama._rope``; every other kind (yarn, linear, dynamic — both
    the modern ``rope_type`` and legacy ``type`` key spellings) refuses:
    converting would silently change every position's frequencies vs the
    checkpoint's training."""
    return _rope_rule(getattr(hf_cfg, "rope_scaling", None),
                      getattr(hf_cfg, "max_position_embeddings", None))


def _rope_rule(rope_scaling: Any, max_positions: Any = None) -> tuple:
    """One HF rope dict (``rope_scaling``, or one kind's entry of
    ``rope_parameters``) as ``LlamaConfig.rope_scaling``."""
    if not rope_scaling:
        return ()
    kind = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
    if kind == "default":
        return ()
    if kind == "llama3":
        return ("llama3", float(rope_scaling["factor"]),
                float(rope_scaling["low_freq_factor"]),
                float(rope_scaling["high_freq_factor"]),
                float(rope_scaling["original_max_position_embeddings"]))
    if kind == "yarn":
        if not rope_scaling.get("truncate", True):
            raise NotImplementedError(
                "yarn with truncate=false (untruncated correction bounds) "
                "is not implemented")
        factor = float(rope_scaling["factor"])
        att = rope_scaling.get("attention_factor")
        mscale = rope_scaling.get("mscale")
        mscale_all = rope_scaling.get("mscale_all_dim")
        if att is None:
            if mscale and mscale_all:
                att = _yarn_get_mscale(factor, mscale) / _yarn_get_mscale(
                    factor, mscale_all)
            else:
                att = _yarn_get_mscale(factor)
        orig = (rope_scaling.get("original_max_position_embeddings")
                or max_positions)
        return ("yarn", factor,
                float(rope_scaling.get("beta_fast") or 32),
                float(rope_scaling.get("beta_slow") or 1),
                float(orig), float(att))
    raise NotImplementedError(
        f"rope_scaling={rope_scaling!r} is not implemented")


def config_from_hf(hf_cfg: Any, page_size: int = 16,
                   dtype: Any = jnp.bfloat16) -> LlamaConfig:
    """Translate a ``transformers`` Llama/Mistral/Qwen config.

    The per-layer attention layout follows ``hf_cfg.layer_types`` when
    present (the authoritative map modern transformers derives from
    ``max_window_layers``: first-N full, rest SWA); otherwise a set
    ``sliding_window`` (Mistral) means uniform SWA. Unsupported features
    raise instead of silently converting to wrong logits.
    """
    n_layers = hf_cfg.num_hidden_layers

    # Architecture allowlist: families whose forward this repo implements
    # exactly. Anything else (Gemma's GELU + softcapping + scaled embeds,
    # Phi's partial rotary, …) must refuse rather than convert to
    # silently-wrong logits.
    supported = ("llama", "mistral", "mixtral", "qwen2", "qwen3",
                 "qwen3_moe", "deepseek_v2", "deepseek_v3", "deepseek_v32",
                 "gigachat3_5", "solar_open2", "pangu_ultra_moe",
                 "granitemoehybrid", "falcon_h1", "mellum")
    if hf_cfg.model_type not in supported:
        raise NotImplementedError(
            f"model_type {hf_cfg.model_type!r} is not supported "
            f"(supported: {supported}); of the DeepSeek line what is still "
            f"refused is fp8 latent or index streams, and the multi-token-"
            f"prediction module is built for pangu_ultra_moe alone; of the "
            f"Granite line the hybrid is built (granitemoehybrid: Mamba-2 "
            f"beside NoPE attention, routed feed-forwards) and what is still "
            f"refused is rope in its attending layers, a bias on its "
            f"projections and dense feed-forwards; of the Falcon-H1 line "
            f"the block of two mixers is built (falcon_h1: Mamba-2 and "
            f"rotary GQA from one normed input, groups of B and C, the "
            f"family's multipliers) and what is still refused is "
            f"mamba_rms_norm false, the norm before the gate, a bias on any "
            f"projection, attn_layer_indices, rope_scaling, an "
            f"attention_in_multiplier other than 1 and a checkpoint's "
            f"tensors; of the Mellum line window and full layers with a "
            f"rotary rule a kind are built (mellum) and what is still "
            f"refused is a theta a kind, a dense feed-forward, a bias and "
            f"a checkpoint's tensors")
    act = getattr(hf_cfg, "hidden_act", "silu")
    if act not in ("silu", "swish"):
        raise NotImplementedError(
            f"hidden_act {act!r} != silu: the SwiGLU MLP here would be "
            f"silently wrong")
    rope_scaling = _convert_rope_scaling(hf_cfg)
    if hf_cfg.model_type == "solar_open2":
        return _config_from_solar(hf_cfg, page_size, dtype)
    if hf_cfg.model_type == "granitemoehybrid":
        return _config_from_granite(hf_cfg, page_size, dtype)
    if hf_cfg.model_type == "falcon_h1":
        return _config_from_falcon_h1(hf_cfg, page_size, dtype)
    if hf_cfg.model_type == "mellum":
        return _config_from_mellum(hf_cfg, page_size, dtype)
    if hf_cfg.model_type.startswith("deepseek"):
        return _config_from_deepseek(hf_cfg, page_size, dtype,
                                     rope_scaling)
    if hf_cfg.model_type == "gigachat3_5":
        return _config_from_gigachat(hf_cfg, page_size, dtype, rope_scaling)
    if hf_cfg.model_type == "pangu_ultra_moe":
        return _config_from_pangu(hf_cfg, page_size, dtype, rope_scaling)
    if getattr(hf_cfg, "mlp_bias", False):
        raise NotImplementedError(
            "MLP biases are not implemented; a bias-free conversion "
            "would be silently wrong")
    moe_kw = {}
    if hf_cfg.model_type == "mixtral":
        moe_kw = dict(
            num_experts=hf_cfg.num_local_experts,
            num_experts_per_token=hf_cfg.num_experts_per_tok,
            # "dense" computes every expert with an exact one-hot top-k
            # mix — the semantics HF Mixtral implements
            # (softmax→top-k→renorm == top-k→softmax). The GShard
            # capacity dispatch stays the opt-in performance mode
            # (dataclasses.replace(moe_dispatch="capacity")).
            moe_dispatch="dense",
        )
    elif hf_cfg.model_type == "qwen3_moe":
        # HF layer rule: MoE unless listed in mlp_only_layers, gated by
        # decoder_sparse_step (modeling_qwen3_moe decoder layer init).
        step = getattr(hf_cfg, "decoder_sparse_step", 1) or 1
        only = set(getattr(hf_cfg, "mlp_only_layers", ()) or ())
        moe_layers = tuple(
            i for i in range(n_layers)
            if i not in only and (i + 1) % step == 0)
        if moe_layers:
            moe_kw = dict(
                num_experts=hf_cfg.num_experts,
                num_experts_per_token=hf_cfg.num_experts_per_tok,
                moe_layers=moe_layers,
                moe_intermediate_size=hf_cfg.moe_intermediate_size,
                moe_router=("softmax_topk",
                            int(bool(hf_cfg.norm_topk_prob))),
                moe_dispatch="dense",
            )
    elif getattr(hf_cfg, "num_experts", 0) or getattr(
            hf_cfg, "num_local_experts", 0):
        raise NotImplementedError(
            "MoE checkpoint mapping is only implemented for mixtral and "
            "qwen3_moe")

    layer_types = getattr(hf_cfg, "layer_types", None)
    if layer_types:
        unknown = set(layer_types) - {"full_attention", "sliding_attention"}
        if unknown:
            raise NotImplementedError(f"layer types {unknown} unsupported")
        swa = tuple(i for i, t in enumerate(layer_types)
                    if t == "sliding_attention")
        window = getattr(hf_cfg, "sliding_window", None) if swa else None
    else:
        window = getattr(hf_cfg, "sliding_window", None)
        # Qwen-family configs carry a sliding_window value gated by a
        # separate use_sliding_window flag — honor the gate.
        if not getattr(hf_cfg, "use_sliding_window", True):
            window = None
        swa = tuple(range(n_layers)) if window else ()

    head_dim = getattr(hf_cfg, "head_dim", None) or (
        hf_cfg.hidden_size // hf_cfg.num_attention_heads)
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=head_dim,
        intermediate_size=hf_cfg.intermediate_size,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        sliding_window=window,
        swa_layers=swa,
        qk_norm=hf_cfg.model_type in ("qwen3", "qwen3_moe"),
        rope_scaling=rope_scaling,
        **moe_kw,
    )


def _layer_share(hf_cfg: Any, experts: int) -> dict:
    """``LlamaConfig``'s ``num_experts`` and ``experts_held`` from the
    experts a config counts and its top-level ``layer_share`` (one chip's
    share of each layer: ``{"chips", "rank", "n_routed_experts"}``; the
    count is then what the chip holds, contiguous from ``rank * count``,
    and ``n_routed_experts`` the router's width). Without the key: all of
    them, held whole."""
    share = getattr(hf_cfg, "layer_share", None)
    if not share:
        return {"num_experts": experts}
    if experts * int(share["chips"]) != int(share["n_routed_experts"]):
        raise ValueError(
            f"layer_share: {experts} experts held x {share['chips']} "
            f"chips is not the router's {share['n_routed_experts']}")
    return {"num_experts": int(share["n_routed_experts"]),
            "experts_held": (int(share["rank"]) * experts, experts)}


def _v3_routed(hf_cfg: Any) -> dict:
    """``LlamaConfig``'s keys for DeepSeek-V3's routed layers (sigmoid
    scores, ``noaux_tc``, groups, a shared expert) behind
    ``first_k_dense_replace`` dense ones, served by the exact grouped
    dispatch; nothing for a model without them. A top-level ``layer_share``
    is one chip's share of each layer (``_config_from_deepseek``)."""
    n_layers = hf_cfg.num_hidden_layers
    first_dense = getattr(hf_cfg, "first_k_dense_replace", 0)
    if not (getattr(hf_cfg, "n_routed_experts", None)
            and n_layers > first_dense):
        return {}
    if hf_cfg.model_type not in _V3_ROUTED:
        raise NotImplementedError(
            "MoE conversion is implemented for deepseek_v3 and "
            "deepseek_v32 (V2's softmax/greedy router differs)")
    if getattr(hf_cfg, "topk_method", "noaux_tc") not in (
            "noaux_tc", None):
        raise NotImplementedError(
            f"topk_method {hf_cfg.topk_method!r} unsupported")
    return dict(
        _layer_share(hf_cfg, int(hf_cfg.n_routed_experts)),
        num_experts_per_token=hf_cfg.num_experts_per_tok,
        moe_layers=tuple(range(first_dense, n_layers)),
        n_shared_experts=hf_cfg.n_shared_experts,
        moe_intermediate_size=hf_cfg.moe_intermediate_size,
        moe_router=("deepseek_v3", getattr(hf_cfg, "n_group", 1),
                    getattr(hf_cfg, "topk_group", 1),
                    int(bool(hf_cfg.norm_topk_prob)),
                    float(hf_cfg.routed_scaling_factor)),
        moe_dispatch="grouped",
    )


def _config_from_deepseek(hf_cfg: Any, page_size: int, dtype: Any,
                          rope_scaling: tuple = ()) -> LlamaConfig:
    """DeepSeek-V2/V3/V3.2 → absorbed-MLA config.

    ``v_head_dim == qk_nope_head_dim`` (the shared head_dim here); q-LoRA
    (the full V2/V3 form) and the direct q projection (V2-lite) both
    convert. The parity test pins our *absorbed* attention against HF's
    materialized MLA — a cross-implementation check of the absorption
    algebra. Routed layers convert for V3's router (sigmoid, noaux_tc,
    group-limited), served by the exact grouped dispatch; V2's softmax /
    greedy router is still refused. ``deepseek_v32`` adds the lightning
    indexer (``index_n_heads``, ``index_head_dim``, ``index_topk``): a
    second stream in every page and top-k selection inside paged attention.

    Two things are set here and nowhere else, so that every engine gets
    them from the model's keys: ``latent_pad``, which brings the latent's
    width (rank + rope) to the next multiple of the 128 lanes the Pallas
    kernels copy by, and one chip's share of the expert layers: a top-level
    ``layer_share`` of ``{"chips", "rank", "n_routed_experts"}`` says that
    ``n_routed_experts`` (the key) counts the experts HELD, contiguous from
    ``rank * held``, of a router that stays ``layer_share
    ["n_routed_experts"]`` wide (``LlamaConfig.experts_held``). A sliced
    vocabulary needs nothing: ``vocab_size`` rows are the slice. A top-level
    ``embed_init_scale`` (no checkpoint has one) is what ``init_params``
    draws the embedding at: ``LlamaConfig.embed_init_scale``.

    ``num_nextn_predict_layers`` is not read for these model types: their
    modules are left out, as the models' own inference code serves without
    them (a step that verifies a module's draft is served for
    ``pangu_ultra_moe``: ``_config_from_pangu``). ``params_from_hf`` maps no
    indexer tensor yet (random weights serve; a checkpoint refuses there).
    """
    if hf_cfg.v_head_dim != hf_cfg.qk_nope_head_dim:
        raise NotImplementedError(
            f"v_head_dim {hf_cfg.v_head_dim} != qk_nope_head_dim "
            f"{hf_cfg.qk_nope_head_dim}: this model shares one head_dim")
    n_layers = hf_cfg.num_hidden_layers
    moe_kw = _v3_routed(hf_cfg)
    # DeepSeek yarn: the generic cos/sin attention factor applies via
    # rope_scaling; for deepseek_v3 ONLY, mscale_all_dim ADDITIONALLY
    # multiplies the softmax scale by mscale^2 (in-tree
    # DeepseekV3Attention.__init__ — DeepseekV2Attention has no such
    # term, verified against transformers 4.57; the V2 parity test pins
    # it).
    index_kw = {}
    if hf_cfg.model_type == "deepseek_v32":
        index_kw = dict(index_n_heads=int(hf_cfg.index_n_heads),
                        index_head_dim=int(hf_cfg.index_head_dim),
                        index_topk=int(hf_cfg.index_topk))
    scale_mult = 1.0
    hf_rs = getattr(hf_cfg, "rope_scaling", None)
    if (rope_scaling and hf_cfg.model_type in _V3_ROUTED
            and getattr(hf_cfg, "use_mla_scaling_factor", True)
            and hf_rs and hf_rs.get("mscale_all_dim")):
        m = _yarn_get_mscale(float(hf_rs["factor"]),
                             float(hf_rs["mscale_all_dim"]))
        scale_mult = m * m
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_attention_heads,
        head_dim=hf_cfg.qk_nope_head_dim,
        intermediate_size=hf_cfg.intermediate_size,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        kv_lora_rank=hf_cfg.kv_lora_rank,
        qk_rope_head_dim=hf_cfg.qk_rope_head_dim,
        q_lora_rank=int(getattr(hf_cfg, "q_lora_rank", None) or 0),
        latent_pad=_latent_pad(hf_cfg.kv_lora_rank
                               + hf_cfg.qk_rope_head_dim),
        rope_scaling=rope_scaling,
        softmax_scale_mult=scale_mult,
        embed_init_scale=float(getattr(hf_cfg, "embed_init_scale", 0.02)),
        mlp_init_scale=float(getattr(hf_cfg, "mlp_init_scale", 0.02)),
        router_bias_init_scale=float(getattr(
            hf_cfg, "router_bias_init_scale", 0.02)),
        **index_kw,
        **moe_kw,
    )


# The model types whose routed layers are DeepSeek-V3's (sigmoid scores, a
# correction bias, a shared expert) and whose yarn raises the softmax scale.
_V3_ROUTED = ("deepseek_v3", "deepseek_v32", "gigachat3_5", "solar_open2",
              "pangu_ultra_moe")


def _config_from_pangu(hf_cfg: Any, page_size: int, dtype: Any,
                       rope_scaling: tuple = ()) -> LlamaConfig:
    """openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``): DeepSeek-V3's
    latent attention and expert layers (``_config_from_deepseek`` reads
    those keys, ``layer_share`` and the init scales included; the router is
    one group, sigmoid scores, the chosen experts' weights normalised) in a
    block with a norm before AND after each sub-layer (``sandwich_norm``),
    plain RoPE, and the model's multi-token-prediction module SERVED:
    ``num_nextn_predict_layers: 1`` makes ``init_params`` draw the module
    and the engine verify its draft in every decode step
    (``LlamaConfig.num_nextn_predict_layers``); more than one is refused
    there. ``params_from_hf`` maps no tensor of the module yet (random
    weights serve; a checkpoint refuses there)."""
    if not getattr(hf_cfg, "sandwich_norm", False):
        raise NotImplementedError(
            "pangu_ultra_moe without sandwich_norm: the block built norms "
            "every sub-layer's input and output")
    return dataclasses.replace(
        _config_from_deepseek(hf_cfg, page_size, dtype, rope_scaling),
        post_norms=True,
        num_nextn_predict_layers=int(getattr(
            hf_cfg, "num_nextn_predict_layers", 0) or 0))

# The one reading of each of GigaChat3.5's keys that name a form and do not
# define it: what ``llama`` implements. Another value is another model.
_GIGACHAT_FORMS = {
    "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post",
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_attention_type": "GigaChat35GatedDeltaNet",
}


def _config_from_gigachat(hf_cfg: Any, page_size: int, dtype: Any,
                          rope_scaling: tuple = ()) -> LlamaConfig:
    """GigaChat3.5 (``model_type: gigachat3_5``): DeepSeek-V3's latent
    attention and expert layers (``_config_from_deepseek`` reads those
    keys, ``layer_share`` and ``embed_init_scale`` included) in the layers
    ``full_attention_layers`` lists, Gated DeltaNet (the ``linear_*`` keys)
    in every other, and the block's form: zero-centred norms before and
    after every sub-layer, a sigmoid gate on attention's output, a clamped
    SwiGLU. A key that names one of those forms is refused, by name, when
    it names another than the one built. ``state_slots`` and
    ``state_checkpoint_tokens`` (top-level keys no checkpoint has, as
    ``layer_share``) size the engine's pool of sequence states and space
    its snapshots; ``mlp_init_scale`` and ``router_bias_init_scale`` are
    what ``init_params`` draws a feed-forward's gate and up matrices and
    a router's correction bias at."""
    for key, built in _GIGACHAT_FORMS.items():
        got = getattr(hf_cfg, key, built)
        if got != built:
            raise NotImplementedError(
                f"{key} {got!r}: the form built is {built!r}, and serving "
                f"this model as that one would be silently wrong")
    if getattr(hf_cfg, "use_shared_expert_sigmoid", False):
        raise NotImplementedError(
            "use_shared_expert_sigmoid: a gated shared expert is not built")
    base = _config_from_deepseek(hf_cfg, page_size, dtype, rope_scaling)
    full = set(hf_cfg.full_attention_layers)
    return dataclasses.replace(
        base,
        linear_layers=tuple(i for i in range(base.num_layers)
                            if i not in full),
        linear=LinearAttention(
            key_heads=int(hf_cfg.linear_num_key_heads),
            value_heads=int(hf_cfg.linear_num_value_heads),
            key_dim=int(hf_cfg.linear_key_head_dim),
            value_dim=int(hf_cfg.linear_value_head_dim),
            conv_kernel=int(hf_cfg.linear_conv_kernel_dim),
            gate_scale=float(getattr(hf_cfg, "linear_sigmoid_gate_scale",
                                     2.0)),
            norm_eps=float(getattr(hf_cfg, "linear_attn_o_norm_eps",
                                   hf_cfg.rms_norm_eps))),
        state_slots=int(getattr(hf_cfg, "state_slots", 64)),
        state_checkpoint_tokens=int(getattr(
            hf_cfg, "state_checkpoint_tokens", 4096)),
        norm_offset=1.0,
        post_norms=True,
        attn_output_gate=bool(getattr(hf_cfg, "gated_attention", False)),
        swiglu_limit=float(getattr(hf_cfg, "swiglu_limit", 0) or 0),
    )


def _config_from_solar(hf_cfg: Any, page_size: int,
                       dtype: Any) -> LlamaConfig:
    """Solar-Open2 (``model_type: solar_open2``): the layers ``gqa_layers``
    lists attend (GQA without positional encoding, ``use_rope: false``;
    the heads' outputs gated, ``use_gqa_gate``), every other layer's mixer
    is Kimi-style delta attention (``linear_attn_config``: as many key as
    value heads, a conv, a decay for every key channel through a low-rank
    projection since ``kda_use_full_proj`` is false, ``beta`` in (0, 2)
    with ``kda_allow_neg_eigval``); every feed-forward from
    ``first_k_dense_replace`` on is DeepSeek-V3's routed one in one group
    (``_v3_routed``, ``layer_share`` included). The low-rank projections
    are as wide as a head (``linear_attn_config.head_dim``: the family's
    published form; the config gives no width). What the published config
    does not give and a top-level key may (no checkpoint has them):
    ``state_slots``, ``state_checkpoint_tokens``, ``embed_init_scale``,
    ``router_bias_init_scale`` as for GigaChat3.5."""
    if getattr(hf_cfg, "use_rope", False):
        raise NotImplementedError(
            "use_rope true: partial rotary beside linear layers is not "
            "the model built (its attention carries no position)")
    if getattr(hf_cfg, "kda_use_full_proj", False):
        raise NotImplementedError(
            "kda_use_full_proj: the decay's projection built is low-rank")
    la = hf_cfg.linear_attn_config
    heads = int(la["num_heads"])
    if la.get("num_kv_heads") not in (None, heads):
        raise NotImplementedError(
            "linear_attn_config.num_kv_heads: a channel-wise decay is built "
            "with as many key heads as value heads")
    attends = set(hf_cfg.gqa_layers)
    n_layers = hf_cfg.num_hidden_layers
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=int(hf_cfg.head_dim),
        intermediate_size=hf_cfg.intermediate_size,
        rope_theta=0.0,
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        attn_output_gate=bool(getattr(hf_cfg, "use_gqa_gate", False)),
        linear_layers=tuple(i for i in range(n_layers) if i not in attends),
        linear=LinearAttention(
            key_heads=heads, value_heads=heads,
            key_dim=int(la["head_dim"]), value_dim=int(la["head_dim"]),
            conv_kernel=int(la["short_conv_kernel_size"]),
            gate_scale=1.0, norm_eps=float(hf_cfg.rms_norm_eps),
            decay="channel",
            beta_scale=2.0 if getattr(hf_cfg, "kda_allow_neg_eigval",
                                      False) else 1.0,
            gate_rank=int(la["head_dim"])),
        state_slots=int(getattr(hf_cfg, "state_slots", 64)),
        state_checkpoint_tokens=int(getattr(
            hf_cfg, "state_checkpoint_tokens", 4096)),
        embed_init_scale=float(getattr(hf_cfg, "embed_init_scale", 0.02)),
        router_bias_init_scale=float(getattr(
            hf_cfg, "router_bias_init_scale", 0.02)),
        **_v3_routed(hf_cfg),
    )


def _refuse_unbuilt(hf_cfg: Any, built: tuple) -> None:
    """A state-space family's forms that are not built, refused by the key
    that asks for them: ``built`` pairs a key with the one value served (a
    missing key is that value); the step ``softplus(dt + dt_bias)`` is
    never clipped."""
    for key, value in built:
        if getattr(hf_cfg, key, value) != value:
            raise NotImplementedError(
                f"{key} {getattr(hf_cfg, key)!r}: what is built is "
                f"{value!r}")
    if getattr(hf_cfg, "time_step_limit", None) not in (
            None, (0.0, float("inf")), [0.0, float("inf")]):
        raise NotImplementedError("time_step_limit: the step is not clipped")


def _config_from_granite(hf_cfg: Any, page_size: int,
                         dtype: Any) -> LlamaConfig:
    """Granite 4.0-H (``model_type: granitemoehybrid``): ``layer_types``
    names each layer's mixer, "mamba" (Mamba-2 in the ``mamba_*`` sizes:
    ``mamba_n_heads`` heads of ``mamba_d_head`` over a state of
    ``mamba_d_state`` and ``mamba_n_groups`` groups of B and C, a conv of
    ``mamba_d_conv`` taps with a bias, the gated norm a group of channels
    at a time) or "attention" (GQA of
    ``hidden_size / num_attention_heads`` a head, no positional encoding,
    scores times ``attention_multiplier``); every feed-forward routes
    ``num_experts_per_tok`` of ``num_local_experts`` experts of width
    ``intermediate_size`` by the softmax over the chosen logits, beside an
    always-on MLP of ``shared_intermediate_size``; the embedding times
    ``embedding_multiplier``, each sub-layer's output times
    ``residual_multiplier``, the logits over ``logits_scaling``. A
    top-level ``layer_share`` is one chip's share of each layer as
    ``_v3_routed`` reads it: ``num_local_experts`` is then what the chip
    holds and ``layer_share.n_routed_experts`` the router's width. The head
    is kept apart from the embedding whatever ``tie_word_embeddings`` says.
    What the published config does not give and a top-level key may (no
    checkpoint has them): ``state_slots``, ``state_checkpoint_tokens``,
    ``embed_init_scale`` as for GigaChat3.5. Each form that is not built is
    refused by its key."""
    _refuse_unbuilt(hf_cfg, (
        ("position_embedding_type", "nope"), ("mamba_proj_bias", False),
        ("attention_bias", False), ("mamba_conv_bias", True),
        ("normalization_function", "rmsnorm")))
    kinds = list(hf_cfg.layer_types)
    n_layers = hf_cfg.num_hidden_layers
    if len(kinds) != n_layers or set(kinds) - {"mamba", "attention"}:
        raise NotImplementedError(
            f"layer_types {kinds!r}: one of 'mamba', 'attention' for each "
            f"of the {n_layers} layers")
    heads, head = int(hf_cfg.mamba_n_heads), int(hf_cfg.mamba_d_head)
    if heads * head != int(hf_cfg.mamba_expand) * hf_cfg.hidden_size:
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = {heads * head} is not "
            f"mamba_expand x hidden_size")
    experts = int(getattr(hf_cfg, "num_local_experts", 0) or 0)
    if not experts:
        raise NotImplementedError(
            "num_local_experts 0: the dense feed-forward of the smaller "
            "hybrids is not built")
    inter, shared = int(hf_cfg.intermediate_size), int(
        getattr(hf_cfg, "shared_intermediate_size", 0) or 0)
    if shared % inter:
        raise NotImplementedError(
            f"shared_intermediate_size {shared} is not a multiple of the "
            f"experts' intermediate_size {inter}")
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        intermediate_size=inter,
        rope_theta=0.0,
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        linear_layers=tuple(i for i, kind in enumerate(kinds)
                            if kind == "mamba"),
        linear=LinearAttention(
            key_heads=int(getattr(hf_cfg, "mamba_n_groups", 1)),
            value_heads=heads, key_dim=int(hf_cfg.mamba_d_state),
            value_dim=head, conv_kernel=int(hf_cfg.mamba_d_conv),
            gate_scale=1.0, norm_eps=float(hf_cfg.rms_norm_eps),
            decay="mamba2"),
        state_slots=int(getattr(hf_cfg, "state_slots", 64)),
        state_checkpoint_tokens=int(getattr(
            hf_cfg, "state_checkpoint_tokens", 4096)),
        embed_init_scale=float(getattr(hf_cfg, "embed_init_scale", 0.02)),
        num_experts_per_token=int(hf_cfg.num_experts_per_tok),
        n_shared_experts=shared // inter,
        moe_intermediate_size=inter,
        moe_router=("softmax_topk", 1),
        moe_dispatch="grouped",
        embedding_multiplier=float(hf_cfg.embedding_multiplier),
        residual_multiplier=float(hf_cfg.residual_multiplier),
        attention_multiplier=float(hf_cfg.attention_multiplier),
        logits_scaling=float(hf_cfg.logits_scaling),
        **_layer_share(hf_cfg, experts),
    )


def _config_from_falcon_h1(hf_cfg: Any, page_size: int,
                           dtype: Any) -> LlamaConfig:
    """Falcon-H1 (``model_type: falcon_h1``): every layer runs a Mamba-2
    mixer and rotary GQA side by side from one normed input, then a dense
    SwiGLU (``LlamaConfig.parallel_layers``). The mixer's inner width is
    ``mamba_d_ssm`` = ``mamba_n_heads x mamba_d_head`` (``mamba_expand`` is
    not read), over a state of ``mamba_d_state`` and ``mamba_n_groups``
    groups of B and C, each group's channels normed apart after the gate;
    attention has ``head_dim`` from the config. The family's multipliers:
    ``embedding_multiplier``; ``lm_head_multiplier`` (served as
    ``logits_scaling`` = its inverse); ``key_multiplier`` on the keys
    (served on the scores: ``attention_multiplier = key_multiplier x
    head_dim ** -0.5``, so the pages hold unscaled keys);
    ``ssm_in_multiplier`` and the five ``ssm_multipliers``; each mixer's
    output times ``ssm_out_multiplier`` / ``attention_out_multiplier``;
    ``mlp_multipliers``. The head is kept apart from the embedding. What
    the published config does not give and a top-level key may (no
    checkpoint has them): ``state_slots``, ``state_checkpoint_tokens`` and
    the scales random weights are drawn at, ``embed_init_scale``,
    ``mixer_init_scale``, ``mlp_init_scale``. Each form that is not built is
    refused by its key."""
    _refuse_unbuilt(hf_cfg, (
        ("mamba_rms_norm", True), ("mamba_norm_before_gate", False),
        ("mamba_proj_bias", False), ("attention_bias", False),
        ("mlp_bias", False), ("projectors_bias", False),
        ("mamba_conv_bias", True), ("mamba_use_mlp", True),
        ("attn_layer_indices", None), ("rope_scaling", None),
        ("attention_in_multiplier", 1)))
    heads, head = int(hf_cfg.mamba_n_heads), int(hf_cfg.mamba_d_head)
    if heads * head != int(hf_cfg.mamba_d_ssm):
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = {heads * head} is not "
            f"mamba_d_ssm {hf_cfg.mamba_d_ssm}")
    n_layers = hf_cfg.num_hidden_layers
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=int(hf_cfg.head_dim),
        intermediate_size=int(hf_cfg.intermediate_size),
        rope_theta=float(hf_cfg.rope_theta),
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        linear_layers=tuple(range(n_layers)),
        parallel_layers=tuple(range(n_layers)),
        linear=LinearAttention(
            key_heads=int(hf_cfg.mamba_n_groups), value_heads=heads,
            key_dim=int(hf_cfg.mamba_d_state), value_dim=head,
            conv_kernel=int(hf_cfg.mamba_d_conv), gate_scale=1.0,
            norm_eps=float(hf_cfg.rms_norm_eps), decay="mamba2"),
        state_slots=int(getattr(hf_cfg, "state_slots", 64)),
        state_checkpoint_tokens=int(getattr(
            hf_cfg, "state_checkpoint_tokens", 4096)),
        embed_init_scale=float(getattr(hf_cfg, "embed_init_scale", 0.02)),
        mixer_init_scale=float(getattr(hf_cfg, "mixer_init_scale", 0.02)),
        mlp_init_scale=float(getattr(hf_cfg, "mlp_init_scale", 0.02)),
        embedding_multiplier=float(hf_cfg.embedding_multiplier),
        logits_scaling=1.0 / float(hf_cfg.lm_head_multiplier),
        attention_multiplier=(float(hf_cfg.key_multiplier)
                              * int(hf_cfg.head_dim) ** -0.5),
        ssm_in_multiplier=float(hf_cfg.ssm_in_multiplier),
        ssm_multipliers=tuple(float(m) for m in hf_cfg.ssm_multipliers),
        ssm_out_multiplier=float(hf_cfg.ssm_out_multiplier),
        attention_out_multiplier=float(hf_cfg.attention_out_multiplier),
        mlp_multipliers=tuple(float(m) for m in hf_cfg.mlp_multipliers),
    )


def _config_from_mellum(hf_cfg: Any, page_size: int,
                        dtype: Any) -> LlamaConfig:
    """Mellum 2 (``model_type: mellum``): ``layer_types`` names each layer
    ``sliding_attention`` (a window of ``sliding_window`` keys) or
    ``full_attention``, two cache groups with a page pool each;
    ``rope_parameters`` gives the rotary rule BY THAT KIND (the full
    layers' is ``rope_scaling``, the window layers' ``swa_rope_scaling``;
    one theta for both, another is refused); GQA of ``head_dim`` a head
    with the per-head norm on q and k of the ``qwen3_moe`` config class,
    whose keys the config carries (``qk_norm`` false at the top level
    serves the model without: the config itself has no key for it); every
    ``mlp_layer_types`` entry ``sparse``: ``num_experts_per_tok`` of
    ``num_experts`` experts of ``moe_intermediate_size`` by the softmax
    over the chosen logits (``norm_topk_prob``), no shared expert, served
    by the exact grouped dispatch. A top-level ``layer_share`` is one
    chip's share of each layer as ``_config_from_granite`` reads it
    (``num_experts`` is then what the chip holds). What the published
    config does not give and a top-level key may: ``window_pages`` (the
    window pool's size, ``LlamaConfig.window_pages``), ``embed_init_scale``.
    Each form that is not built is refused by its key."""
    _refuse_unbuilt(hf_cfg, (("attention_bias", False), ("mlp_bias", False),
                             ("use_sliding_window", True),
                             ("norm_topk_prob", True)))
    n_layers = hf_cfg.num_hidden_layers
    kinds = list(hf_cfg.layer_types)
    if (len(kinds) != n_layers
            or set(kinds) - {"sliding_attention", "full_attention"}):
        raise NotImplementedError(
            f"layer_types {kinds!r}: 'sliding_attention' or "
            f"'full_attention' for each of the {n_layers} layers")
    mlps = list(getattr(hf_cfg, "mlp_layer_types", None)
                or ["sparse"] * n_layers)
    if len(mlps) != n_layers or set(mlps) != {"sparse"}:
        raise NotImplementedError(
            f"mlp_layer_types {mlps!r}: a dense feed-forward beside the "
            f"routed ones is not built for this family")
    rules = dict(hf_cfg.rope_parameters)
    if set(rules) - {"sliding_attention", "full_attention"} or set(
            kinds) - set(rules):
        raise NotImplementedError(
            f"rope_parameters {sorted(rules)}: one rule for each kind in "
            f"layer_types")
    thetas = {float(rules[kind]["rope_theta"]) for kind in set(kinds)}
    if len(thetas) != 1:
        raise NotImplementedError(
            f"rope_parameters: rope_theta {sorted(thetas)} differs by "
            f"layer kind; one theta serves every layer")

    def rule(kind):
        return _rope_rule(rules.get(kind), hf_cfg.max_position_embeddings)

    swa = tuple(i for i, kind in enumerate(kinds)
                if kind == "sliding_attention")
    full_rule = rule("full_attention") if len(swa) < n_layers else ()
    swa_rule = rule("sliding_attention") if swa else ()
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=n_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=int(getattr(hf_cfg, "head_dim", None)
                     or hf_cfg.hidden_size // hf_cfg.num_attention_heads),
        intermediate_size=hf_cfg.intermediate_size,
        rope_theta=thetas.pop(),
        norm_eps=float(hf_cfg.rms_norm_eps),
        page_size=page_size,
        dtype=dtype,
        sliding_window=hf_cfg.sliding_window if swa else None,
        swa_layers=swa,
        window_pages=int(getattr(hf_cfg, "window_pages", 0)),
        qk_norm=bool(getattr(hf_cfg, "qk_norm", True)),
        rope_scaling=full_rule,
        swa_rope_scaling=(() if not swa or swa_rule == full_rule
                          else swa_rule or ("default",)),
        embed_init_scale=float(getattr(hf_cfg, "embed_init_scale", 0.02)),
        num_experts_per_token=int(hf_cfg.num_experts_per_tok),
        moe_intermediate_size=int(hf_cfg.moe_intermediate_size),
        moe_router=("softmax_topk", 1),
        moe_dispatch="grouped",
        **_layer_share(hf_cfg, int(hf_cfg.num_experts)),
    )


def _latent_pad(width: int) -> int:
    """Zero lanes behind a latent of ``width`` (rank + rope) values: up to
    the next multiple of the 128 lanes the Pallas kernels copy by (DeepSeek's
    512 + 64 → 64). A latent narrower than one tile (test models) would be
    mostly padding and never reaches a compiled kernel: none."""
    return -width % 128 if width >= 128 else 0


def _deinterleave(w: np.ndarray, dr: int) -> np.ndarray:
    """Permute the trailing ``dr`` output columns from HF DeepSeek's
    interleaved-rotary layout (pairs (2i, 2i+1)) to this repo's
    half-split layout (pairs (i, i+dr/2)).

    Rotations act on activations, so permuting the columns that PRODUCE
    the rope dims makes half-split rope equal interleaved rope up to the
    same permutation on both q_pe and k_pe — and their dot product (the
    only consumer) is permutation-invariant.
    """
    order = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    out = w.copy()
    out[..., -dr:] = w[..., -dr:][..., order]
    return out


def params_from_hf(state_dict: Mapping[str, Any], cfg: LlamaConfig,
                   mla_rope_interleaved: bool = True) -> Params:
    """Build the parameter pytree from an HF Llama-architecture state dict.

    Accepts torch tensors or numpy arrays. Norm scales stay fp32 (this
    repo's convention — norms compute in fp32); projections cast to
    ``cfg.dtype``. ``mla_rope_interleaved`` mirrors DeepSeek's
    ``rope_interleave`` (True in both HF implementations; V3 exposes the
    flag) — when set, the rope-producing weight columns are permuted so
    this repo's half-split rotary reproduces HF's interleaved one (see
    ``_deinterleave``).
    """
    if cfg.num_nextn_predict_layers:
        raise NotImplementedError(
            "no checkpoint's tensors are mapped for a prediction module "
            "(nor for the post-norms of its model): random weights serve "
            "(llama.init_params)")
    if cfg.linear_layers:
        raise NotImplementedError(
            "no checkpoint's tensors are mapped for linear layers (nor for "
            "the gates and post-norms of their model): random weights "
            "serve (llama.init_params)")
    consumed: set = set()

    def get(name):
        consumed.add(name)
        t = state_dict[name]
        if hasattr(t, "detach"):  # torch tensor
            t = t.detach().to("cpu").float().numpy()
        return np.asarray(t)

    def proj(name):  # [out, in] -> [in, out], model dtype
        return jnp.asarray(get(name).T, cfg.dtype)

    def norm(name):  # fp32 scale vector
        return jnp.asarray(get(name), jnp.float32)

    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": norm(p + "input_layernorm.weight"),
            "mlp_norm": norm(p + "post_attention_layernorm.weight"),
            "wo": proj(p + "self_attn.o_proj.weight"),
        }
        if p + "mlp.gate.weight" in state_dict:
            # DeepSeek / Qwen3-MoE layer: router + routed experts. The
            # router KIND decides the extra tensors: deepseek_v3 REQUIRES
            # the e_score_correction bias and shared expert (a truncated
            # checkpoint fails here, at load, naming the tensor);
            # softmax_topk (Qwen3-MoE) has neither.
            E = cfg.num_experts
            deepseek = cfg.moe_router and cfg.moe_router[0] == "deepseek_v3"
            layer["router"] = proj(p + "mlp.gate.weight")
            if deepseek:
                layer["router_bias"] = norm(
                    p + "mlp.gate.e_score_correction_bias")
            for ours, theirs in (("w_gate", "gate_proj"),
                                 ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                layer[ours] = jnp.stack([
                    proj(p + f"mlp.experts.{e}.{theirs}.weight")
                    for e in range(E)])
            if deepseek:
                for ours, theirs in (("w_gate_sh", "gate_proj"),
                                     ("w_up_sh", "up_proj"),
                                     ("w_down_sh", "down_proj")):
                    layer[ours] = proj(
                        p + f"mlp.shared_experts.{theirs}.weight")
        elif p + "block_sparse_moe.gate.weight" in state_dict:  # Mixtral
            E = cfg.num_experts
            layer["router"] = proj(p + "block_sparse_moe.gate.weight")
            for ours, theirs in (("w_gate", "w1"), ("w_up", "w3"),
                                 ("w_down", "w2")):
                # Stack via per-expert proj(): only ONE expert's fp32
                # copy is live at a time (a real 8x7B stack would
                # otherwise hold ~2 GB of transient fp32 per tensor).
                layer[ours] = jnp.stack([
                    proj(p + f"block_sparse_moe.experts.{e}"
                             f".{theirs}.weight")
                    for e in range(E)])
        else:
            layer["w_gate"] = proj(p + "mlp.gate_proj.weight")
            layer["w_up"] = proj(p + "mlp.up_proj.weight")
            layer["w_down"] = proj(p + "mlp.down_proj.weight")
        if cfg.is_mla:
            # DeepSeek: q either direct (V2-lite) or via the q-LoRA
            # compressed latent; fused latent down-projection, RMS-normed
            # latent, fused k_nope/v up-projections split into the
            # absorbed form.
            r, dr, hd = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                         cfg.head_dim)
            H = cfg.num_heads
            if p + "self_attn.q_a_proj.weight" in state_dict:  # q-LoRA
                layer["w_dq"] = proj(p + "self_attn.q_a_proj.weight")
                layer["q_latent_norm"] = norm(
                    p + "self_attn.q_a_layernorm.weight")
                wq = get(p + "self_attn.q_b_proj.weight").T
            else:
                wq = get(p + "self_attn.q_proj.weight").T  # [h|q_lora, H*(hd+dr)]
            wq = wq.reshape(wq.shape[0], H, hd + dr)
            if mla_rope_interleaved:
                wq = _deinterleave(wq, dr)
            layer["wq"] = jnp.asarray(
                wq.reshape(wq.shape[0], H * (hd + dr)), cfg.dtype)
            kva = get(p + "self_attn.kv_a_proj_with_mqa.weight").T
            layer["w_dkv"] = jnp.asarray(kva[:, :r], cfg.dtype)
            k_rope = kva[:, r:]
            if mla_rope_interleaved:
                k_rope = _deinterleave(k_rope, dr)
            layer["w_kr"] = jnp.asarray(k_rope, cfg.dtype)
            layer["latent_norm"] = norm(
                p + "self_attn.kv_a_layernorm.weight")
            kvb = get(p + "self_attn.kv_b_proj.weight").reshape(
                H, 2 * hd, r)  # [H, nope+v, r]
            layer["w_uk"] = jnp.asarray(
                kvb[:, :hd, :].transpose(0, 2, 1), cfg.dtype)
            layer["w_uv"] = jnp.asarray(
                kvb[:, hd:, :].transpose(0, 2, 1), cfg.dtype)
        else:
            layer["wq"] = proj(p + "self_attn.q_proj.weight")
            layer["wk"] = proj(p + "self_attn.k_proj.weight")
            layer["wv"] = proj(p + "self_attn.v_proj.weight")
            if cfg.qk_norm:  # Qwen3: per-head RMS on Q/K pre-RoPE
                layer["q_norm"] = norm(p + "self_attn.q_norm.weight")
                layer["k_norm"] = norm(p + "self_attn.k_norm.weight")
            if p + "self_attn.q_proj.bias" in state_dict:  # Qwen2 lineage
                for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"),
                                     ("bv", "v_proj")):
                    layer[ours] = jnp.asarray(
                        get(p + f"self_attn.{theirs}.bias"), cfg.dtype)
        layers.append(layer)

    embed = jnp.asarray(get("model.embed_tokens.weight"), cfg.dtype)
    if "lm_head.weight" in state_dict:
        lm_head = proj("lm_head.weight")
    else:  # tie_word_embeddings
        lm_head = embed.T
    params = {
        "embed": embed,
        "layers": layers,
        "final_norm": norm("model.norm.weight"),
        "lm_head": lm_head,
    }
    # Every tensor the checkpoint carries must have landed in the pytree
    # (modulo non-persistent rotary buffers older exports include) — a
    # leftover weight means an architectural feature this model lacks,
    # and ignoring it would serve silently-wrong logits.
    leftover = [k for k in state_dict
                if k not in consumed and "rotary_emb" not in k]
    if leftover:
        raise NotImplementedError(
            f"checkpoint carries unmapped tensors ({leftover[:4]}…) — "
            f"this architecture has features the conversion would drop")
    return params


def load_hf_checkpoint(path: str, page_size: int = 16,
                       dtype: Any = jnp.bfloat16):
    """Load a local HF checkpoint directory → ``(LlamaConfig, Params)``.

    Uses ``transformers`` to materialize the state dict (handles both
    safetensors and torch shards); zero-egress environments must have the
    checkpoint on disk already.
    """
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(path)
    cfg = config_from_hf(hf_cfg, page_size=page_size, dtype=dtype)
    # Validate the config BEFORE materializing weights; load at the
    # checkpoint's own dtype without full nn.Module init — fp32
    # materialization of an 8B checkpoint would double peak host RAM
    # (get() upcasts per-tensor during conversion anyway).
    import inspect as _inspect

    # transformers >= 4.56 renamed torch_dtype -> dtype; pick by
    # signature (an unknown kwarg can be silently absorbed into config
    # kwargs on some releases, so try/except is not a reliable probe).
    sig = _inspect.signature(AutoModelForCausalLM.from_pretrained)
    accepts_dtype = "dtype" in sig.parameters or any(
        p.kind is _inspect.Parameter.VAR_KEYWORD
        for p in sig.parameters.values())
    dtype_kw = {"dtype": "auto"} if accepts_dtype else {
        "torch_dtype": "auto"}
    model = AutoModelForCausalLM.from_pretrained(
        path, low_cpu_mem_usage=True, **dtype_kw)
    params = params_from_hf(
        model.state_dict(), cfg,
        mla_rope_interleaved=getattr(hf_cfg, "rope_interleave", True))
    return cfg, params
