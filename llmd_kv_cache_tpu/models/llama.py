"""Llama-family transformer with a paged KV cache, TPU-first.

Pure-functional JAX: parameters are a pytree, the forward step is a single
jit with static shapes (padded token blocks + masks, no data-dependent
Python control flow), bfloat16 activations/weights with float32 softmax and
norms. RoPE, RMSNorm, SwiGLU, grouped-query attention.

One ``forward`` serves prefill and decode: queries at logical positions
``ctx_lens + i`` attend to everything already in the paged cache plus
themselves. The cache update (scatter) happens inside the jit so the whole
token step is one XLA program; donate the caches for in-place updates.
"""

from __future__ import annotations

import inspect
import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import sparse_index
from ..ops.kv_pages import page_writes, write_kv_pages
from ..ops.paged_attention import paged_attention

Params = dict[str, Any]

# ``jax.named_scope`` names inside every step program: metadata only (the
# ``op_name`` of each HLO op, which a profiler trace carries as ``tf_op``),
# so device time can be read by region of the model instead of by the
# fusion names the compiler chose. Per layer: the projections up to RoPE,
# writing new K/V into the pool, attention with its output projection,
# the MLP. Once per program: the embedding, the final norm + lm_head,
# in-program sampling.
SCOPE_QKV = "qkv"
SCOPE_KV_WRITE = "kv_write"
SCOPE_ATTENTION = "attention"
SCOPE_MLP = "mlp"
SCOPE_EMBED = "embed"
SCOPE_LM_HEAD = "lm_head"
SCOPE_SAMPLE = "sample"
SCOPES = (SCOPE_QKV, SCOPE_KV_WRITE, SCOPE_ATTENTION, SCOPE_MLP,
          SCOPE_EMBED, SCOPE_LM_HEAD, SCOPE_SAMPLE)
# Inside ``attention`` and ``mlp``, in the programs of a model that has the
# mechanism: the indexer's projections and its scores over a row's pages,
# the choice of the keys a query keeps, attention over the chosen keys
# alone (a decode step; a prefill chunk masks inside ``attention``), and
# the routed experts' grouped matmuls with the sort before and the
# combination after. No metric reads them yet (``kvbench/trace/reduce.py``
# keeps an op's name and drops its scope: ROADMAP S0, device time by scope);
# they are for whoever opens a profiler capture of a step, where every op's
# name carries its scopes. A model with linear layers has the recurrence's
# two kernels under their own names there too (``ops.gated_deltanet``:
# ``gdn_scan`` / ``gdn_step``, or ``kda_scan`` / ``kda_step`` where the decay
# is channel-wise; ``ops.mamba2``: ``mamba2_scan`` / ``mamba2_step`` where the
# layer is a state-space one), which the ``*_roofline`` readers find by the
# ops' names.
SCOPE_INDEX = "dsa_index"
SCOPE_SELECT = "dsa_select"
SCOPE_SPARSE_ATTENTION = "dsa_attend"
SCOPE_MOE_DISPATCH = "moe_dispatch"

# The two step programs' names: what ``jax.jit`` calls the functions below
# and a trace calls their executions (``jit_<name>``). A forward's step form
# (``step_program``: what the engine dispatches) keeps its forward's name.
# Readers of traces match these; tests/test_model.py pins both sides.
PROGRAM_PREFILL = "forward_prefill_pallas"
PROGRAM_DECODE = "forward_decode_pallas"


@dataclass(frozen=True)
class LinearAttention:
    """The sizes of a model's Gated DeltaNet layers (``LlamaConfig.linear``):
    ``key_heads`` query/key heads of ``key_dim``, each serving ``value_heads
    / key_heads`` value heads of ``value_dim``; a depthwise causal conv of
    ``conv_kernel`` taps over the q, k and v channels; the output norm's
    gate ``gate_scale * sigmoid(z)`` and its eps. A sequence's cache in
    such a layer is one float32 state ``[value_heads, key_dim, value_dim]``
    and the conv's last ``conv_kernel - 1`` inputs (``ops.gated_deltanet``).

    ``decay`` is the form of the recurrence's decay: ``"head"``, one scalar
    a value head and token (Gated DeltaNet: ``w_qkvz``, ``w_ba``), or
    ``"channel"``, one value a head and key channel (Kimi-style delta
    attention: q, k, v from ``w_conv_in``; the decay and the output gate
    through low-rank projections ``gate_rank`` wide; as many key heads as
    value heads). ``beta = beta_scale * sigmoid(.)``: 2 admits negative
    eigenvalues of a token's transition.

    ``decay == "mamba2"`` is no delta rule: a Mamba-2 state-space layer
    (``_mamba2``, ``ops.mamba2``) in the same sizes: ``value_heads`` heads of
    ``value_dim`` channels, a state ``key_dim`` wide, ``key_heads`` groups
    of ``B`` and ``C`` (head ``h`` reads group ``h // (value_heads /
    key_heads)``), a conv with a bias over ``[x | B | C]``
    (``conv_channels`` as above), one decay a head. Its output is normed
    after the gate ``silu(z)``, each group's ``inner / key_heads`` channels
    by their own mean square; the delta rules' ``gate_scale``,
    ``beta_scale`` and ``gate_rank`` mean nothing there and are refused."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    gate_scale: float = 2.0
    norm_eps: float = 1e-6
    decay: str = "head"
    beta_scale: float = 1.0
    gate_rank: int = 0

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.inner

    @property
    def inner(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def state_shape(self) -> tuple:
        """A sequence's recurrent state in one layer, as the pool holds it
        (float32): ``[value heads, key_dim, value_dim]``, or a Mamba-2
        layer's tiles of heads side by side (``ops.mamba2.state_shape``:
        as many values, no lane left empty)."""
        if self.decay == "mamba2":
            from ..ops.mamba2 import state_shape

            return state_shape(self.value_heads, self.value_dim, self.key_dim)
        return (self.value_heads, self.key_dim, self.value_dim)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 64
    intermediate_size: int = 1408
    # 0: no positional encoding at all (NoPE): ``_rope`` hands back what
    # it was given.
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    page_size: int = 16
    dtype: Any = jnp.bfloat16
    # Hybrid attention: layers listed in ``swa_layers`` use a sliding
    # window of ``sliding_window`` keys (Mistral/Gemma-style); the rest are
    # full attention. Both unset → pure full attention.
    sliding_window: Any = None  # Optional[int]
    swa_layers: tuple = ()
    # Pages of a two-pool model's window pool, where the engine's own
    # configuration gives none (``EngineConfig.num_swa_pages``): a
    # deployment's file sizes the pool with the model, as ``state_slots``
    # sizes a state pool. 0: as many as the global pool.
    window_pages: int = 0
    # Per-head RMSNorm on Q and K before RoPE (Qwen3-style QK-norm).
    # With GQA this makes the family cover Qwen3; False = plain Llama.
    qk_norm: bool = False
    # Mixture-of-experts MLP (Mixtral-style): 0 → dense. Experts shard over
    # the ``ep`` mesh axis.
    num_experts: int = 0
    num_experts_per_token: int = 2
    # Expert compute: "capacity" = GShard-style top-k dispatch into fixed
    # per-expert buffers of ceil(T·k/E · capacity_factor) tokens — compute
    # scales with tokens, not num_experts; overflow tokens lose their MoE
    # contribution (residual passes through). "dense" = every expert over
    # every token with a one-hot mix (exact, O(E) compute; useful as the
    # reference formulation and for tiny models). "grouped" (the
    # deepseek_v3 router only) = exact too: the tokens x k assignments that
    # fall to the experts held are grouped by expert and run through one
    # grouped matmul, so the work follows the assignments, not the experts.
    moe_dispatch: str = "capacity"
    moe_capacity_factor: float = 2.0
    # DeepSeek-style MoE extensions (all () /0 for the classic Mixtral
    # family): ``moe_layers`` lists the MoE layer indices (empty = every
    # layer when num_experts > 0 — dense-first_k layouts list the rest);
    # ``n_shared_experts``/``moe_intermediate_size`` size the always-on
    # shared expert and the routed experts' inner dim; ``moe_router`` =
    # ("deepseek_v3", n_group, topk_group, norm_topk_prob, 
    # routed_scaling_factor) selects the sigmoid scoring +
    # bias-corrected group-limited top-k router (weights from unbiased
    # sigmoid scores; the e_score_correction bias is a parameter,
    # ``router_bias``).
    moe_layers: tuple = ()
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    moe_router: tuple = ()
    # One chip's share of an expert layer (deepseek_v3 router):
    # ``(first, count)`` = this layer holds experts ``[first, first +
    # count)`` of ``num_experts``, which stays the router's width. It
    # routes over all of them, computes its own experts' terms (weights
    # normalised over all chosen experts, held or not) and adds the shared
    # expert; what the absent experts would add is left out. () = all.
    experts_held: tuple = ()
    # Multi-head latent attention (DeepSeek-V2/V3): KV is cached as one
    # per-token latent of ``kv_lora_rank`` dims plus a decoupled-RoPE key
    # of ``qk_rope_head_dim`` dims SHARED across heads — ~an order of
    # magnitude less KV memory/bandwidth than GQA, which is the TPU-first
    # reason to run MLA in its absorbed form (see _forward_impl_grouped):
    # attention becomes multi-query over the latent itself (kv_heads=1,
    # head_dim=rank+rope), so the paged cache, offload, and event
    # machinery apply unchanged with the latent as the block payload.
    # 0 → standard attention. Events tag blocks ``mla_attention``
    # (reference events.go:34 KVCacheSpecKindMlaAttention).
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    # DeepSeek q-LoRA: > 0 → ``init_params`` makes the query's down
    # projection ``w_dq`` with its norm (and the latent's norm,
    # kv_a_layernorm, which every q-LoRA DeepSeek model has). The forward
    # reads the tree, not this field: a checkpoint decides by its tensors.
    q_lora_rank: int = 0
    # Learned sparse attention (DeepSeek-V3.2's DSA; needs MLA with q-LoRA):
    # every page holds a second stream, the indexer's key of
    # ``index_head_dim`` values a token (the pool's V stack, which plain
    # MLA leaves empty); a query scores its row's keys with
    # ``index_n_heads`` light heads and attends the ``index_topk`` best
    # (``ops.sparse_index``). 0 → every key is attended.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Standard deviation ``init_params`` draws the embedding at (random
    # weights only; a checkpoint brings its own). Every other matrix is
    # drawn at 0.02. At 0.02 a first layer's input is smaller than what its
    # attention adds, an average of random values over the keys attended;
    # a configuration whose attention selects keys says here how large the
    # residual it selects into is (its file's ``assumed``).
    embed_init_scale: float = 0.02
    # Zero-padding appended to the MLA latent cache payload so its width
    # (rank + rope + pad) hits the Mosaic 128-lane alignment the Pallas
    # kernels need on real TPU — DeepSeek-V2 shapes set 64 (512+64+64=640).
    # The pad is part of the cache layout everywhere (pool, offload files,
    # fingerprints), so padded and unpadded engines never share a store;
    # attention math is invariant to it up to fp rounding of the scale
    # factor (zero key dims score zero, value reads slice [:rank]).
    latent_pad: int = 0
    # How MLA flash-decode feeds the shared latent to its two matmuls:
    # "copy" (default) DMAs each page once and mirrors it VMEM->VMEM so
    # score and output matmuls get independent buffers; "reuse" aliases
    # them (half the VMEM, but measured 2x slower at b8/ctx4k on v5e —
    # ROADMAP D3). Pallas decode path only.
    mla_decode_stream: str = "copy"
    # Fused-projection column layout (serving-time, set by the engine —
    # not a checkpoint property; save canonicalizes it back to 1). 1 =
    # canonical [q|k|v] / [gate|up] column order. t > 1 = per-rank
    # interleaved order [q_0|k_0|v_0 | q_1|k_1|v_1 | ...] where part_i
    # is rank i's contiguous column slice: a uniform tp split of the
    # fused axis then hands every shard exactly its own fused block, so
    # fused projections compose with Megatron column sharding (the
    # canonical order cannot — uniform chunks straddle the q/k/v
    # boundaries). The forward's split sites consult this.
    fused_interleave: int = 1
    # RoPE scaling: () = plain RoPE; ("llama3", factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings) — Llama-3.1's
    # frequency-band NTK scheme; or ("yarn", factor, beta_fast, beta_slow,
    # original_max, attention_factor) — NTK-by-parts with cos/sin scaling
    # (see _rope). Tuples so the frozen config stays hashable for jit
    # static args.
    rope_scaling: tuple = ()
    # The window layers' rule where it differs from the full layers' (a
    # model that gives ``rope_parameters`` by layer kind): of
    # ``rope_scaling``'s form, ``("default",)`` for plain RoPE, () = the
    # same as ``rope_scaling``. The shared body takes a layer's rule from
    # ``layer_rope``.
    swa_rope_scaling: tuple = ()
    # DeepSeek yarn couples mscale into the ATTENTION SCALE (in-tree
    # transformers: scaling = qk_head_dim^-0.5 * mscale(factor,
    # mscale_all_dim)^2) on top of the generic cos/sin factor; this
    # multiplier carries that term. 1.0 everywhere else.
    softmax_scale_mult: float = 1.0
    # Attention sinks (StreamingLLM): with a sliding window, the first
    # ``attention_sinks`` positions stay attendable past the window — the
    # reference's ``sink_full_attention`` spec kind (events.go:40).
    # Supported for uniform-SWA models (every layer in swa_layers); the
    # hybrid two-pool reclamation would free sink blocks.
    attention_sinks: int = 0
    # Layer kinds. ``linear_layers`` lists the layers whose mixer is a
    # Gated DeltaNet of the sizes in ``linear`` (a ``LinearAttention``);
    # every other layer attends (``layer_kind``). A linear layer keeps no
    # pages: the page pools hold ``page_layers`` only, and a sequence's
    # states live in a pool of ``state_slots`` slots beside them
    # (``init_state_pool``), snapshotted at block boundaries so that a
    # prefix hit finds the state its pages end on: a prefill's checkpoint
    # trails it by under ``state_checkpoint_tokens`` (``_plan_snapshots``).
    linear_layers: tuple = ()
    linear: Any = None  # Optional[LinearAttention]
    state_slots: int = 0
    state_checkpoint_tokens: int = 0
    # The block's form (GigaChat3.5: ``norm_type`` zero-centred,
    # ``layernorm_type`` pre_post, ``gated_attention``, ``swiglu_limit``):
    # every norm scales by ``norm_offset + weight``; with ``post_norms`` a
    # sub-layer's output is normed too before it joins the residual; the
    # attention heads' outputs are gated by ``sigmoid(x W_g)`` ahead of
    # ``wo``; a SwiGLU's gate is capped at ``swiglu_limit`` before its SiLU
    # and its up branch clipped to +-that (0 = neither).
    norm_offset: float = 0.0
    post_norms: bool = False
    attn_output_gate: bool = False
    swiglu_limit: float = 0.0
    # Standard deviation ``init_params`` draws a feed-forward's gate and up
    # matrices at (random weights only): a configuration whose SwiGLU
    # clamps says here how often its pre-activations pass the limit.
    mlp_init_scale: float = 0.02
    # Standard deviation ``init_params`` draws a chip's share of a router's
    # ``e_score_correction_bias`` at (random weights only). A trained bias
    # balances the experts' load; a random one unbalances it, so the share
    # of tokens the experts held receive (and the grouped matmuls' time)
    # differs from seed to seed by about six times this value over the
    # score gaps it competes with (at 0.02: +-25% around ``held /
    # experts``; at 0.002: the sampling noise of a chunk).
    router_bias_init_scale: float = 0.02
    # Multi-token prediction (DeepSeek-V3's form): 1 = the model brings one
    # prediction module, a block of its own kind behind the last layer that
    # drafts the token after next from the main model's hidden state and
    # the next token (``draft_logits`` below), and a decode step verifies
    # the draft: two positions a row, one or two tokens out
    # (``drafting_step_program``). The module's latents are one more layer
    # of every page: layer 0 of the pool, the main layers behind it
    # (``page_layers``). 0 = none, and nothing of this is in any program.
    num_nextn_predict_layers: int = 0
    # Granite's four scalars (1 / 0 for every other model, whose programs
    # hold nothing of them): the embedding's rows times
    # ``embedding_multiplier``; what each sub-layer adds to the residual
    # times ``residual_multiplier``; attention's scores times
    # ``attention_multiplier`` in place of ``head_dim ** -0.5`` (0: that);
    # the logits over ``logits_scaling``. The residual's is applied where a
    # sub-layer's output joins it (``_sublayer_out``); the other three reach
    # the shared body as a view of the parameters (``multiplied``), which
    # ``with_state`` hands it.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # Layers of two mixers (Falcon-H1's block): a layer listed here keeps a
    # state AND pages. From one normed input ``u`` a Mamba-2 mixer and GQA
    # run side by side and ``ssm_out_multiplier Mamba2(u) +
    # attention_out_multiplier Attn(u)`` joins the residual; then the MLP.
    # Such a layer is one of ``linear_layers`` (the shared body runs it as
    # one, handed the pages with the state: ``_parallel_block``,
    # ``with_pages_in_state``) and one of ``page_layers``. () for every
    # other model, whose programs hold nothing of this.
    parallel_layers: tuple = ()
    # Falcon-H1's further scalars (1 / () for every other model), each
    # applied in float32 to the product that ``multiplied`` names:
    # ``ssm_multipliers`` = ``(z, x, B, C, dt)`` over the columns of a
    # Mamba-2 mixer's input projection, on top of ``ssm_in_multiplier``
    # on its input; each mixer's output projection times its own scalar;
    # ``mlp_multipliers`` = (the gate's pre-activation, the MLP's output).
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = ()
    ssm_out_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    mlp_multipliers: tuple = ()
    # Standard deviation ``init_params`` draws the input projections of a
    # layer of two mixers at (``w_in``, ``wq``, ``wk``, ``wv``; random
    # weights only): under multipliers that shrink what they project, the
    # size at which a state is read back and attention chooses among keys.
    mixer_init_scale: float = 0.02

    def __post_init__(self):
        if self.num_nextn_predict_layers:
            if self.num_nextn_predict_layers != 1:
                raise NotImplementedError(
                    "more than one prediction module (a step that drafts "
                    "more than one position) is not built")
            if not self.is_mla or self.linear_layers or self.is_dsa:
                raise NotImplementedError(
                    "a prediction module is served over latent attention "
                    "alone (the decode kernel verifies two positions over "
                    "one shared latent head): beside key/value pages, a "
                    "state pool (states rolled back by row) or an indexer "
                    "it is not built")
        if self.linear_layers:
            if self.linear is None:
                raise ValueError("linear_layers need their sizes (linear)")
            # The layers that attend keep pages of a latent (absorbed MLA)
            # or of GQA keys and values; the linear layers' decay is a
            # scalar a head or a value a key channel: the hybrids of
            # states and pages that are built.
            if self.linear.decay not in ("head", "channel", "mamba2"):
                raise ValueError(
                    f"linear.decay {self.linear.decay!r}: a scalar a head "
                    f"(\"head\") or a value a key channel (\"channel\"), "
                    f"both delta rules, or a state-space layer "
                    f"(\"mamba2\")")
            if self.linear.decay == "mamba2":
                for name, plain in (("gate_scale", 1.0), ("beta_scale", 1.0),
                                    ("gate_rank", 0)):
                    if getattr(self.linear, name) != plain:
                        raise ValueError(
                            f"linear.{name} {getattr(self.linear, name)!r} "
                            f"is a delta rule's: a mamba2 layer has no such "
                            f"term (leave it at {plain!r})")
                if self.linear.state_shape[0] % self.linear.key_heads:
                    raise NotImplementedError(
                        f"linear.key_heads {self.linear.key_heads} "
                        f"(mamba_n_groups) over {self.linear.state_shape[0]} "
                        f"state tiles of heads side by side: a tile would "
                        f"hold heads of two groups of B and C")
                if self.is_mla or self.norm_offset or self.post_norms:
                    raise NotImplementedError(
                        "a mamba2 layer is built beside GQA pages under "
                        "plain pre-norm")
            if self.linear.decay == "channel" and (
                    self.linear.key_heads != self.linear.value_heads
                    or self.linear.gate_rank <= 0):
                raise ValueError(
                    "a channel-wise decay needs as many key heads as value "
                    "heads and the width of its low-rank projections "
                    "(gate_rank)")
            if not all(0 <= i < self.num_layers for i in self.linear_layers):
                raise ValueError("linear_layers indices out of range")
            if (len(set(self.linear_layers)) == self.num_layers
                    and not self.parallel_layers):
                raise ValueError(
                    "a model of linear layers alone has no pages for a "
                    "snapshot to stand on")
            if (self.is_dsa or self.sliding_window is not None
                    or self.swa_layers):
                raise ValueError(
                    "linear layers beside an indexer or a window are not "
                    "built")
            if self.linear.value_heads % self.linear.key_heads:
                raise ValueError("value heads must divide by key heads")
            if self.state_slots < 2:
                raise ValueError(
                    "a model with linear layers needs state_slots >= 2 (a "
                    "working slot and a snapshot)")
        if self.parallel_layers:
            if (self.linear is None or self.linear.decay != "mamba2"
                    or self.is_mla or self.num_experts):
                raise NotImplementedError(
                    "a layer of two mixers is Mamba-2 beside GQA under a "
                    "dense MLP: with a delta rule, latent pages or experts "
                    "it is not built")
            if (tuple(self.parallel_layers) != tuple(range(self.num_layers))
                    or tuple(self.linear_layers) != self.parallel_layers):
                raise NotImplementedError(
                    "parallel_layers must name every layer, in order, and "
                    "linear_layers the same: a layer of two mixers beside a "
                    "layer of one (whose pages the shared body would write "
                    "apart from theirs) is not built")
        elif (self.ssm_out_multiplier != 1.0
                or self.attention_out_multiplier != 1.0):
            raise ValueError(
                "ssm_out_multiplier and attention_out_multiplier weigh the "
                "two mixers of a parallel layer: the model has none")
        if self.ssm_multipliers and len(self.ssm_multipliers) != 5:
            raise ValueError("ssm_multipliers are five: (z, x, B, C, dt)")
        if self.mlp_multipliers and len(self.mlp_multipliers) != 2:
            raise ValueError("mlp_multipliers are two: (gate, output)")
        if self.num_experts > 0 and self.num_experts_per_token > self.num_experts:
            raise ValueError(
                f"num_experts_per_token ({self.num_experts_per_token}) exceeds "
                f"num_experts ({self.num_experts})"
            )
        if self.kv_lora_rank > 0:
            if self.qk_rope_head_dim <= 0 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "MLA needs an even qk_rope_head_dim > 0 (decoupled-RoPE "
                    f"key dims), got {self.qk_rope_head_dim}")
            if self.sliding_window is not None or self.swa_layers:
                raise ValueError(
                    "sliding_window_mla is not implemented: MLA configs "
                    "cannot set sliding_window/swa_layers")
            if self.qk_norm:
                raise ValueError("qk_norm is not defined for MLA configs")
        if self.moe_router:
            kind = self.moe_router[0]
            if kind == "deepseek_v3" and len(self.moe_router) == 5:
                if self.moe_dispatch not in ("dense", "grouped"):
                    raise ValueError(
                        "the deepseek_v3 router is implemented for the "
                        "exact dispatches only ('grouped', 'dense')")
                n_group = self.moe_router[1]
                if n_group < 1 or self.num_experts % n_group != 0:
                    raise ValueError(
                        "num_experts must divide by n_group >= 1")
                if self.num_experts // n_group < 2:
                    raise ValueError(
                        "deepseek_v3 group scoring sums each group's "
                        "top-2 corrected scores: groups need >= 2 experts")
            elif kind == "softmax_topk" and len(self.moe_router) == 2:
                pass  # Qwen3-MoE: classic router, norm_topk_prob in [1]
            else:
                raise ValueError(
                    "moe_router must be ('deepseek_v3', n_group, "
                    "topk_group, norm_topk_prob, factor) or "
                    f"('softmax_topk', norm_topk_prob); got "
                    f"{self.moe_router!r}")
        if self.moe_layers and not all(
                0 <= i < self.num_layers for i in self.moe_layers):
            raise ValueError("moe_layers indices out of range")
        if self.moe_dispatch == "grouped" and not self.routes_by_share:
            raise ValueError(
                "moe_dispatch='grouped' serves the deepseek_v3 router and "
                "('softmax_topk', 1), the softmax over the chosen logits")
        if self.experts_held:
            if not self.routes_by_share:
                raise ValueError(
                    "experts_held (a chip's share of an expert layer) is "
                    "implemented for the deepseek_v3 router and for "
                    "('softmax_topk', 1) under moe_dispatch='grouped'")
            first, count = self.experts_held
            if count < 1 or first < 0 or first + count > self.num_experts:
                raise ValueError(
                    f"experts_held {self.experts_held!r} is not a range of "
                    f"the router's {self.num_experts} experts")
        if self.index_topk:
            if not (self.is_mla and self.q_lora_rank > 0):
                raise ValueError(
                    "the indexer's queries come from the q latent: "
                    "index_topk needs MLA with q_lora_rank > 0")
            if (self.index_n_heads < 1
                    or self.index_head_dim < self.qk_rope_head_dim):
                raise ValueError(
                    "index_topk needs index_n_heads >= 1 and "
                    "index_head_dim >= qk_rope_head_dim (its rope dims)")
        if self.q_lora_rank and not self.is_mla:
            raise ValueError("q_lora_rank is an MLA knob")
        for rule in (self.rope_scaling, self.swa_rope_scaling):
            ok = not rule or (rule[0] == "llama3" and len(rule) == 5) or (
                rule[0] == "yarn" and len(rule) == 6) or (
                rule is self.swa_rope_scaling and rule == ("default",))
            if not ok:
                raise ValueError(
                    "rope_scaling must be ('llama3', factor, low_freq_factor,"
                    " high_freq_factor, original_max) or ('yarn', factor, "
                    "beta_fast, beta_slow, original_max, attention_factor), "
                    "swa_rope_scaling one of those or ('default',); "
                    f"got {rule!r}")
        if self.swa_rope_scaling and (self.is_mla or not self.swa_layers):
            raise ValueError(
                "swa_rope_scaling is the window layers' rule of a GQA model "
                "with swa_layers")
        if self.softmax_scale_mult != 1.0 and not self.is_mla:
            raise ValueError(
                "softmax_scale_mult is a DeepSeek-yarn (MLA) knob")
        if self.mla_decode_stream not in ("copy", "reuse"):
            raise ValueError(
                "mla_decode_stream must be 'copy' or 'reuse', got "
                f"{self.mla_decode_stream!r}")
        if self.mla_decode_stream != "copy" and not self.is_mla:
            raise ValueError("mla_decode_stream is an MLA knob")
        if self.latent_pad:
            if not self.is_mla:
                raise ValueError("latent_pad only applies to MLA configs")
            if self.latent_pad < 0:
                raise ValueError("latent_pad must be >= 0")
        if self.fused_interleave < 1:
            raise ValueError("fused_interleave must be >= 1")
        if self.fused_interleave > 1 and self.is_mla:
            # The MLA fused block mixes head-sharded (wq/w_dq) and
            # replicated (w_dkv/w_kr) columns — no uniform interleave
            # makes that shardable; MLA serves unfused under tp.
            raise ValueError(
                "fused_interleave > 1 is not supported for MLA configs")
        if self.has_multipliers:
            if not self.linear_layers:
                raise NotImplementedError(
                    "embedding_multiplier, attention_multiplier and "
                    "logits_scaling reach the programs through with_state: "
                    "a model without linear layers is not served with them")
            if self.is_mla or self.qk_norm or self.fused_interleave != 1:
                raise NotImplementedError(
                    "attention_multiplier scales plain GQA queries: with "
                    "latent attention, a norm on q or an interleaved fused "
                    "layout it is not built")
        if self.attention_sinks:
            if self.sliding_window is None:
                raise ValueError("attention_sinks requires sliding_window")
            if self.is_hybrid:
                raise ValueError(
                    "attention sinks need a uniform-SWA model "
                    "(sink_full_attention); hybrid layouts would reclaim "
                    "sink blocks from the window-bounded SWA pool")

    def layer_window(self, layer_idx: int):
        if self.sliding_window is not None and layer_idx in self.swa_layers:
            return self.sliding_window
        return None

    @property
    def is_hybrid(self) -> bool:
        """Mixed full-attention and SWA layers → two KV-cache groups with
        separate page pools (vLLM's hybrid memory allocator model,
        reference ``hma.go:32-66``)."""
        if self.sliding_window is None or not self.swa_layers:
            return False
        swa = set(self.swa_layers) & set(range(self.num_layers))
        return bool(swa) and swa != set(range(self.num_layers))

    def group_layers(self, group_idx: int) -> tuple:
        """Layer indices of a cache group: group 0 = full attention,
        group 1 = sliding window (hybrid models only)."""
        swa = set(self.swa_layers) if self.sliding_window is not None else set()
        if group_idx == 0:
            return tuple(li for li in range(self.num_layers) if li not in swa)
        return tuple(li for li in range(self.num_layers) if li in swa)

    def layer_group(self, layer_idx: int) -> int:
        return 1 if (self.is_hybrid and layer_idx in self.swa_layers) else 0

    def layer_rope(self, layer_idx: int) -> tuple:
        """The layer's rotary rule, by its kind: ``swa_rope_scaling`` in a
        window layer of a model that has one, else ``rope_scaling``."""
        rule = self.rope_scaling
        if self.swa_rope_scaling and self.layer_window(layer_idx):
            rule = self.swa_rope_scaling
        return () if rule == ("default",) else rule

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def layer_kind(self, layer_idx: int) -> str:
        """``"linear"`` (a state a sequence) or ``"attention"`` (pages). A
        layer of two mixers (``parallel_layers``) keeps both and is run as
        a linear one."""
        return "linear" if layer_idx in self.linear_layers else "attention"

    @property
    def page_layers(self) -> tuple:
        """The layers that keep pages, in order: a page pool's layer axis.
        A prediction module's layer is ``-1`` and comes first: seen as a
        model of one layer (``_module_view``) its latents are layer 0."""
        return (-1,) * self.num_nextn_predict_layers + tuple(
            i for i in range(self.num_layers)
            if i not in self.linear_layers or i in self.parallel_layers)

    @property
    def is_dsa(self) -> bool:
        """Learned sparse attention: pages hold the indexer's key stream
        beside the latent, and a query attends its ``index_topk`` best."""
        return self.index_topk > 0

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def routes_by_share(self) -> bool:
        """A router whose layer a chip may hold a share of
        (``experts_held``) and serve by the exact grouped dispatch:
        DeepSeek-V3's, always; the softmax over the chosen logits
        (``("softmax_topk", 1)``) where ``moe_dispatch`` is "grouped"."""
        if not self.moe_router:
            return False
        return self.moe_router[0] == "deepseek_v3" or (
            tuple(self.moe_router) == ("softmax_topk", 1)
            and self.moe_dispatch == "grouped")

    @property
    def has_multipliers(self) -> bool:
        """Whether ``multiplied`` changes anything of the parameters."""
        return (self.embedding_multiplier != 1.0 or self.logits_scaling != 1.0
                or bool(self.attention_multiplier)
                or bool(self.parallel_layers) or bool(self.ssm_multipliers)
                or self.ssm_in_multiplier != 1.0
                or bool(self.mlp_multipliers))

    @property
    def step_counters(self) -> tuple:
        """What a step program of this model counts on the device and
        hands back behind its sampled tokens (``step_program``): of a
        routed model, the assignments (token x chosen expert, real tokens
        only) that fell to the experts held, and the experts they touched,
        both summed over the routed layers."""
        routed = self.num_experts > 0 and self.routes_by_share
        return ("assignments_held", "experts_touched") if routed else ()

    @property
    def kv_cache_heads(self) -> int:
        """Head count of the paged cache layout (MLA: the latent is one
        shared 'head' — multi-query over the compressed KV)."""
        return 1 if self.is_mla else self.num_kv_heads

    @property
    def kv_cache_head_dim(self) -> int:
        """Per-token width of the paged cache payload (MLA: latent rank +
        decoupled-RoPE key + alignment pad; offload specs must use this,
        not head_dim)."""
        if self.is_mla:
            return self.kv_lora_rank + self.qk_rope_head_dim + self.latent_pad
        return self.head_dim

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Test-sized config (fast CPU compile)."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
        )

    @classmethod
    def qwen3_tiny(cls) -> "LlamaConfig":
        """Test-sized Qwen3-family config (GQA + QK-norm — the
        architecture of the reference's headline benchmark model,
        ``benchmarking/73-capacity`` Qwen3-32B)."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
            qk_norm=True,
        )

    @classmethod
    def gemma_tiny(cls) -> "LlamaConfig":
        """Test-sized Gemma-2/3-style hybrid config: sliding-window and
        full-attention layers interleaved 1:1 — the layout that drives
        the two-group HMA path (separate window-bounded SWA page pool,
        group-tagged events; reference ``hma.go:32-66`` consumer side)."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
            sliding_window=8, swa_layers=(0, 2),
        )

    @classmethod
    def sink_tiny(cls) -> "LlamaConfig":
        """Test-sized StreamingLLM-style config: every layer SWA with
        attention sinks — the ``sink_full_attention`` spec kind."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
            sliding_window=8, swa_layers=(0, 1), attention_sinks=4,
        )

    @classmethod
    def deepseek_tiny(cls) -> "LlamaConfig":
        """Test-sized DeepSeek-family config (MLA: latent KV cache with
        decoupled-RoPE keys, served in absorbed form). Cache payload is
        16+8=24 dims/token vs GQA-tiny's 2×2×16=64 — the memory ratio is
        the point of the family."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=4, head_dim=16, intermediate_size=128, page_size=4,
            kv_lora_rank=16, qk_rope_head_dim=8,
        )

    @classmethod
    def mixtral_tiny(cls) -> "LlamaConfig":
        """Test-sized Mixtral-style MoE config (top-2 of 4 experts,
        GShard capacity dispatch)."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
            num_experts=4, num_experts_per_token=2,
        )


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize parameters (truncated-normal projections, ones norms).

    Jitted, one program per layer KIND rather than one for the model: the
    eager form dispatches one device op per weight, and a single program
    unrolled over every layer compiles slowly at real depth for weights
    that differ only in their key (PERF.md, PR 22). Dense and MoE layers
    each compile once and run per layer.
    """
    keys = jax.random.split(key, 2 + cfg.num_layers)
    layers = [
        _init_layer_jit(
            keys[2 + i], cfg,
            cfg.num_experts > 0 and (not cfg.moe_layers
                                     or i in cfg.moe_layers),
            cfg.layer_kind(i) == "linear")
        for i in range(cfg.num_layers)
    ]
    return {"layers": layers, **_init_top_jit(keys[0], keys[1], cfg),
            **_init_module(key, cfg)}


def _init_module(key: jax.Array, cfg: "LlamaConfig") -> Params:
    """``{"mtp": ...}``, the prediction module's own parameters, of a model
    that brings one (else nothing): the projection ``w_eh`` of ``[embedding
    ; hidden state]`` (each behind a norm of its own), one block of the
    model's kind (an expert layer where the model has experts) and the norm
    ahead of the head. The embedding and the head are the model's."""
    if not cfg.num_nextn_predict_layers:
        return {}
    h = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, 1), 2)
    return {"mtp": {
        "enorm": _norm_init(ks[0], h, cfg),
        "hnorm": _norm_init(ks[1], h, cfg),
        "w_eh": _dense_init(ks[0], (2 * h, h), cfg.dtype),
        "layer": _init_layer_jit(ks[1], cfg, cfg.num_experts > 0),
        "final_norm": _norm_init(jax.random.fold_in(ks[1], 1), h, cfg),
    }}


def _dense_init(k, shape, dt, scale=0.02):
    return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
            * scale).astype(dt)


def _norm_init(k, n: int, cfg: "LlamaConfig"):
    """A norm's weight: ones, or for a zero-centred norm (``norm_offset``)
    a draw around zero, so that a forward which scaled by the weight alone,
    or by one plus twice it, would not pass for this one."""
    if not cfg.norm_offset:
        return jnp.ones((n,), jnp.float32)
    return _dense_init(jax.random.fold_in(k, n), (n,), jnp.float32, 0.1)


@partial(jax.jit, static_argnames=("cfg",))
def _init_top_jit(embed_key: jax.Array, head_key: jax.Array,
                  cfg: LlamaConfig) -> Params:
    h = cfg.hidden_size
    return {
        "embed": _dense_init(embed_key, (cfg.vocab_size, h), cfg.dtype,
                             cfg.embed_init_scale),
        "final_norm": _norm_init(head_key, h, cfg),
        "lm_head": _dense_init(head_key, (h, cfg.vocab_size), cfg.dtype),
    }


@partial(jax.jit, static_argnames=("cfg", "is_moe_layer", "linear"))
def _init_layer_jit(key: jax.Array, cfg: LlamaConfig,
                    is_moe_layer: bool, linear: bool = False) -> Params:
    dt = cfg.dtype
    h, hd = cfg.hidden_size, cfg.head_dim

    def dense(k, shape):
        return _dense_init(k, shape, dt)

    def ffn(k, shape):
        return _dense_init(k, shape, dt, cfg.mlp_init_scale)

    lk = jax.random.split(key, 10)
    layer = {
        "attn_norm": _norm_init(lk[3], h, cfg),
        "wo": dense(lk[3], ((cfg.linear.inner if linear
                             else cfg.num_heads * hd), h)),
        "mlp_norm": _norm_init(lk[4], h, cfg),
    }
    if cfg.post_norms:
        layer["attn_post_norm"] = _norm_init(lk[5], h, cfg)
        layer["mlp_post_norm"] = _norm_init(lk[6], h, cfg)
    if cfg.attn_output_gate and not linear:
        layer["w_og"] = dense(jax.random.fold_in(lk[3], 1),
                              (h, cfg.num_heads * hd))
    if linear and cfg.linear.decay == "mamba2":
        layer.update(_init_mamba2(lk[0], cfg))
        if cfg.parallel_layers:
            # A layer of two mixers: GQA's matrices beside the Mamba-2
            # mixer's, each mixer its own output projection.
            def mixer_in(k, shape):
                return _dense_init(k, shape, dt, cfg.mixer_init_scale)

            layer.update({
                "w_ssm_out": layer["wo"],
                "wq": mixer_in(lk[1], (h, cfg.num_heads * hd)),
                "wk": mixer_in(lk[2], (h, cfg.num_kv_heads * hd)),
                "wv": mixer_in(lk[8], (h, cfg.num_kv_heads * hd)),
                "wo": dense(lk[9], (cfg.num_heads * hd, h)),
            })
    elif linear:
        la = cfg.linear
        ck = jax.random.split(lk[0], 6)
        # The decay's parameters as the family initialises them: A in
        # [1, 16), a step dt log-uniform in [1e-3, 1e-1] through its
        # inverse softplus. With the token's own term (x W_a, about +-1.7)
        # the heads' memories then run from a few tokens to a thousand.
        # (A channel-wise decay draws its step a key channel, A a head.)
        channel = la.decay == "channel"
        step = jnp.exp(jax.random.uniform(
            ck[4], (la.key_heads * la.key_dim if channel
                    else la.value_heads,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        if channel:
            rk = jax.random.split(ck[1], 5)
            layer.update({
                "w_conv_in": dense(ck[0], (h, la.conv_channels)),
                "w_beta": dense(rk[0], (h, la.value_heads)),
                "w_f_down": dense(rk[1], (h, la.gate_rank)),
                "w_f_up": dense(rk[2], (la.gate_rank,
                                        la.key_heads * la.key_dim)),
                "w_g_down": dense(rk[3], (h, la.gate_rank)),
                "w_g_up": dense(rk[4], (la.gate_rank, la.inner)),
            })
        else:
            layer.update({
                "w_qkvz": dense(ck[0], (h, la.conv_channels + la.inner)),
                "w_ba": dense(ck[1], (h, 2 * la.value_heads)),
            })
        layer.update({
            "conv_w": _dense_init(ck[2], (la.conv_kernel, la.conv_channels),
                                  jnp.float32, 0.5),
            "A_log": jnp.log(jax.random.uniform(
                ck[3], (la.value_heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),
            "o_norm": _norm_init(ck[5], la.value_dim, cfg),
        })
    elif cfg.is_mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        qr = cfg.q_lora_rank
        layer.update({
            # q carries nope (head_dim) + decoupled-rope dims per head;
            # KV is down-projected to the shared latent, with per-head
            # up-projections absorbed into the attention at serve time.
            "wq": dense(lk[0], (qr or h, cfg.num_heads * (hd + dr))),
            "w_dkv": dense(lk[1], (h, r)),
            "w_kr": dense(lk[2], (h, dr)),
            "w_uk": dense(lk[8], (cfg.num_heads, r, hd)),
            "w_uv": dense(lk[9], (cfg.num_heads, r, hd)),
        })
        if qr:
            ik = jax.random.split(lk[0], 5)
            layer.update({
                "w_dq": dense(ik[1], (h, qr)),
                "q_latent_norm": _norm_init(ik[1], qr, cfg),
                "latent_norm": _norm_init(ik[1], r, cfg),
            })
        if cfg.is_dsa:
            # The indexer: per-head queries from the q latent, one key
            # (LayerNorm, weight and bias) and the heads' weights from
            # the layer's input.
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            layer.update({
                "w_iq": dense(ik[2], (qr, hi * di)),
                "w_ik": dense(ik[3], (h, di)),
                "w_iw": dense(ik[4], (h, hi)),
                "index_norm": jnp.ones((di,), jnp.float32),
                "index_norm_bias": jnp.zeros((di,), jnp.float32),
            })
    else:
        layer.update({
            "wq": dense(lk[0], (h, cfg.num_heads * hd)),
            "wk": dense(lk[1], (h, cfg.num_kv_heads * hd)),
            "wv": dense(lk[2], (h, cfg.num_kv_heads * hd)),
        })
    if cfg.qk_norm:
        layer["q_norm"] = jnp.ones((hd,), jnp.float32)
        layer["k_norm"] = jnp.ones((hd,), jnp.float32)
    if is_moe_layer:
        e = cfg.num_experts
        held = cfg.num_experts_held  # the router keeps its width
        inter = cfg.moe_intermediate_size or cfg.intermediate_size
        layer.update({
            "router": dense(lk[7], (h, e)),
            "w_gate": ffn(lk[4], (held, h, inter)),
            "w_up": ffn(lk[5], (held, h, inter)),
            "w_down": dense(lk[6], (held, inter, h)),
        })
        if (cfg.moe_router and cfg.moe_router[0] == "softmax_topk"
                and cfg.n_shared_experts):
            # An always-on MLP beside the experts, no bias on the choice.
            sh = inter * cfg.n_shared_experts
            skeys = jax.random.split(lk[7], 4)
            layer.update({
                "w_gate_sh": ffn(skeys[1], (h, sh)),
                "w_up_sh": ffn(skeys[2], (h, sh)),
                "w_down_sh": dense(skeys[3], (sh, h)),
            })
        if cfg.moe_router and cfg.moe_router[0] == "deepseek_v3":
            # deepseek_v3: bias + shared expert
            sh = inter * max(cfg.n_shared_experts, 1)
            skeys = jax.random.split(lk[7], 4)
            layer.update({
                # e_score_correction_bias: zero for a whole layer (what its
                # tests and the benchmark's routed fixture pin); a chip's
                # share draws it small and not zero, so that a forward
                # that left it out would send other tokens to the experts
                # held.
                "router_bias": (_dense_init(skeys[0], (e,), jnp.float32,
                                            cfg.router_bias_init_scale)
                                if cfg.experts_held
                                else jnp.zeros((e,), jnp.float32)),
                "w_gate_sh": ffn(skeys[1], (h, sh)),
                "w_up_sh": ffn(skeys[2], (h, sh)),
                "w_down_sh": dense(skeys[3], (sh, h)),
            })
    else:
        layer.update({
            "w_gate": ffn(lk[4], (h, cfg.intermediate_size)),
            "w_up": ffn(lk[5], (h, cfg.intermediate_size)),
            "w_down": dense(lk[6], (cfg.intermediate_size, h)),
        })
    return layer


def _init_mamba2(key: jax.Array, cfg: LlamaConfig) -> Params:
    """A Mamba-2 mixer's own parameters (``_mamba2``): ``w_in`` gives ``[z |
    x B C | dt]``; the conv's taps at 0.5 and its bias; the decay's
    parameters as the family initialises them (``A`` in [1, 16), a step
    log-uniform in [1e-3, 1e-1] through its inverse softplus: a head's
    memory runs from a few tokens to thousands); the skip ``D`` at one;
    the gated norm's weight over all inner channels. In a layer of two
    mixers ``w_in`` is drawn at ``cfg.mixer_init_scale``."""
    la = cfg.linear
    ck = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(
        ck[3], (la.value_heads,), jnp.float32, math.log(1e-3),
        math.log(1e-1)))
    return {
        "w_in": _dense_init(
            ck[0], (cfg.hidden_size,
                    la.inner + la.conv_channels + la.value_heads), cfg.dtype,
            cfg.mixer_init_scale if cfg.parallel_layers else 0.02),
        "conv_w": _dense_init(ck[1], (la.conv_kernel, la.conv_channels),
                              jnp.float32, 0.5),
        "conv_b": _dense_init(jax.random.fold_in(ck[1], 1),
                              (la.conv_channels,), jnp.float32),
        "A_log": jnp.log(jax.random.uniform(
            ck[2], (la.value_heads,), jnp.float32, 1.0, 16.0)),
        "dt_bias": jnp.log(jnp.expm1(step)),
        "D": jnp.ones((la.value_heads,), jnp.float32),
        "o_norm": _norm_init(ck[4], la.inner, cfg),
    }


def _interleave_concat(parts: list, t: int, axis: int = 1) -> jax.Array:
    """Concatenate projection blocks in per-rank interleaved column order.

    t == 1 reproduces the canonical order. For t > 1 every part's fused
    axis must divide by t (the engine only requests an interleave the tp
    validation already guarantees); rank i's slice of each part lands
    contiguously, so a uniform t-way split of the result gives rank i
    exactly ``[part0_i | part1_i | ...]`` — its local fused block."""
    if t == 1:
        return jnp.concatenate(parts, axis=axis)
    for p in parts:
        if p.shape[axis] % t:
            raise ValueError(
                f"fused_interleave={t} does not divide projection width "
                f"{p.shape[axis]}")
    chunks = []
    for i in range(t):
        for p in parts:
            n = p.shape[axis] // t
            chunks.append(
                jax.lax.slice_in_dim(p, i * n, (i + 1) * n, axis=axis))
    return jnp.concatenate(chunks, axis=axis)


def _deinterleave_split(w: jax.Array, widths: tuple, t: int,
                        axis: int = 1) -> list:
    """Inverse of :func:`_interleave_concat`: recover the canonical
    per-projection blocks from a (possibly interleaved) fused array."""
    if t == 1:
        outs, off = [], 0
        for n in widths:
            outs.append(jax.lax.slice_in_dim(w, off, off + n, axis=axis))
            off += n
        return outs
    blk = sum(widths) // t
    ranks = [jax.lax.slice_in_dim(w, i * blk, (i + 1) * blk, axis=axis)
             for i in range(t)]
    outs = []
    off = 0
    for n in widths:
        outs.append(jnp.concatenate(
            [jax.lax.slice_in_dim(r, off, off + n // t, axis=axis)
             for r in ranks], axis=axis))
        off += n // t
    return outs


def split_fused_out(y: jax.Array, widths: tuple, t: int) -> list:
    """Split a fused projection's OUTPUT activations back into the
    per-projection tensors, honoring the interleaved layout.

    For t == 1 these are the canonical static slices. For t > 1 the
    last dim is reshaped ``[t, blk]`` (a shard-boundary split under the
    Megatron column sharding, so GSPMD keeps it local), each part's
    per-rank columns sliced, and the rank axis merged back — rank-major
    order IS canonical order, since rank i's slice was the i-th
    contiguous chunk of the canonical projection."""
    if t == 1:
        outs, off = [], 0
        for n in widths:
            outs.append(y[..., off:off + n])
            off += n
        return outs
    blk = sum(widths) // t
    yb = y.reshape(*y.shape[:-1], t, blk)
    outs, off = [], 0
    for n in widths:
        part = yb[..., off:off + n // t]
        outs.append(part.reshape(*y.shape[:-1], n))
        off += n // t
    return outs


def fuse_params(params: Params, cfg: LlamaConfig) -> Params:
    """Fuse per-layer projections that share an input into wider matmuls.

    Serving-time transform (applied once at engine startup): one
    [h, Nq+Nk+Nv] product reads the activations once and replaces three
    back-to-back [h, N] products. The trade depends on the width:
    ``fuse_profitable`` holds the rule and the engine's auto default
    consults it.

    - ``wq/wk/wv`` (+ ``bq/bk/bv``) → ``w_qkv`` (+ ``b_qkv``)
    - MLA: ``wq|w_dq`` + ``w_dkv`` + ``w_kr`` → ``w_mla_in``
      (all consume post-norm attn input; q-LoRA keeps its separate
      ``wq`` over the normed q latent)
    - dense SwiGLU: ``w_gate/w_up`` → ``w_gate_up``
    - DeepSeek shared experts: ``w_gate_sh/w_up_sh`` → ``w_gate_up_sh``

    Originals are dropped (no weight memory doubling). The forward
    accepts both layouts. TP-sharded serving fuses in the per-rank
    INTERLEAVED column order (``cfg.fused_interleave`` = tp, set by the
    engine): the canonical column order cannot shard uniformly across
    tp (chunks would straddle the q/k/v and gate/up boundaries), but
    interleaving each rank's slices makes the uniform Megatron column
    split hand every shard exactly its local fused block. MLA keeps the
    canonical order only (``fused_interleave > 1`` is refused by the
    config: its fused block mixes head-sharded and replicated columns).
    """
    t = cfg.fused_interleave
    out = dict(params)
    fused_layers = []
    fused_any = False
    for layer in params["layers"]:
        lyr = dict(layer)
        if "wk" in lyr:  # standard / GQA attention
            lyr["w_qkv"] = _interleave_concat(
                [lyr.pop("wq"), lyr.pop("wk"), lyr.pop("wv")], t)
            if "bq" in lyr:
                lyr["b_qkv"] = _interleave_concat(
                    [lyr.pop("bq"), lyr.pop("bk"), lyr.pop("bv")], t,
                    axis=0)
            fused_any = True
        elif "w_dkv" in lyr:  # absorbed MLA (canonical order; t == 1)
            head_in = (lyr.pop("w_dq") if "w_dq" in lyr
                       else lyr.pop("wq"))
            lyr["w_mla_in"] = jnp.concatenate(
                [head_in, lyr.pop("w_dkv"), lyr.pop("w_kr")], axis=1)
            fused_any = True
        if "w_gate" in lyr and lyr["w_gate"].ndim == 2:  # dense SwiGLU
            lyr["w_gate_up"] = _interleave_concat(
                [lyr.pop("w_gate"), lyr.pop("w_up")], t)
            fused_any = True
        if "w_gate_sh" in lyr:
            lyr["w_gate_up_sh"] = _interleave_concat(
                [lyr.pop("w_gate_sh"), lyr.pop("w_up_sh")], t)
            fused_any = True
        fused_layers.append(lyr)
    out["layers"] = fused_layers
    if "mtp" in params:
        # The module's block, as a model of that one layer.
        out["mtp"] = {**params["mtp"], "layer": fuse_params(
            {"layers": [params["mtp"]["layer"]]}, cfg)["layers"][0]}
    if fused_any:
        # Record the interleave the tree was ACTUALLY fused with, so
        # unfuse_params can refuse a mismatched config instead of silently
        # de-interleaving into scrambled wq/wk/wv. A no-op call on an
        # already-fused tree keeps the original marker.
        out["fused_interleave"] = t
    return out


def maybe_fuse_params(params: Params, cfg: LlamaConfig) -> Params:
    """``fuse_params`` iff ``fuse_profitable(cfg)`` — the one place the
    profit gate composes with the transform, shared by the engine's
    auto default and whoever shares one tree between engines."""
    return fuse_params(params, cfg) if fuse_profitable(cfg) else params


def fuse_profitable(cfg: LlamaConfig, tp: int = 1) -> bool:
    """Whether ``fuse_params`` is expected to help this model on TPU.

    The rule: fuse from a per-shard hidden width of 4096 up. It puts
    ``mistral-7b-l16`` on the fused path and ``qwen3-1.7b`` on the
    unfused in every accepted cell of ``PERF_LEDGER.jsonl``; the ledger
    has no pair across the gate, and the July 2026 reading it was set
    from (a gain at 4096, a loss at 2048) has no record left. Engines
    with ``fuse_projections=None`` and ``maybe_fuse_params`` consult this.

    ``tp`` scales the gate to PER-SHARD widths: under Megatron column
    sharding each rank multiplies into 1/tp of the fused output columns,
    so a hidden-4096 model at tp=2 runs the narrow per-core products of a
    hidden-2048 one. The boundary therefore applies to
    ``hidden_size / tp``, not the full-model width.
    """
    return cfg.hidden_size // max(1, tp) >= 4096


def unfuse_params(params: Params, cfg: LlamaConfig) -> Params:
    """Inverse of :func:`fuse_params`: split fused projections back into
    the canonical per-projection layout. Checkpoints always store the
    canonical layout (portable across fused/unfused engines, TP sharding,
    and the trainer); a fused serving tree is unfused on save. No-op on
    an already-canonical tree.

    The interleave is read from the ``fused_interleave`` marker that
    :func:`fuse_params` stamped on the tree. A fused tree without the
    marker, or one whose marker disagrees with ``cfg.fused_interleave``,
    raises: de-interleaving with the wrong ``t`` would silently scramble
    ``wq/wk/wv`` column order (a checkpoint saved from such a tree is
    corrupt with no error anywhere downstream)."""
    out = dict(params)
    marker = out.pop("fused_interleave", None)
    if "mtp" in out:
        # The module's block, as a model of that one layer under the same
        # marker (a canonical block comes back as it is).
        out["mtp"] = {**out["mtp"], "layer": unfuse_params(
            {"layers": [out["mtp"]["layer"]], "fused_interleave": marker},
            cfg)["layers"][0]}
    fused_keys = ("w_qkv", "b_qkv", "w_mla_in", "w_gate_up", "w_gate_up_sh")
    if not any(k in lyr for lyr in params["layers"] for k in fused_keys):
        return out  # already canonical
    if marker is None:
        raise ValueError(
            "cannot unfuse: tree has fused projections but no "
            "fused_interleave marker (was it fused by fuse_params?)")
    t = int(marker)
    if t != cfg.fused_interleave:
        raise ValueError(
            f"fused_interleave mismatch: tree was fused with t={t} but "
            f"cfg.fused_interleave={cfg.fused_interleave}; unfusing with "
            "the wrong interleave would scramble the q/k/v column order")
    layers = []
    for layer in params["layers"]:
        lyr = dict(layer)
        if "w_qkv" in lyr:
            nq = cfg.num_heads * cfg.head_dim
            nk = cfg.num_kv_heads * cfg.head_dim
            w = lyr.pop("w_qkv")
            nv = w.shape[1] - nq - nk
            lyr["wq"], lyr["wk"], lyr["wv"] = _deinterleave_split(
                w, (nq, nk, nv), t)
            if "b_qkv" in lyr:
                b = lyr.pop("b_qkv")
                lyr["bq"], lyr["bk"], lyr["bv"] = _deinterleave_split(
                    b, (nq, nk, nv), t, axis=0)
        if "w_mla_in" in lyr:  # canonical order only (t == 1)
            r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            w = lyr.pop("w_mla_in")
            qc = w.shape[1] - r - dr
            head_key = "w_dq" if "q_latent_norm" in lyr else "wq"
            lyr[head_key] = w[:, :qc]
            lyr["w_dkv"] = w[:, qc:qc + r]
            lyr["w_kr"] = w[:, qc + r:]
        if "w_gate_up" in lyr:
            w = lyr.pop("w_gate_up")
            inter = w.shape[1] // 2
            lyr["w_gate"], lyr["w_up"] = _deinterleave_split(
                w, (inter, inter), t)
        if "w_gate_up_sh" in lyr:
            w = lyr.pop("w_gate_up_sh")
            sh = w.shape[1] // 2
            lyr["w_gate_sh"], lyr["w_up_sh"] = _deinterleave_split(
                w, (sh, sh), t)
        layers.append(lyr)
    out["layers"] = layers
    return out


def init_kv_cache(cfg: LlamaConfig, num_pages: int,
                  dtype=None) -> tuple[jax.Array, jax.Array]:
    """Allocate the paged K and V pools: ``[layers, pages, kvh, page, hd]``.

    MLA: the K pool holds the per-token latent (+rope key) as one shared
    head; the V pool is width-0 — attention reads values from the same
    latent, so a separate V cache would double the memory MLA exists to
    save. The zero-width array keeps every donation/offload seam shaped.
    A model with an indexer (``cfg.is_dsa``) keeps its key stream there,
    ``index_head_dim`` wide: allocated, written and donated with the
    latent under one page id.

    ``dtype`` overrides the pool element type (serving-time choice —
    ``float8_e4m3fn`` halves KV HBM traffic and capacity; e4m3's
    per-element exponent needs no scale arrays, so the cache layout and
    every scatter/gather/offload seam are unchanged). The compute path
    stays bf16: ``scatter_kv_pages`` casts on write, the attention
    backends upcast on read.
    """
    dtype = cfg.dtype if dtype is None else dtype
    shape = (len(cfg.page_layers), num_pages, cfg.kv_cache_heads,
             cfg.page_size, cfg.kv_cache_head_dim)
    v_width = cfg.kv_cache_head_dim
    if cfg.is_mla:
        # The second stack is the indexer's key stream where the model has
        # one: under the same page ids, so a page is both or neither.
        v_width = cfg.index_head_dim if cfg.is_dsa else 0
    return jnp.zeros(shape, dtype), jnp.zeros(shape[:-1] + (v_width,), dtype)


def init_state_pool(cfg: LlamaConfig) -> tuple[jax.Array, jax.Array]:
    """The pool of sequence states of a model with linear layers, beside
    its page pools: ``(recurrent [linear layers, slots + 1, value heads,
    key_dim, value_dim] float32`` (a Mamba-2 layer's tiles in the last
    three places: ``LinearAttention.state_shape``)``, conv [linear layers,
    slots + 1, conv_kernel - 1, conv channels]`` in the model's
    type``)``. A slot holds one
    sequence's state in every linear layer: a running row's, or a snapshot
    at a block boundary. Slot 0 is the spare one (as page 0 is): rows that
    decode nothing and snapshots nobody asked for are written there."""
    la, n = cfg.linear, len(cfg.linear_layers)
    return (jnp.zeros((n, cfg.state_slots + 1, *la.state_shape),
                      jnp.float32),
            jnp.zeros((n, cfg.state_slots + 1, la.conv_kernel - 1,
                       la.conv_channels), cfg.dtype))


def init_kv_cache_hybrid(
    cfg: LlamaConfig, num_pages: int, num_swa_pages: int, dtype=None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Allocate separate page pools for a hybrid model's two cache groups:
    ``(k0, v0, k1, v1)`` with group 0 = full-attention layers (num_pages)
    and group 1 = SWA layers (num_swa_pages — window-bounded, so typically
    much smaller; this is the memory win of hybrid attention).
    ``dtype`` as in ``init_kv_cache``."""
    if not cfg.is_hybrid:
        raise ValueError("init_kv_cache_hybrid needs a hybrid config")
    dtype = cfg.dtype if dtype is None else dtype

    def shape(group, pages):
        return (len(cfg.group_layers(group)), pages, cfg.num_kv_heads,
                cfg.page_size, cfg.head_dim)

    return (
        jnp.zeros(shape(0, num_pages), dtype),
        jnp.zeros(shape(0, num_pages), dtype),
        jnp.zeros(shape(1, num_swa_pages), dtype),
        jnp.zeros(shape(1, num_swa_pages), dtype),
    )


def _rms_norm(x: jax.Array, weight: jax.Array, eps: float,
              offset: float = 0.0) -> jax.Array:
    """``offset``: a zero-centred norm scales by ``offset + weight``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if offset:
        weight = weight + offset
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


def _swiglu(gate: jax.Array, up: jax.Array, limit: float) -> jax.Array:
    """``silu(gate) * up`` in float32; with a ``limit`` the gate is capped
    at it before its SiLU and the up branch clipped to +-it."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _moe_router(mlp_in: jax.Array, layer: dict, cfg: "LlamaConfig",
                aux_out: Any):
    """Shared routing: top-k expert choices + softmaxed weights, and the
    Switch-style load-balancing term ``E·Σ_e f_e·P_e`` appended to
    ``aux_out`` (training; None skips it)."""
    e = cfg.num_experts
    k = cfg.num_experts_per_token
    router_logits = (
        mlp_in @ layer["router"].astype(mlp_in.dtype)
    ).astype(jnp.float32)  # [b,s,E]
    top_w, top_idx = jax.lax.top_k(router_logits, k)  # [b,s,k]
    if (cfg.moe_router and cfg.moe_router[0] == "softmax_topk"
            and not cfg.moe_router[1]):
        # Qwen3-MoE with norm_topk_prob=False: weights are the top-k
        # entries of the FULL softmax, NOT renormalized (HF
        # Qwen3MoeSparseMoeBlock — "only diff with mixtral").
        weights = jnp.take_along_axis(
            jax.nn.softmax(router_logits, axis=-1), top_idx, axis=-1)
    else:
        weights = jax.nn.softmax(top_w, axis=-1)
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # [b,s,k,E]
    if aux_out is not None:
        probs = jax.nn.softmax(router_logits, axis=-1)  # [b,s,E]
        f = jnp.mean(jnp.sum(onehot, axis=2) / k, axis=(0, 1))  # [E]
        p = jnp.mean(probs, axis=(0, 1))  # [E]
        aux_out.append(e * jnp.sum(f * p))
    return top_idx, weights, onehot


def _moe_dense(mlp_in, layer, cfg, aux_out):
    """Reference formulation: every expert over every token, one-hot mix.
    Exact but O(num_experts) compute."""
    _top_idx, weights, onehot = _moe_router(mlp_in, layer, cfg, aux_out)
    # bf16 matmuls, f32 activation math (mirrors the dense branch).
    gate = jax.nn.silu(jnp.einsum(
        "bsh,ehi->bsei", mlp_in, layer["w_gate"]
    ).astype(jnp.float32))
    up = jnp.einsum("bsh,ehi->bsei", mlp_in, layer["w_up"]).astype(jnp.float32)
    expert_out = jnp.einsum(
        "bsei,eih->bseh", (gate * up).astype(mlp_in.dtype), layer["w_down"]
    ).astype(jnp.float32)
    mix = jnp.einsum("bsk,bske,bseh->bsh", weights, onehot, expert_out)
    return mix.astype(mlp_in.dtype)


def _moe_capacity(mlp_in, layer, cfg, aux_out, valid=None):
    """GShard/Switch-style capacity dispatch: tokens scatter into fixed
    per-expert buffers of C = ceil(T·k/E · capacity_factor) slots via
    one-hot einsums (static shapes, XLA/MXU-friendly), experts run on
    [E, C, H], results combine back weighted. Compute scales with
    T·k·capacity_factor — independent of num_experts — at the cost of
    dropping assignments past an expert's capacity (earlier tokens win;
    dropped assignments contribute nothing, the residual passes through).
    Experts (and their buffers) shard over the ``ep`` mesh axis.

    ``valid`` ([b, s] bool, optional): padded positions are excluded from
    routing so they can never consume capacity slots that real tokens
    need (attention masks them, the router would not).
    """
    batch, seq, hidden = mlp_in.shape
    e = cfg.num_experts
    k = cfg.num_experts_per_token
    t = batch * seq
    top_idx, weights, _onehot = _moe_router(mlp_in, layer, cfg, aux_out)

    capacity = max(1, math.ceil(t * k * cfg.moe_capacity_factor / e))
    x = mlp_in.reshape(t, hidden)
    # Assignment axis a = (token, choice), token-major: earlier tokens win
    # capacity slots.
    oh = jax.nn.one_hot(top_idx.reshape(t * k), e, dtype=jnp.int32)  # [A,E]
    if valid is not None:
        mask = valid.reshape(t).astype(jnp.int32)
        oh = oh * jnp.repeat(mask, k)[:, None]
    pos_a = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=1)      # [A]
    # one_hot zeroes out-of-range rows, so over-capacity assignments (and
    # masked tokens, whose oh row is zero) drop out of the dispatch.
    pos_oh = jax.nn.one_hot(pos_a, capacity, dtype=mlp_in.dtype)     # [A,C]
    oh_tk = oh.astype(mlp_in.dtype).reshape(t, k, e)
    pos_tk = pos_oh.reshape(t, k, capacity)
    # A token's k assignments land in distinct (expert, slot) cells, so
    # summing the choice axis gives a lossless [T,E,C] dispatch — no
    # k-times-repeated activations.
    disp = jnp.einsum("tke,tkc->tec", oh_tk, pos_tk)                 # [T,E,C]

    buf = jnp.einsum("tec,th->ech", disp, x)                         # [E,C,H]
    gate = jax.nn.silu(
        jnp.einsum("ech,ehi->eci", buf, layer["w_gate"]).astype(jnp.float32))
    up = jnp.einsum("ech,ehi->eci", buf, layer["w_up"]).astype(jnp.float32)
    expert_out = jnp.einsum(
        "eci,eih->ech", (gate * up).astype(mlp_in.dtype), layer["w_down"]
    ).astype(jnp.float32)
    combine = jnp.einsum(
        "tke,tkc,tk->tec", oh_tk.astype(jnp.float32),
        pos_tk.astype(jnp.float32), weights.reshape(t, k))
    y = jnp.einsum("tec,ech->th", combine, expert_out)               # [T,H]
    return y.reshape(batch, seq, hidden).astype(mlp_in.dtype)


def _deepseek_route(x, layer, cfg):
    """DeepseekV3TopkRouter over the router's whole width: ``(idx [T, k],
    w [T, k])``. Sigmoid scores; top-k SELECTION uses bias-corrected
    scores restricted to the best ``topk_group`` of ``n_group`` expert
    groups (group score = sum of its top-2 corrected scores); mix WEIGHTS
    are the unbiased sigmoid scores of the chosen experts, optionally
    renormalized over all of them, times the routed scaling factor."""
    _kind, n_group, topk_group, norm_flag, factor = cfg.moe_router
    e = layer["router"].shape[1]
    k = cfg.num_experts_per_token
    logits = x.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)  # [T, E]
    choice = scores + layer["router_bias"][None, :].astype(jnp.float32)
    group_scores = jax.lax.top_k(
        choice.reshape(-1, n_group, e // n_group), 2)[0].sum(-1)
    _, gidx = jax.lax.top_k(group_scores, topk_group)  # [T, topk_group]
    gmask = jnp.sum(jax.nn.one_hot(gidx, n_group), axis=1)  # [T, n_group]
    smask = jnp.repeat(gmask, e // n_group, axis=-1)  # [T, E]
    masked = jnp.where(smask > 0, choice, 0.0)
    _, idx = jax.lax.top_k(masked, k)  # [T, k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_flag:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * factor


def _experts_dense(x, layer, idx, w, first, limit=0.0):
    """Every expert held over every token, mixed by the router's weights:
    exact, O(experts held) work and a ``[T, E, I]`` intermediate. The form
    the grouped dispatch is tested against."""
    held = layer["w_gate"].shape[0]
    mix_w = jnp.einsum(
        "tk,tke->te", w,
        jax.nn.one_hot(idx - first, held, dtype=jnp.float32))
    gate = jnp.einsum(
        "th,ehi->tei", x, layer["w_gate"]).astype(jnp.float32)
    up = jnp.einsum("th,ehi->tei", x, layer["w_up"]).astype(jnp.float32)
    expert_out = jnp.einsum(
        "tei,eih->teh", _swiglu(gate, up, limit).astype(x.dtype),
        layer["w_down"]).astype(jnp.float32)
    return jnp.einsum("te,teh->th", mix_w, expert_out)


# Rows a grouped matmul's tile holds: the assignments are padded to a
# whole number of them.
_GMM_ROWS = 128
# What one grid step of ``gmm`` may hold in VMEM, of Mosaic's default 16 MiB
# of scoped VMEM (megablox passes no ``vmem_limit_bytes``), and the largest
# piece of an expert's matrix it moves.
_GMM_VMEM_BYTES = 12 << 20
_GMM_PIECE_BYTES = 3 << 20


def gmm_vmem_bytes(tm, tk, tn, itemsize):
    """What ``gmm`` keeps in VMEM under a tiling: two buffers each of the
    ``[tk, tn]`` weight piece, the ``[tm, tk]`` rows and the ``[tm, tn]``
    float32 output, and the float32 accumulator."""
    return 2 * tk * tn * itemsize + 2 * tm * tk * itemsize + 3 * tm * tn * 4


def gmm_tiling(m, k, n, itemsize):
    """``(tm, tk, tn)`` for ``[m, k]`` rows times experts of ``[k, n]``, from
    the shapes alone. ``gmm`` moves one ``[tk, tn]`` piece of one expert's
    matrix a grid step; the pieces tile the matrix in multiples of 128 lanes
    (an axis that is no multiple goes whole), weigh at most
    ``_GMM_PIECE_BYTES`` and fit ``_GMM_VMEM_BYTES`` with the rows and the
    output beside them. ``k`` goes whole where a piece of twice the rows'
    tile in lanes then fits: no accumulator pass, and a group that straddles
    two row tiles keeps its piece, for the rows read again once a tile of
    ``n``. Else, and among those, the largest piece, the wider in ``n`` of
    equals (a wider output meets the accumulator less often). What decided:
    ``hack/bench_gmm.py --sweep`` (PERF.md §6, PR 58)."""
    del m  # under 128 rows the MXU's time is the weights' own: no gain
    tm = _GMM_ROWS

    def tiles(axis):
        return [d for d in range(128, axis + 1, 128) if axis % d == 0] or [
            axis]

    fit = [(tk, tn) for tk in tiles(k) for tn in tiles(n)
           if tk * tn * itemsize <= _GMM_PIECE_BYTES
           and gmm_vmem_bytes(tm, tk, tn, itemsize) <= _GMM_VMEM_BYTES]
    tk, tn = max(fit or [(tiles(k)[0], tiles(n)[0])],
                 key=lambda p: (p[0] == k and p[1] >= 2 * tm,
                                p[0] * p[1], p[1]))
    return tm, tk, tn


def _grouped_matmul(lhs, rhs, group_sizes, kernel):
    """``lhs [M, K]`` times ``rhs [G, K, N]``, rows grouped by ``rhs``'s
    first axis: the first ``group_sizes[0]`` rows take ``rhs[0]`` and so
    on; rows past the groups come out as anything. ``kernel``: None → XLA's
    ``ragged_dot`` (the XLA programs), else ``{"interpret": bool}`` → the
    Pallas grouped matmul (megablox ``gmm``), which visits the tiles that
    hold rows of a group and reads the weights of the groups they touch:
    work and bytes follow the assignments, in pieces that ``gmm_tiling``
    sizes to the expert. On one v5e a layer's three matmuls take 0.48 ms
    at granite-4.0-h-small's widths with 36 experts held for a decode
    step of 5 tokens (90% of the touched experts' bytes; 0.76 ms in the
    256-512 KB pieces of the rule before) and 2.09 ms at DeepSeek-V3.2's
    with 16 held for a chunk of 512 (83%; 2.26): ``hack/bench_gmm.py``'s
    table, PERF.md §6, PR 58. Off the chip ``gmm`` would run
    interpreted."""
    if kernel is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    k, n = rhs.shape[1:]
    tiling = gmm_tiling(lhs.shape[0], k, n, rhs.dtype.itemsize)
    return gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
               tiling=tiling, interpret=kernel["interpret"])


def _experts_grouped(x, layer, idx, w, first, valid, kernel, counters,
                     limit=0.0):
    """The routed experts' part of a layer, exactly, with work that grows
    with the assignments held: the ``T x k`` assignments are sorted by
    expert (a counting sort: those that fall to an expert not held, or
    belong to a padded token, go last), the tokens gathered in that order,
    gate, up and down run as grouped matmuls over the experts held, and
    every token sums its own assignments' rows. No token is dropped and
    nothing is ``[T, E, I]``."""
    t, k = idx.shape
    held = layer["w_gate"].shape[0]
    local = (idx - first).reshape(t * k)
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & jnp.repeat(valid.reshape(t), k)
    group = jnp.where(mine, local, held)                          # [A]
    onehot = jax.nn.one_hot(group, held + 1, dtype=jnp.int32)    # [A, H+1]
    sizes = jnp.sum(onehot, axis=0)                               # [H+1]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    dest = (jnp.cumsum(sizes) - sizes)[group] + rank   # a permutation of A
    n_rows = -(-t * k // _GMM_ROWS) * _GMM_ROWS
    token_at = jnp.zeros((n_rows,), jnp.int32).at[dest].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), k))
    rows = x[token_at]                                            # [M, h]
    sizes = sizes[:held]
    if counters is not None:
        for name, n in (("assignments_held", jnp.sum(sizes)),
                        ("experts_touched", jnp.sum(sizes > 0))):
            counters[name] = counters.get(name, 0) + n
    gate = _grouped_matmul(rows, layer["w_gate"], sizes, kernel)
    up = _grouped_matmul(rows, layer["w_up"], sizes, kernel)
    out = _grouped_matmul(_swiglu(gate, up, limit).astype(x.dtype),
                          layer["w_down"], sizes, kernel)         # [M, h] f32
    # Rows past the held assignments hold nothing that was computed.
    out = jnp.where((jnp.arange(n_rows) < jnp.sum(sizes))[:, None], out, 0.0)
    mine_w = jnp.where(mine.reshape(t, k), w, 0.0)
    return jnp.einsum("tk,tkh->th", mine_w,
                      out[dest].reshape(t, k, out.shape[-1]))


def _moe_deepseek(mlp_in, layer, cfg, valid=None, kernel=None,
                  counters=None):
    """DeepSeek-V3 MoE (DeepseekV3TopkRouter + DeepseekV3MoE semantics):
    ``_deepseek_route`` over all ``cfg.num_experts``, the routed experts
    this layer holds (``cfg.experts_held``; all without it) by
    ``cfg.moe_dispatch`` (``_experts_grouped`` or ``_experts_dense``, both
    exact), and a shared expert that always adds in. An expert chosen and
    not held adds nothing here: its chip adds it."""
    b, s, h = mlp_in.shape
    x = mlp_in.reshape(b * s, h)
    idx, w = _deepseek_route(x, layer, cfg)
    first = cfg.experts_held[0] if cfg.experts_held else 0
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        if cfg.moe_dispatch == "grouped":
            out = _experts_grouped(x, layer, idx, w, first, valid, kernel,
                                   counters, cfg.swiglu_limit)
        else:
            out = _experts_dense(x, layer, idx, w, first, cfg.swiglu_limit)
    out = out.astype(mlp_in.dtype)

    if "w_gate_up_sh" in layer:  # fused serving layout (fuse_params)
        sh_gu = (x @ layer["w_gate_up_sh"]).astype(jnp.float32)
        sh_i = sh_gu.shape[-1] // 2
        sh_gate, sh_up = split_fused_out(sh_gu, (sh_i, sh_i),
                                         cfg.fused_interleave)
    else:
        sh_gate = (x @ layer["w_gate_sh"]).astype(jnp.float32)
        sh_up = (x @ layer["w_up_sh"]).astype(jnp.float32)
    shared = _swiglu(sh_gate, sh_up, cfg.swiglu_limit).astype(
        x.dtype) @ layer["w_down_sh"]
    return (out + shared).reshape(b, s, h)


def _moe_softmax_share(mlp_in, layer, cfg, valid=None, kernel=None,
                       counters=None):
    """A chip's share of a layer routed by the softmax over the chosen
    logits (Granite's gate, Mixtral's: ``("softmax_topk", 1)``): the
    router's logits over all ``cfg.num_experts`` in float32, the ``k``
    largest, their softmax; the terms of the experts this layer holds
    (``cfg.experts_held``; all without it) by the exact grouped dispatch,
    weighted over all ``k`` chosen, held or not; and the always-on MLP
    where the layer has one. An expert chosen and not held adds nothing
    here: its chip adds it. (``_moe_deepseek``'s lines for the dispatch and
    the shared MLP stand here again: its frame lies under four cells'
    programs and keeps its size.)"""
    b, s, h = mlp_in.shape
    x = mlp_in.reshape(b * s, h)
    logits = x.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg.num_experts_per_token)
    first = cfg.experts_held[0] if cfg.experts_held else 0
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        out = _experts_grouped(x, layer, idx, jax.nn.softmax(top, axis=-1),
                               first, valid, kernel, counters,
                               cfg.swiglu_limit).astype(mlp_in.dtype)
    if "w_gate_up_sh" in layer:  # fused serving layout (fuse_params)
        sh_gu = (x @ layer["w_gate_up_sh"]).astype(jnp.float32)
        sh_gate, sh_up = split_fused_out(
            sh_gu, (sh_gu.shape[-1] // 2,) * 2, cfg.fused_interleave)
    elif "w_gate_sh" in layer:
        sh_gate = (x @ layer["w_gate_sh"]).astype(jnp.float32)
        sh_up = (x @ layer["w_up_sh"]).astype(jnp.float32)
    else:
        return out.reshape(b, s, h)
    shared = _swiglu(sh_gate, sh_up, cfg.swiglu_limit).astype(
        x.dtype) @ layer["w_down_sh"]
    return (out + shared).reshape(b, s, h)


def _mlp(mlp_in: jax.Array, layer: dict, cfg: "LlamaConfig",
         aux_out: Any = None, valid: Any = None, kernel: Any = None,
         counters: Any = None) -> jax.Array:
    """MLP block: dense SwiGLU or top-k MoE (capacity dispatch by default,
    dense reference formulation via ``cfg.moe_dispatch="dense"``; the
    deepseek_v3 router when ``cfg.moe_router`` selects it).

    Dispatch is keyed on the LAYER's parameters (``router`` present →
    MoE), so dense-first_k DeepSeek layouts mix layer kinds in one model.
    Expert matmuls stay in the model dtype (bf16 MXU path, like the dense
    branch); only router/softmax/mix math runs in f32. ``valid`` ([b, s]
    bool) excludes padded positions from capacity routing and from the
    grouped dispatch; ``kernel`` and ``counters`` as ``_experts_grouped``
    takes them.
    """
    if "router" in layer:
        if cfg.moe_router and cfg.moe_router[0] == "deepseek_v3":
            return _moe_deepseek(mlp_in, layer, cfg, valid=valid,
                                 kernel=kernel, counters=counters)
        if cfg.moe_dispatch == "grouped":
            return _moe_softmax_share(mlp_in, layer, cfg, valid=valid,
                                      kernel=kernel, counters=counters)
        if cfg.moe_dispatch == "capacity":
            return _moe_capacity(mlp_in, layer, cfg, aux_out, valid=valid)
        if cfg.moe_dispatch == "dense":
            return _moe_dense(mlp_in, layer, cfg, aux_out)
        raise ValueError(f"unknown moe_dispatch: {cfg.moe_dispatch!r}")

    if "w_gate_up" in layer:  # fused serving layout (fuse_params)
        gu = (mlp_in @ layer["w_gate_up"]).astype(jnp.float32)
        inter = gu.shape[-1] // 2
        gate, up = split_fused_out(gu, (inter, inter),
                                   cfg.fused_interleave)
    else:
        gate = (mlp_in @ layer["w_gate"]).astype(jnp.float32)
        up = (mlp_in @ layer["w_up"]).astype(jnp.float32)
    return _swiglu(gate, up, cfg.swiglu_limit).astype(
        mlp_in.dtype) @ layer["w_down"]


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          scaling: tuple = ()) -> jax.Array:
    """Rotary position embedding. x: [b, s, heads, hd], positions: [b, s].

    ``scaling`` is ``LlamaConfig.rope_scaling``: ``()`` for plain RoPE,
    ``("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)`` — the Llama-3.1 frequency-band
    NTK scheme (long wavelengths divided by ``factor``, short kept,
    smooth ramp between) — or ``("yarn", factor, beta_fast, beta_slow,
    original_max, attention_factor)``; both match transformers'
    ``modeling_rope_utils`` formulas. ``theta`` 0 is a model without
    positional encoding: ``x`` as it came.
    """
    if not theta:
        return x
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    att = 1.0
    if scaling and scaling[0] == "llama3":
        _, factor, low_f, high_f, orig = scaling
        wavelen = 2.0 * math.pi / freqs
        low_wl = orig / low_f       # wavelengths above this: fully scaled
        high_wl = orig / high_f     # wavelengths below this: unscaled
        smooth = (orig / wavelen - low_f) / (high_f - low_f)
        mid = (1.0 - smooth) * freqs / factor + smooth * freqs
        freqs = jnp.where(wavelen > low_wl, freqs / factor,
                          jnp.where(wavelen < high_wl, freqs, mid))
    elif scaling:
        # yarn (NTK-by-parts, paper 2309.00071; matches transformers'
        # _compute_yarn_parameters with truncate=True): dims below the
        # beta_fast correction bound extrapolate (unscaled), above the
        # beta_slow bound interpolate (freq/factor), linear ramp between;
        # cos/sin are scaled by the pre-resolved attention factor.
        _, factor, beta_fast, beta_slow, orig, att = scaling

        def corr_dim(n_rot):  # full-dim index for a rotation count
            return (hd * math.log(orig / (n_rot * 2.0 * math.pi))
                    ) / (2.0 * math.log(theta))

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), hd - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 0.001), 0.0, 1.0)
        extrap = 1.0 - ramp
        freqs = (freqs / factor) * (1.0 - extrap) + freqs * extrap
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, half]
    cos = jnp.cos(angles)[:, :, None, :] * att
    sin = jnp.sin(angles)[:, :, None, :] * att
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
                eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight + bias).astype(x.dtype)


def _rope_leading(x: jax.Array, dr: int, positions: jax.Array,
                  cfg: "LlamaConfig") -> jax.Array:
    """RoPE on the first ``dr`` dims of ``x [b, s, heads, d]`` (the
    indexer's layout: rope dims first), the rest as they are."""
    return jnp.concatenate(
        [_rope(x[..., :dr], positions, cfg.rope_theta, cfg.rope_scaling),
         x[..., dr:]], axis=-1)


def _sublayer_out(out, gate_in, layer, cfg, which: str) -> jax.Array:
    """What a sub-layer (``which``: ``attn`` or ``mlp``) adds to the
    residual. A mixer's output ``out`` goes through its output projection,
    gated first by ``sigmoid(gate_in W_g)`` where the layer has that gate
    (attention's: the heads' outputs, from the layer's normed input); with
    ``cfg.post_norms`` the result is normed; what joins the residual is
    that times ``cfg.residual_multiplier``."""
    if which == "attn":
        if "w_og" in layer:
            gate = jax.nn.sigmoid((gate_in @ layer["w_og"]).astype(
                jnp.float32))
            out = (out * gate).astype(out.dtype)
        out = out @ layer["wo"]
    if cfg.post_norms:
        out = _rms_norm(out, layer[which + "_post_norm"], cfg.norm_eps,
                        cfg.norm_offset)
    if cfg.residual_multiplier != 1.0:
        out = (out.astype(jnp.float32) * cfg.residual_multiplier).astype(
            out.dtype)
    return out


def _linear_inputs(x, layer, la, live):
    """What a linear mixer projects from its normed input ``x [b, s, h]``:
    ``(the conv's input [b, s, conv channels], the output gate's
    pre-activation [b, s, inner], beta [b, s, value heads], the log-decay
    g)``, ``beta`` and ``g`` float32 and 0 where ``live [b, s, 1]`` is
    not. ``g`` is ``[b, s, value heads]`` (``decay`` "head": ``[q, k, v,
    z] = x W_qkvz``, ``[b, a] = x W_ba``, ``g = -exp(A_log) softplus(a +
    dt_bias)``) or ``[b, s, heads, key_dim]`` ("channel": ``[q, k, v] = x
    W_conv_in``, ``z = (x W_g_down) W_g_up``, ``b = x W_beta``, ``g =
    -exp(A_log) softplus((x W_f_down) W_f_up + dt_bias)`` with ``A_log`` a
    head and ``dt_bias`` a channel)."""
    f32 = jnp.float32
    if la.decay == "channel":
        a = ((x @ layer["w_f_down"]) @ layer["w_f_up"]).astype(f32)
        g = jax.nn.softplus(a + layer["dt_bias"]).reshape(
            *a.shape[:2], la.key_heads, la.key_dim)
        g = -jnp.exp(layer["A_log"])[:, None] * g
        beta = la.beta_scale * jax.nn.sigmoid(
            (x @ layer["w_beta"]).astype(f32))
        return (x @ layer["w_conv_in"],
                (x @ layer["w_g_down"]) @ layer["w_g_up"],
                jnp.where(live, beta, 0.0),
                jnp.where(live[..., None], g, 0.0))
    qkvz = x @ layer["w_qkvz"]
    ba = (x @ layer["w_ba"]).astype(f32)
    chans = la.conv_channels
    beta = jnp.where(live, jax.nn.sigmoid(ba[..., :la.value_heads]), 0.0)
    g = jnp.where(live, -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[..., la.value_heads:] + layer["dt_bias"]), 0.0)
    return qkvz[..., :chans], qkvz[..., chans:], beta, g


def _gated_deltanet(x, layer, cfg, lj, state, valid, ctx_lens, new_lens,
                    kernel):
    """A Gated DeltaNet mixer over ``x [b, s, h]`` (the layer's normed
    input): ``(heads' outputs [b, s, value heads x value_dim] before the
    output projection, the state as handed in with this layer's part
    updated)``. ``lj`` is the layer's index in the state pool; ``state``,
    ``kernel`` as ``_forward_impl_grouped`` takes them.

    The projections (``_linear_inputs``: q, k, v, the gate's z, beta and
    the log-decay, in the form ``cfg.linear.decay`` names); a depthwise
    causal conv over q, k, v (its first taps read the row's conv state:
    the last inputs of what came before) and SiLU; q, k of unit length per
    head, q times ``key_dim^-1/2``; the recurrence (``ops.gated_deltanet``:
    a chunk is scanned in blocks of a page, a decode step updates every
    row's state in place; ``kda_*`` where the decay is channel-wise); the
    heads' outputs normed per head and gated by ``gate_scale *
    sigmoid(z)``."""
    if cfg.linear.decay == "mamba2":  # no delta rule: a section of its own
        return (_parallel_block if cfg.parallel_layers else _mamba2)(
            x, layer, cfg, lj, state, valid, ctx_lens, new_lens, kernel)
    from ..ops import gated_deltanet as gd

    la = cfg.linear
    if la.decay == "channel":
        scan, step = gd.kda_scan, gd.kda_step
        scopes = gd.KERNEL_KDA_SCAN, gd.KERNEL_KDA_STEP
    else:
        scan, step = gd.gdn_scan, gd.gdn_step
        scopes = gd.KERNEL_SCAN, gd.KERNEL_STEP
    f32 = jnp.float32
    b, s, _ = x.shape
    recurrent, conv, slots, snap = state
    taps = la.conv_kernel
    nk = la.key_heads * la.key_dim
    use = dict(kernel=kernel is not None,
               interpret=bool(kernel and kernel["interpret"]))

    mixed, z, beta, g = _linear_inputs(x, layer, la, valid[..., None])
    fresh = ctx_lens == 0                                          # [b]
    tail = jnp.where(fresh[:, None, None], 0, conv[lj, slots])
    window = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)

    def tail_at(n):
        """The conv state after a row's first ``n [b]`` tokens."""
        at = n[:, None] + jnp.arange(taps - 1)[None, :]
        return jnp.take_along_axis(window, at[:, :, None], axis=1)

    mixed = jax.nn.silu(sum(
        window[:, j:j + s].astype(f32) * layer["conv_w"][j]
        for j in range(taps))).astype(x.dtype)
    new_tail = tail_at(new_lens).astype(conv.dtype)
    conv = conv.at[lj, slots].set(new_tail)

    def unit(t, heads):
        t = t.reshape(b, s, heads, -1).astype(f32)
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(mixed[..., :nk], la.key_heads) * la.key_dim ** -0.5
    k = unit(mixed[..., nk:2 * nk], la.key_heads)
    v = mixed[..., 2 * nk:].reshape(b, s, la.value_heads, la.value_dim)

    if s == 1:
        with jax.named_scope(scopes[1]):
            o, recurrent = step(recurrent, lj, slots, q[:, 0], k[:, 0],
                                    v[:, 0], g[:, 0], beta[:, 0], **use)
        o = o[:, None]
    else:
        if snap is not None and b != 1:
            raise ValueError("a chunk that leaves snapshots is one row")
        outs = []
        with jax.named_scope(scopes[0]):
            for i in range(b):
                first = jnp.where(fresh[i], 0.0, recurrent[lj, slots[i]])
                o_i, end, inner = scan(
                    q[i], k[i], v[i], g[i], beta[i], first,
                    -1 if snap is None else snap[0], block=cfg.page_size,
                    **use)
                recurrent = recurrent.at[lj, slots[i]].set(end)
                outs.append(o_i)
            if snap is not None:
                recurrent = recurrent.at[lj, snap[1]].set(inner)
                recurrent = recurrent.at[lj, snap[2]].set(end)
                conv = conv.at[lj, snap[1]].set(tail_at(
                    (snap[0:1] + 1) * cfg.page_size)[0].astype(conv.dtype))
                conv = conv.at[lj, snap[2]].set(new_tail[0])
        o = jnp.stack(outs)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + la.norm_eps)
    o = o * (cfg.norm_offset + layer["o_norm"]) * (
        la.gate_scale * jax.nn.sigmoid(z.reshape(o.shape).astype(f32)))
    return (o.astype(x.dtype).reshape(b, s, la.inner),
            (recurrent, conv, slots, snap))


# -- a state-space layer (Mamba-2) -------------------------------------------
# Reached from ``_gated_deltanet`` (what the shared body calls for a layer
# that keeps a state), which keeps its own lines for the conv's tail and the
# snapshots: its frame lies under two cells' programs.


def _conv_through_tail(mixed, layer, conv, lj, slots, fresh, new_lens, snap,
                       page_size):
    """A depthwise causal conv (with its bias) and SiLU over ``mixed [b, s,
    channels]``, its first taps reading each row's conv state (the last
    ``taps - 1`` inputs of what came before; zeros for a ``fresh`` row):
    ``(the conv's output in mixed's type, conv)`` with the rows' new tails
    written under their slots and, for a chunk that leaves snapshots
    (``snap``, one row), the tail after the requested block and the one at
    the chunk's end under theirs."""
    s = mixed.shape[1]
    taps = layer["conv_w"].shape[0]
    tail = jnp.where(fresh[:, None, None], 0, conv[lj, slots])
    window = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)

    def tail_at(n):
        """The conv state after a row's first ``n [b]`` tokens."""
        at = n[:, None] + jnp.arange(taps - 1)[None, :]
        return jnp.take_along_axis(window, at[:, :, None], axis=1)

    out = jax.nn.silu(sum(
        window[:, j:j + s].astype(jnp.float32) * layer["conv_w"][j]
        for j in range(taps)) + layer["conv_b"]).astype(mixed.dtype)
    new_tail = tail_at(new_lens).astype(conv.dtype)
    conv = conv.at[lj, slots].set(new_tail)
    if snap is not None:
        conv = conv.at[lj, snap[1]].set(tail_at(
            (snap[0:1] + 1) * page_size)[0].astype(conv.dtype))
        conv = conv.at[lj, snap[2]].set(new_tail[0])
    return out, conv


def _mamba2(x, layer, cfg, lj, state, valid, ctx_lens, new_lens, kernel):
    """A Mamba-2 mixer over ``x [b, s, h]`` (the layer's normed input), as
    ``_gated_deltanet`` returns: ``(the heads' outputs [b, s, inner] before
    the output projection, the state with this layer's part updated)``.

    ``[z | x B C | dt] = x W_in``; a depthwise causal conv with a bias over
    ``x B C`` and SiLU; the step ``d = softplus(dt + dt_bias)`` a head (0
    at a padded token: the state stays), ``A = -exp(A_log)``; the
    recurrence ``S <- exp(d A) S + d x (x) B``, ``y = S C + D x``
    (``ops.mamba2``: a chunk is scanned in blocks of a page, a decode step
    updates every row's state in place), a head reading its group's ``B``
    and ``C``; ``y silu(z)`` normed a group of ``inner / key_heads``
    channels at a time (one group: over all inner channels)."""
    from ..ops import mamba2 as m2

    la = cfg.linear
    f32 = jnp.float32
    b, s, _ = x.shape
    recurrent, conv, slots, snap = state
    inner, n = la.inner, la.key_dim
    use = dict(kernel=kernel is not None,
               interpret=bool(kernel and kernel["interpret"]))
    if snap is not None and b != 1:
        raise ValueError("a chunk that leaves snapshots is one row")

    zxd = x @ layer["w_in"]
    z = zxd[..., :inner].astype(f32)
    dt = jnp.where(valid[..., None], jax.nn.softplus(
        zxd[..., inner + la.conv_channels:].astype(f32) + layer["dt_bias"]),
        0.0)
    fresh = ctx_lens == 0                                          # [b]
    mixed, conv = _conv_through_tail(
        zxd[..., inner:inner + la.conv_channels], layer, conv, lj, slots,
        fresh, new_lens, snap, cfg.page_size)
    xs = mixed[..., :inner].reshape(b, s, la.value_heads, la.value_dim)
    bs = mixed[..., inner:inner + la.key_heads * n].reshape(
        b, s, la.key_heads, n)
    cs = mixed[..., inner + la.key_heads * n:].reshape(b, s, la.key_heads, n)
    a = -jnp.exp(layer["A_log"])

    if s == 1:
        with jax.named_scope(m2.KERNEL_STEP):
            y, recurrent = m2.mamba2_step(
                recurrent, lj, slots, xs[:, 0], bs[:, 0], cs[:, 0], dt[:, 0],
                a, layer["D"], **use)
        y = y[:, None]
    else:
        outs = []
        with jax.named_scope(m2.KERNEL_SCAN):
            for i in range(b):
                first = jnp.where(fresh[i], 0.0, recurrent[lj, slots[i]])
                y_i, end, at_block = m2.mamba2_scan(
                    xs[i], bs[i], cs[i], dt[i], a, layer["D"], first,
                    -1 if snap is None else snap[0], block=cfg.page_size,
                    **use)
                recurrent = recurrent.at[lj, slots[i]].set(end)
                outs.append(y_i)
            if snap is not None:
                recurrent = recurrent.at[lj, snap[1]].set(at_block)
                recurrent = recurrent.at[lj, snap[2]].set(end)
        y = jnp.stack(outs)
    y = _group_rms(y.reshape(b, s, inner) * jax.nn.silu(z), la.key_heads,
                   la.norm_eps)
    return ((y * layer["o_norm"]).astype(x.dtype),
            (recurrent, conv, slots, snap))


def _group_rms(y, groups: int, eps: float):
    """``y [..., channels]`` over its root mean square, each of ``groups``
    equal runs of channels by its own."""
    g = y.reshape(*y.shape[:-1], groups, -1)
    return (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + eps)).reshape(y.shape)


# -- a layer of two mixers (Falcon-H1) ---------------------------------------
# Reached as ``_mamba2`` is: the shared body runs such a layer as a linear
# one (its norm, this, ``_sublayer_out``, the MLP) and touches no page of it.
# The page pools and the rows' page table therefore ride where the recurrent
# pool does in ``state`` (``with_pages_in_state``), from layer to layer
# through here and back to the step program.

SCOPE_MIXER_SSM = "mixer.ssm"
SCOPE_MIXER_ATTENTION = "mixer.attention"


def _parallel_block(x, layer, cfg, lj, state, valid, ctx_lens, new_lens,
                    kernel):
    """Both mixers of a parallel layer over ``x [b, s, h]``, its ONE normed
    input, as ``_mamba2`` returns: ``([the Mamba-2 mixer's gated, normed
    output | the attention heads' outputs] [b, s, inner + heads x head_dim],
    the state with this layer's part of every pool updated)``. The layer's
    ``wo`` as ``multiplied`` views it takes the two halves through their
    own output projections under their own multipliers (``_Summed``).

    The attention is the attending branch's: q, k, v (fused or not; the
    queries carry ``attention_multiplier``), RoPE, the new keys and values
    written into this layer of the donated page pools, then the kernel the
    step's form asks for: XLA (``kernel`` None), the Pallas decode kernel
    for one position a row, the Pallas prefill kernel for a chunk (a row a
    program: ``EngineConfig.decode_batch_rows`` does not reach here)."""
    (recurrent, k_cache, v_cache, table), conv, slots, snap = state
    with jax.named_scope(SCOPE_MIXER_SSM):
        ssm, (recurrent, conv, _, _) = _mamba2(
            x, layer, cfg, lj, (recurrent, conv, slots, snap), valid,
            ctx_lens, new_lens, kernel)
    with jax.named_scope(SCOPE_MIXER_ATTENTION):
        b, s, _ = x.shape
        at = cfg.page_layers.index(cfg.linear_layers[lj])
        positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
        total_lens = ctx_lens + new_lens
        nq = cfg.num_heads * cfg.head_dim
        nk = cfg.num_kv_heads * cfg.head_dim
        if "w_qkv" in layer:  # fused serving layout (fuse_params)
            q, k, v = split_fused_out(x @ layer["w_qkv"], (nq, nk, nk),
                                      cfg.fused_interleave)
        else:
            q, k, v = x @ layer["wq"], x @ layer["wk"], x @ layer["wv"]
        q = _rope(q.reshape(b, s, cfg.num_heads, cfg.head_dim), positions,
                  cfg.rope_theta, cfg.rope_scaling)
        k = _rope(k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim), positions,
                  cfg.rope_theta, cfg.rope_scaling)
        v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope(SCOPE_KV_WRITE):
            writes = page_writes(cfg.page_size, table, positions, valid)
            k_cache = write_kv_pages(k_cache, writes, k, layer_idx=at)
            v_cache = write_kv_pages(v_cache, writes, v, layer_idx=at)
        if kernel is None:
            attn = paged_attention(q, k_cache, v_cache, table, positions,
                                   total_lens, layer_idx=at)
        else:
            from ..ops import pallas_paged_attention as ppa

            if s == 1:
                attn = ppa.pallas_paged_decode_attention(
                    q[:, 0], k_cache, v_cache, table, total_lens,
                    layer_idx=at, interpret=kernel["interpret"])[:, None]
            else:
                attn = ppa.pallas_paged_prefill_attention(
                    q, k_cache, v_cache, table, ctx_lens, total_lens,
                    q_tile=_prefill_q_tile(cfg, s), layer_idx=at,
                    interpret=kernel["interpret"])
    return (jnp.concatenate([ssm, attn.reshape(b, s, nq)], axis=-1),
            ((recurrent, k_cache, v_cache, table), conv, slots, snap))


def _forward_impl_grouped(params, cfg, tokens, k_caches, v_caches, tables,
                          ctx_lens, new_lens, attention_fn, last_only=False,
                          ragged=None, kernel=None, counters=None,
                          state=None):
    """Shared transformer body over grouped KV pools.

    ``k_caches[g]`` holds group g's layers stacked in ``cfg.group_layers(g)``
    order with its own page pool; ``tables[g]`` is that pool's page table.
    The non-hybrid case is the 1-tuple degenerate form. ``attention_fn(q,
    k_stack, v_stack, layer_idx, page_table, positions, total_lens, window)
    -> [b, seq, heads, hd]`` picks the backend. It is handed the group's
    whole stack and the layer's index in it, and new K/V rows are written
    at ``[layer_idx, page, :, slot, :]`` of the donated stack: no step
    program takes a layer out of a pool or puts one back, which moved the
    pool around every layer where the step's own rows are a few KiB.

    ``last_only=True`` computes logits only for each sequence's final valid
    token (``new_lens - 1``) — the prefill-chunk case, where the full
    [seq, vocab] lm_head matmul and its fp32 materialization are pure waste
    (chunk × vocab of matmul and of HBM write on logits nobody reads).

    ``ragged=row_starts`` ([rows+1] flat-token prefix sums) is the ragged
    mixed-batch mode: ``tokens`` is one flat axis [1, total_q] where row r
    owns slots ``[row_starts[r], row_starts[r+1])`` at logical positions
    ``ctx_lens[r] + i`` — ``ctx_lens``/``new_lens`` are per-ROW [rows],
    ``tables[g]`` is [rows, pages_per_seq], and the attention backend must
    understand the ragged layout (``pallas_paged_ragged_attention``).
    ``last_only=True`` then returns one logit row per ragged row (each
    row's final token) — logits [1, rows, vocab].

    A model with an indexer (``cfg.is_dsa``) writes its index keys into
    ``v_caches[g]`` beside the latent and hands ``attention_fn`` one more
    argument, ``index=(q_idx, w_idx)``: the indexer's queries ``[b, seq,
    heads, width]`` and head weights ``[b, seq, heads]`` (float32), with
    which the backend scores the row's pages of ``v_stack`` and selects
    (``ops.sparse_index``). ``kernel`` and ``counters`` go to the routed
    layers (``_experts_grouped``).

    A model with linear layers (``cfg.linear_layers``) is handed ``state =
    (recurrent, conv, slots, snap)``: its state pool (``init_state_pool``),
    each row's slot in it, and for a prefill chunk ``snap = [block, slot,
    end_slot]``: the state after the chunk's block ``block`` is also written
    to ``slot`` and the state at the chunk's end to ``end_slot`` (the spare
    slot 0 where nothing is wanted). A row at position 0 starts from no
    state whatever its slot holds. The pools come back as a fourth result.
    ``kernel`` also picks the recurrence's Pallas kernels.
    """
    batch, seq = tokens.shape
    if cfg.linear_layers and (state is None or ragged is not None):
        raise NotImplementedError(
            "linear layers are served by the padded step programs, handed "
            "their state pool: no ragged batches")
    if cfg.is_dsa and ragged is not None:
        raise NotImplementedError(
            "learned sparse attention is served by the padded step "
            "programs: no ragged batches")
    if ragged is not None:
        if batch != 1:
            raise ValueError(
                f"ragged mode takes one flat token axis [1, total_q], "
                f"got batch={batch}")
        rows = ctx_lens.shape[0]
        flat = jnp.arange(seq)
        row_of = jnp.clip(
            jnp.searchsorted(ragged, flat, side="right") - 1, 0, rows - 1)
        positions = (ctx_lens[row_of] + flat - ragged[row_of])[None, :]
        valid = (flat < ragged[-1])[None, :]
        new_tokens = (positions[0], valid[0], row_of)
    else:
        positions = ctx_lens[:, None] + jnp.arange(seq)[None, :]  # [b, s]
        valid = jnp.arange(seq)[None, :] < new_lens[:, None]
        new_tokens = (positions, valid)
    total_lens = ctx_lens + new_lens

    # Static layer→(group, local index) map, resolved at trace time. One
    # pool holds the layers that keep pages, in order.
    local_idx = {li: (0, j) for j, li in enumerate(cfg.page_layers)}
    if len(k_caches) > 1:
        for g in range(len(k_caches)):
            for j, li in enumerate(cfg.group_layers(g)):
                local_idx[li] = (g, j)

    # Where the step's tokens land in each group's pool: the same for
    # every layer of the group and for K and V.
    with jax.named_scope(SCOPE_KV_WRITE):
        writes = [page_writes(cfg.page_size, table, *new_tokens)
                  for table in tables]

    def write_layer(cache, g, lj, new_kv):
        """Group ``g``'s stack ``cache`` with ``new_kv`` written into its
        layer ``lj``, in place on the donated buffer."""
        with jax.named_scope(SCOPE_KV_WRITE):
            return write_kv_pages(cache, writes[g], new_kv, layer_idx=lj)

    with jax.named_scope(SCOPE_EMBED):
        x = params["embed"][tokens]  # [b, s, h]

    k_caches = list(k_caches)
    v_caches = list(v_caches)
    for li, layer in enumerate(params["layers"]):
        if cfg.layer_kind(li) == "linear":
            with jax.named_scope(SCOPE_QKV):
                attn_in = _rms_norm(x, layer["attn_norm"], cfg.norm_eps,
                                    cfg.norm_offset)
            with jax.named_scope(SCOPE_ATTENTION):
                attn, state = _gated_deltanet(
                    attn_in, layer, cfg, cfg.linear_layers.index(li), state,
                    valid, ctx_lens, new_lens, kernel)
                x = x + _sublayer_out(attn, attn_in, layer, cfg, "attn")
            with jax.named_scope(SCOPE_MLP):
                mlp_in = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps,
                                   cfg.norm_offset)
                x = x + _sublayer_out(
                    _mlp(mlp_in, layer, cfg, valid=valid, kernel=kernel,
                         counters=counters), None, layer, cfg, "mlp")
            continue
        g, lj = local_idx[li]
        table = tables[g]
        if cfg.is_mla:
            with jax.named_scope(SCOPE_QKV):
                attn_in = _rms_norm(x, layer["attn_norm"], cfg.norm_eps,
                                    cfg.norm_offset)
                # Absorbed MLA (DeepSeek-V2 §2.1.2, TPU-first formulation):
                # cache ONLY the latent [c_kv ; rope-key] per token and fold
                # the per-head up-projections into the query and output — the
                # attention core is then plain multi-query paged attention
                # with head_dim = rank+rope over the cache this file already
                # pages, and HBM traffic per token drops by ~num_heads·2.
                r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
                if "w_mla_in" in layer:  # fused serving layout (fuse_params)
                    fused = attn_in @ layer["w_mla_in"]
                    qc = fused.shape[-1] - r - dr  # static split point
                    head_in = fused[..., :qc]
                    c_kv = fused[..., qc:qc + r]
                    k_rope_in = fused[..., qc + r:]
                    if "q_latent_norm" in layer:
                        # q-LoRA: the fused block holds w_dq's output; the
                        # norm between down- and up-projection stays.
                        q_in = _rms_norm(head_in, layer["q_latent_norm"],
                                         cfg.norm_eps, cfg.norm_offset)
                        q = q_in @ layer["wq"]
                    else:
                        q = head_in
                else:
                    if "w_dq" in layer:
                        # DeepSeek q-LoRA: q is down-projected to a compressed
                        # latent, RMS-normed, then up-projected per head — the
                        # norm between the two matmuls prevents precomposition.
                        q_in = _rms_norm(attn_in @ layer["w_dq"],
                                         layer["q_latent_norm"], cfg.norm_eps,
                                         cfg.norm_offset)
                    else:
                        q_in = attn_in
                    q = q_in @ layer["wq"]
                    c_kv = attn_in @ layer["w_dkv"]  # [b, s, r]
                    k_rope_in = attn_in @ layer["w_kr"]
                q = q.reshape(batch, seq, cfg.num_heads, cfg.head_dim + dr)
                q_nope, q_rope = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
                q_rope = _rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
                if "latent_norm" in layer:
                    # DeepSeek kv_a_layernorm: the latent is RMS-normed before
                    # the up-projections — cached post-norm, so absorption is
                    # unchanged (w_uk applies to the normed latent).
                    c_kv = _rms_norm(c_kv, layer["latent_norm"], cfg.norm_eps,
                                     cfg.norm_offset)
                k_rope = _rope(k_rope_in[:, :, None, :],
                               positions, cfg.rope_theta,
                               cfg.rope_scaling)  # [b, s, 1, dr]
                latent = jnp.concatenate(
                    [c_kv[:, :, None, :], k_rope], axis=-1)  # [b, s, 1, r+dr]
                # Absorb W_UK: q·(latent@W_UK) == (q@W_UK^T)·latent.
                q_lat = jnp.einsum("bshd,hrd->bshr", q_nope, layer["w_uk"])
                q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)
                if cfg.latent_pad:
                    # 128-lane alignment pad (see LlamaConfig.latent_pad):
                    # zero key dims score zero against any query, so the
                    # attention output only sees the pad through fp rounding
                    # of the two-step scale factor (~1 ulp).
                    pad = [(0, 0)] * 3 + [(0, cfg.latent_pad)]
                    latent = jnp.pad(latent, pad)
                    q_eff = jnp.pad(q_eff, pad)
                # The attention backends scale by q.shape[-1]^-0.5 (the padded
                # cache width); MLA's logical scale is the per-head q/k width
                # (nope+rope), times the DeepSeek-yarn mscale^2 when set.
                q_eff = q_eff * (
                    q_eff.shape[-1] ** 0.5 / (cfg.head_dim + dr) ** 0.5
                    * cfg.softmax_scale_mult)

            # Values ARE the latent: pass the K pool as both K and V (the
            # width-0 V pool is never read), then un-absorb W_UV.
            extra = {}
            if cfg.is_dsa:
                with jax.named_scope(SCOPE_INDEX):
                    # The lightning indexer: light per-head queries from
                    # the q latent, one key a token (LayerNorm, RoPE on its
                    # leading rope dims) cached beside the latent, and the
                    # heads' weights from the layer's input.
                    # Kept in float32 from the projections to the one
                    # rounding each takes (the key's into the pool, the
                    # query's into the kernel): which keys a query keeps
                    # turns on differences of a rounding's size, and these
                    # three matmuls are a hundredth of the layer's.
                    hi, di = cfg.index_n_heads, cfg.index_head_dim

                    def proj(a, w):
                        return jnp.matmul(
                            a, w, preferred_element_type=jnp.float32)

                    q_idx = _rope_leading(
                        proj(q_in, layer["w_iq"]).reshape(
                            batch, seq, hi, di),
                        dr, positions, cfg).astype(x.dtype)
                    k_idx = _rope_leading(
                        _layer_norm(proj(attn_in, layer["w_ik"]),
                                    layer["index_norm"],
                                    layer["index_norm_bias"],
                                    cfg.norm_eps)[:, :, None, :],
                        dr, positions, cfg)                # [b, s, 1, di]
                    w_idx = proj(attn_in, layer["w_iw"]) * (
                        hi ** -0.5 * di ** -0.5)
                v_caches[g] = write_layer(v_caches[g], g, lj, k_idx)
                extra = {"index": (q_idx, w_idx)}
            k_caches[g] = write_layer(k_caches[g], g, lj, latent)
            v_stack = v_caches[g] if cfg.is_dsa else k_caches[g]
            with jax.named_scope(SCOPE_ATTENTION):
                # Absorbed, or per head where ``attention_fn`` brings a
                # ``per_head_fn`` (a prefill chunk of enough queries).
                queries = (q_eff, q_nope, q_rope)
                attn = _latent_attention(
                    attention_fn, queries, layer, k_caches[g], v_stack, lj,
                    table, positions, total_lens, **extra)
        else:
            with jax.named_scope(SCOPE_QKV):
                attn_in = _rms_norm(x, layer["attn_norm"], cfg.norm_eps,
                                    cfg.norm_offset)
                if "w_qkv" in layer:  # fused serving layout (fuse_params)
                    qkv = attn_in @ layer["w_qkv"]
                    if "b_qkv" in layer:
                        qkv = qkv + layer["b_qkv"]
                    nq = cfg.num_heads * cfg.head_dim
                    nk = cfg.num_kv_heads * cfg.head_dim
                    nv = qkv.shape[-1] - nq - nk
                    q, k, v = split_fused_out(qkv, (nq, nk, nv),
                                              cfg.fused_interleave)
                else:
                    q = attn_in @ layer["wq"]
                    k = attn_in @ layer["wk"]
                    v = attn_in @ layer["wv"]
                    if "bq" in layer:  # Qwen2-lineage QKV projection biases
                        q = q + layer["bq"]
                        k = k + layer["bk"]
                        v = v + layer["bv"]
                q = q.reshape(batch, seq, cfg.num_heads, cfg.head_dim)
                k = k.reshape(batch, seq, cfg.num_kv_heads, cfg.head_dim)
                v = v.reshape(batch, seq, cfg.num_kv_heads, cfg.head_dim)
                if cfg.qk_norm:  # Qwen3: per-head RMS over head_dim, pre-RoPE
                    q = _rms_norm(q, layer["q_norm"], cfg.norm_eps,
                                  cfg.norm_offset)
                    k = _rms_norm(k, layer["k_norm"], cfg.norm_eps,
                                  cfg.norm_offset)
                q = _rope(q, positions, cfg.rope_theta, cfg.layer_rope(li))
                k = _rope(k, positions, cfg.rope_theta, cfg.layer_rope(li))

            k_caches[g] = write_layer(k_caches[g], g, lj, k)
            v_caches[g] = write_layer(v_caches[g], g, lj, v)
            with jax.named_scope(SCOPE_ATTENTION):
                attn = attention_fn(
                    q, k_caches[g], v_caches[g], lj, table, positions,
                    total_lens, cfg.layer_window(li))
        with jax.named_scope(SCOPE_ATTENTION):
            x = x + _sublayer_out(attn.reshape(batch, seq, -1), attn_in,
                                  layer, cfg, "attn")

        with jax.named_scope(SCOPE_MLP):
            mlp_in = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps,
                               cfg.norm_offset)
            x = x + _sublayer_out(
                _mlp(mlp_in, layer, cfg, valid=valid, kernel=kernel,
                     counters=counters), None, layer, cfg, "mlp")

    with jax.named_scope(SCOPE_LM_HEAD):
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_offset)
        if last_only:
            if ragged is not None:
                # One logit row per ragged row: its final flat token
                # (row_starts[r+1] - 1; empty rows clamp to slot 0 and the
                # caller ignores them).
                idx = jnp.maximum(ragged[1:] - 1, 0)[None, :]  # [1, rows]
                x = jnp.take_along_axis(x, idx[:, :, None], axis=1)
            else:
                idx = jnp.maximum(new_lens - 1, 0)  # [b]
                x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    if state is not None:
        return logits, tuple(k_caches), tuple(v_caches), state[:2]
    return logits, tuple(k_caches), tuple(v_caches)


def greedy_tokens(logits: jax.Array) -> jax.Array:
    """The tail of every program that samples: the best token of each row
    of float32 ``logits [rows, vocab]``, as ``int32 [rows]``."""
    with jax.named_scope(SCOPE_SAMPLE):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _xla_attention(cfg):
    """The XLA attention backend of ``forward`` and ``forward_hybrid``."""
    def attention(q, k_stack, v_stack, layer_idx, table, positions,
                  total_lens, window, index=None):
        keep = None
        if index is not None:
            # Learned sparse attention as a mask over dense attention: the
            # index stack is v_stack, the values are the latent (k_stack).
            with jax.named_scope(SCOPE_INDEX):
                scores = sparse_index.index_scores_xla(
                    *index, sparse_index.gather_index_keys(
                        v_stack, layer_idx, table))
            with jax.named_scope(SCOPE_SELECT):
                keep = sparse_index.keep_mask(scores, positions, total_lens,
                                              cfg.index_topk)
            v_stack = k_stack
        return paged_attention(
            q, k_stack, v_stack, table, positions, total_lens,
            sliding_window=window,
            attention_sinks=cfg.attention_sinks or None, layer_idx=layer_idx,
            keep=keep,
        )
    return attention


def _forward_impl(params, cfg, tokens, k_cache, v_cache, page_table,
                  ctx_lens, new_lens, attention_fn, last_only=False,
                  kernel=None, counters=None, state=None):
    logits, ks, vs, *rest = _forward_impl_grouped(
        params, cfg, tokens, (k_cache,), (v_cache,), (page_table,),
        ctx_lens, new_lens, attention_fn, last_only=last_only,
        kernel=kernel, counters=counters, state=state,
    )
    return (logits, ks[0], vs[0], *rest)


@partial(jax.jit, static_argnames=("cfg", "last_only"),
         donate_argnames=("k_cache", "v_cache"))
def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [batch, seq] int32 (padded)
    k_cache: jax.Array,  # [layers, pages, kvh, page_size, hd] (donated)
    v_cache: jax.Array,  # same (donated)
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    ctx_lens: jax.Array,  # [batch] tokens already cached before this call
    new_lens: jax.Array,  # [batch] valid new tokens in `tokens`
    last_only: bool = False,
    counters: dict | None = None,
    state: tuple | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One model step (prefill or decode), XLA attention backend.

    Returns ``(logits [b, seq, vocab], k_cache, v_cache)``. Query i of
    sequence b sits at logical position ``ctx_lens[b] + i``; padded
    positions (``i >= new_lens[b]``) are masked and scatter to the garbage
    page. ``last_only=True`` → logits is [b, 1, vocab], the final valid
    position of each row (prefill chunks; see ``_forward_impl_grouped``).
    ``counters``: a dict the step form hands in, filled while tracing with
    ``cfg.step_counters`` (``step_program``). ``state``: a model with
    linear layers' state pool, slots and snapshot request
    (``_forward_impl_grouped``); the pool comes back as a fourth result.
    """
    return _forward_impl(
        params, cfg, tokens, k_cache, v_cache, page_table, ctx_lens, new_lens,
        _xla_attention(cfg), last_only=last_only, counters=counters,
        state=state,
    )


@partial(jax.jit, static_argnames=("cfg", "last_only"),
         donate_argnames=("k0", "v0", "k1", "v1"))
def forward_hybrid(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,   # [batch, seq] int32 (padded)
    k0: jax.Array,       # group 0 (full attention): [g0_layers, pages, kvh, p, hd]
    v0: jax.Array,
    k1: jax.Array,       # group 1 (SWA): [g1_layers, swa_pages, kvh, p, hd]
    v1: jax.Array,
    table0: jax.Array,   # [batch, pages_per_seq] into group 0's pool
    table1: jax.Array,   # [batch, pages_per_seq] into group 1's pool
    ctx_lens: jax.Array,
    new_lens: jax.Array,
    last_only: bool = False,
    counters: dict | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One model step for a hybrid (mixed full/SWA) model over two
    separately-paged cache groups. XLA attention backend."""
    logits, ks, vs = _forward_impl_grouped(
        params, cfg, tokens, (k0, k1), (v0, v1), (table0, table1),
        ctx_lens, new_lens, _xla_attention(cfg), last_only=last_only,
        counters=counters,
    )
    return logits, ks[0], vs[0], ks[1], vs[1]


@partial(
    jax.jit,
    static_argnames=("cfg", "interpret", "mesh", "batch_rows"),
    donate_argnames=("k_cache", "v_cache"),
)
def forward_decode_pallas(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [batch, 1] int32
    k_cache: jax.Array,
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, pages_per_seq]
    ctx_lens: jax.Array,  # [batch]
    new_lens: jax.Array,  # [batch] 1 for live rows, 0 for padding
    interpret: bool = False,
    mesh=None,
    batch_rows: int = 1,
    counters: dict | None = None,
    state: tuple | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Decode step (seq == 1) using the Pallas flash-decode kernel.

    Same semantics as ``forward``; streaming pages HBM→VMEM in-kernel
    avoids materializing the gathered KV — the long-context win over the
    XLA reference path. ``mesh`` (tp axis) runs the kernel per-shard over
    the kv-heads sharding via ``shard_map``.
    """
    from ..ops.pallas_paged_attention import (
        pallas_paged_decode_attention, sharded_paged_decode_attention)

    sinks = cfg.attention_sinks or None
    if cfg.is_dsa and mesh is not None:
        raise NotImplementedError(
            "learned sparse attention is not sharded over a mesh")

    def dense(q, k_stack, table, lens, layer_idx):
        return pallas_paged_decode_attention(
            q[:, 0], k_stack, k_stack, table, lens, shared_kv=True,
            shared_stream=cfg.mla_decode_stream, layer_idx=layer_idx,
            interpret=interpret)

    def sparse(q, k_stack, idx_stack, table, lens, layer_idx, index):
        """Score the rows that hold more than ``index_topk`` keys over
        their own pages of the index stream, choose, gather the chosen
        latents (every key of a shorter row) into a pool of their own and
        attend that: a row of n keys reads n index keys and min(n, topk)
        latents."""
        topk = cfg.index_topk
        with jax.named_scope(SCOPE_INDEX):
            scores = sparse_index.dsa_index_scores_paged(
                *index, idx_stack, layer_idx, table,
                jnp.where(lens > topk, lens, 0), interpret=interpret)[:, 0]
        with jax.named_scope(SCOPE_SELECT):
            picked, count = sparse_index.select_topk(scores, lens, topk)
            chosen = sparse_index.gather_selected(
                k_stack, layer_idx, table, picked, count)
        with jax.named_scope(SCOPE_SPARSE_ATTENTION):
            pages = chosen.shape[0] // lens.shape[0]
            own = jnp.arange(chosen.shape[0], dtype=jnp.int32).reshape(
                lens.shape[0], pages)
            return dense(q, chosen, own, count, None)

    def pallas_attention(q, k_stack, v_stack, layer_idx, table, _positions,
                         total_lens, window, index=None):
        # The stacked operand + in-kernel layer index: a sliced cache
        # materializes a per-layer copy at the pallas custom-call
        # boundary (see ops.pallas_paged_attention._superblock_streamer).
        if index is not None:
            # Rows of at most index_topk keys attend all of them: while
            # the whole batch is such rows, today's kernel whole.
            if table.shape[1] * cfg.page_size <= cfg.index_topk:
                out = dense(q, k_stack, table, total_lens, layer_idx)
            else:
                out = jax.lax.cond(
                    jnp.max(total_lens) > cfg.index_topk,
                    lambda: sparse(q, k_stack, v_stack, table, total_lens,
                                   layer_idx, index),
                    lambda: dense(q, k_stack, table, total_lens, layer_idx))
            return out[:, None]
        if mesh is not None:
            out = sharded_paged_decode_attention(
                mesh, q[:, 0], k_stack, v_stack, table, total_lens,
                sliding_window=window, sinks=sinks, shared_kv=cfg.is_mla,
                shared_stream=cfg.mla_decode_stream,
                layer_idx=layer_idx, interpret=interpret,
            )
        else:
            out = pallas_paged_decode_attention(
                q[:, 0], k_stack, v_stack, table, total_lens,
                sliding_window=window, sinks=sinks, shared_kv=cfg.is_mla,
                shared_stream=cfg.mla_decode_stream,
                layer_idx=layer_idx, batch_rows=batch_rows,
                interpret=interpret,
            )
        return out[:, None]  # restore the seq axis

    return _forward_impl(
        params, cfg, tokens, k_cache, v_cache, page_table, ctx_lens, new_lens,
        pallas_attention, kernel={"interpret": interpret}, counters=counters,
        state=state,
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "interpret", "mesh", "last_only"),
    donate_argnames=("k_cache", "v_cache"),
)
def forward_prefill_pallas(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [batch, seq] int32 (padded)
    k_cache: jax.Array,
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, pages_per_seq]
    ctx_lens: jax.Array,
    new_lens: jax.Array,
    interpret: bool = False,
    mesh=None,
    last_only: bool = False,
    counters: dict | None = None,
    state: tuple | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill using the Pallas flash-prefill kernel.

    Same semantics as ``forward``: queries attend causally over the cached
    prefix plus themselves (clipped to the layer's sliding window when
    set, with out-of-window pages skipped), streaming pages HBM→VMEM
    in-kernel instead of materializing the gathered KV. ``mesh`` (tp axis)
    runs the kernel per-shard over the kv-heads sharding.
    """
    from ..ops.pallas_paged_attention import (
        pallas_paged_prefill_attention, sharded_paged_prefill_attention)

    seq = tokens.shape[1]
    # Query rows per program: target group·q_tile ≈ 1024 so each
    # online-softmax round is a [~1024, head_dim]×[head_dim, keys]
    # matmul: bigger tiles re-stream the KV fewer times. Every accepted
    # cell runs this rule; the ledger has no pair across tile sizes.
    # Tiny test seqs fall back to their gcd.
    group = cfg.num_heads // max(1, cfg.kv_cache_heads)
    q_tile = math.gcd(seq, max(128, 1024 // max(1, group)))
    if group * q_tile > 4096:
        # One latent head serves every query head (absorbed MLA at 128
        # heads): 128 query rows of it are 16k rows of 640 lanes, beyond
        # what a program's blocks, state and scores may hold. 2048 rows.
        q_tile = math.gcd(seq, max(16, 2048 // group))

    sinks = cfg.attention_sinks or None
    if cfg.is_dsa and mesh is not None:
        raise NotImplementedError(
            "learned sparse attention is not sharded over a mesh")

    def attention_fn(q, k_stack, v_stack, layer_idx, table, positions,
                     total_lens, window, index=None):
        # Stacked operand + in-kernel layer index: a sliced cache
        # materializes a per-layer copy at the pallas custom-call
        # boundary (see ops.pallas_paged_attention._superblock_streamer).
        bias = None
        if index is not None:
            # Every query of the chunk scores its row's keys and keeps its
            # index_topk best: dense latent attention, masked (as the
            # model's own prefill does). v_stack is the index stream; the
            # values are the latent.
            if table.shape[1] * cfg.page_size > cfg.index_topk:
                with jax.named_scope(SCOPE_INDEX):
                    scores = sparse_index.dsa_index_scores(
                        *index, sparse_index.gather_index_keys(
                            v_stack, layer_idx, table),
                        total_lens, interpret=interpret)
                with jax.named_scope(SCOPE_SELECT):
                    bias = sparse_index.dsa_keep_bias(
                        scores, positions, total_lens,
                        topk=cfg.index_topk, interpret=interpret)
            v_stack = k_stack
        if mesh is not None:
            return sharded_paged_prefill_attention(
                mesh, q, k_stack, v_stack, table, ctx_lens, total_lens,
                q_tile=q_tile, sliding_window=window,
                sinks=sinks, shared_kv=cfg.is_mla, layer_idx=layer_idx,
                interpret=interpret,
            )
        return pallas_paged_prefill_attention(
            q, k_stack, v_stack, table, ctx_lens, total_lens,
            q_tile=q_tile, sliding_window=window,
            sinks=sinks, shared_kv=cfg.is_mla, layer_idx=layer_idx,
            bias=bias, interpret=interpret,
        )

    _offer_per_head(attention_fn, cfg, seq, mesh, ctx_lens, interpret)
    return _forward_impl(
        params, cfg, tokens, k_cache, v_cache, page_table, ctx_lens, new_lens,
        attention_fn, last_only=last_only, kernel={"interpret": interpret},
        counters=counters, state=state,
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "interpret"),
    donate_argnames=("k_cache", "v_cache"),
)
def forward_ragged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [1, total_q] int32 flat mixed batch (padded)
    k_cache: jax.Array,  # [layers, pages, kvh, page_size, hd] (donated)
    v_cache: jax.Array,  # same (donated)
    page_table: jax.Array,  # [rows, pages_per_seq] int32
    row_starts: jax.Array,  # [rows+1] int32 flat-token prefix sums
    ctx_lens: jax.Array,  # [rows] tokens already cached per row
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step via the single ragged kernel.

    Row r's new tokens occupy flat slots ``[row_starts[r],
    row_starts[r+1])`` of ``tokens`` at logical positions
    ``ctx_lens[r] + i`` — a decode row is a 1-token row, a prefill chunk a
    longer one; one dispatch serves the whole mixed batch with no
    per-sequence padding (the flat axis pads only to the q-tile multiple;
    slots at and past ``row_starts[-1]`` are inert). Returns
    ``(logits [rows, vocab], k_cache, v_cache)`` — one logit row per
    ragged row, its final token (the next-token logits for both decode
    rows and a prefill chunk's last token). Single-shard only: the engine
    gates the ragged path off under tp/sp meshes and pp pipelines.
    """
    from ..ops.pallas_paged_attention import pallas_paged_ragged_attention

    total_q = tokens.shape[1]
    new_lens = row_starts[1:] - row_starts[:-1]  # [rows]
    # Ragged batches mix 1-token decode rows with long prefill chunks, so
    # the tile stays small — a decode row straddles at most one tile and
    # pays at most q_tile-1 dead query rows, while a chunk spans many
    # tiles at full occupancy.
    q_tile = math.gcd(total_q, 8)

    sinks = cfg.attention_sinks or None

    def attention_fn(q, k_stack, v_stack, layer_idx, table, positions,
                     total_lens, window):
        out = pallas_paged_ragged_attention(
            q[0], k_stack, v_stack, table, row_starts, ctx_lens,
            q_tile=q_tile, sliding_window=window, sinks=sinks,
            shared_kv=cfg.is_mla, layer_idx=layer_idx, interpret=interpret,
        )
        return out[None]

    logits, ks, vs = _forward_impl_grouped(
        params, cfg, tokens, (k_cache,), (v_cache,), (page_table,),
        ctx_lens, new_lens, attention_fn, last_only=True,
        ragged=row_starts,
    )
    return logits[0], ks[0], vs[0]


# -- step forms: a forward as the engine's ``step()`` dispatches it ----------


def pack_inputs(arrays) -> tuple[np.ndarray, tuple]:
    """A program's per-step inputs as one flat ``int32`` array, so that they
    reach the device in one transfer where each array was one, and the
    shapes that take it apart again."""
    arrays = [np.asarray(a, np.int32) for a in arrays]
    return (np.concatenate([a.ravel() for a in arrays]),
            tuple(a.shape for a in arrays))


def unpack_inputs(packed, shapes: tuple) -> list:
    """``pack_inputs`` undone: static slices, inside a program or out."""
    arrays, at = [], 0
    for shape in shapes:
        size = math.prod(shape)
        arrays.append(packed[at:at + size].reshape(shape))
        at += size
    return arrays


def step_program(body, static=(), kept_row=None):
    """The step form of a forward: the same body with its per-step inputs
    in one array and the sampling as its tail, jitted under the same name.

    ``body(params, cfg, tokens, *pools, *rest, **static) -> (logits,
    *pools)`` is a forward as written above, ``rest`` its other per-step
    arrays in its own order (the page tables, ``ctx_lens``, ``new_lens``;
    the ragged forward's ``row_starts``). The step form is called
    ``(params, cfg, packed, pools, shapes=, **static)`` with ``packed,
    shapes = pack_inputs((tokens, *rest))`` and ``pools`` the tuple of the
    body's pools (donated), and returns ``(tokens, row, pools)``: ``int32
    [rows]``, the greedy choice over the float32 logits of each row's one
    position (``[rows, 1, vocab]``: a decode step, or a chunk with
    ``last_only=True``). No logits leave the program but, with
    ``keep_row=True``, one row ``[vocab]`` (``kept_row(*rest)``'s, else
    row 0: what the last chunk of a prefill leaves in
    ``Request.last_logits``); otherwise ``row`` is None.
    ``token_sharding`` constrains the unpacked tokens
    (sequence-parallel prefill: the compute follows them).
    Where the body takes ``counters`` and the model counts anything on the
    device (``cfg.step_counters``), the counts follow the tokens in the
    same array, ``int32 [rows + len(cfg.step_counters)]``, so that they
    reach the host in the tokens' own transfer.
    ``prev``: what the last program of this form returned as its tokens,
    still on the device and not donated, for a step launched before the
    host has read them (``MiniEngine._launch_decode``). ``packed`` then ends
    in one vector more, ``src`` (``int32 [rows]``): a row takes its token
    from row ``src`` of ``prev`` (an index: a row's place may differ from
    one step to the next), or from ``packed`` as ever where ``src`` is -1.
    """
    counted = "counters" in inspect.signature(body).parameters

    def program(params, cfg, packed, pools, shapes, prev=None,
                keep_row=False, token_sharding=None, **kw):
        tokens, *rest = unpack_inputs(packed, shapes)
        if prev is not None:
            *rest, src = rest
            tokens = jnp.where(src[:, None] >= 0,
                               prev[jnp.maximum(src, 0)][:, None], tokens)
        if token_sharding is not None:
            tokens = jax.lax.with_sharding_constraint(tokens, token_sharding)
        counters = {} if counted and cfg.step_counters else None
        if counters is not None:
            kw["counters"] = counters
        out, *pools = body(params, cfg, tokens, *pools, *rest, **kw)
        if out.ndim == 3:
            if out.shape[1] != 1:
                raise ValueError(
                    f"a step form samples one position a row, got logits "
                    f"{out.shape}: pass last_only=True with a chunk")
            out = out[:, 0]
        row = None
        if keep_row:
            row = out[0 if kept_row is None else kept_row(*rest)]
        picked = greedy_tokens(out)
        if counters is not None:
            picked = jnp.concatenate([picked, jnp.stack(
                [jnp.asarray(counters.get(name, 0), jnp.int32)
                 for name in cfg.step_counters])])
        return picked, row, tuple(pools)

    program.__name__ = program.__qualname__ = body.__name__
    return jax.jit(
        program,
        static_argnames=("cfg", "shapes", "keep_row", "token_sharding",
                         *static),
        donate_argnames=("pools",))


class _Scaled:
    """Stands where the shared body looks for a matrix: the matrix with a
    scale that is applied in float32 before the result is rounded to the
    model's type. As an embedding (``[rows]``) its rows times ``scale``; as
    a projection (``x @ .``) the product's first ``upto`` columns (all of
    them: None) times ``scale``."""

    def __init__(self, w, scale, upto=None):
        self.w, self.scale, self.upto = w, scale, upto

    def __getitem__(self, rows):
        return (self.w[rows].astype(jnp.float32) * self.scale).astype(
            self.w.dtype)

    def __rmatmul__(self, x):
        y = jnp.matmul(x, self.w, preferred_element_type=jnp.float32)
        if self.upto is None:
            y = y * self.scale
        else:
            y = jnp.where(jnp.arange(y.shape[-1]) < self.upto,
                          y * self.scale, y)
        return y.astype(x.dtype)


class _Summed:
    """Stands where ``_sublayer_out`` looks for the output projection of a
    layer of two mixers: handed ``[m | a]`` (``_parallel_block``) it answers
    ``scale_m (m @ w_m) + scale_a (a @ w_a)``, each product scaled in
    float32 and the sum rounded once to the model's type."""

    def __init__(self, *parts):
        self.parts = parts  # (matrix [rows, h], scale) in the halves' order

    def __rmatmul__(self, x):
        at, y = 0, 0.0
        for w, scale in self.parts:
            y = y + scale * jnp.matmul(x[..., at:at + w.shape[0]], w,
                                       preferred_element_type=jnp.float32)
            at += w.shape[0]
        return y.astype(x.dtype)


def multiplied(params: Params, cfg: LlamaConfig) -> Params:
    """``params`` as the shared body reads them under Granite's scalars
    (``LlamaConfig.embedding_multiplier`` ...): the embedding's rows times
    ``embedding_multiplier``, the logits over ``logits_scaling``, and in
    the layers that attend the queries times ``attention_multiplier *
    head_dim ** 0.5``, since the attention kernels scale scores by
    ``head_dim ** -0.5`` themselves (``wq``, or the query columns of a fused
    ``w_qkv``). Under Falcon-H1's as well: a Mamba-2 mixer's ``w_in`` times
    ``ssm_in_multiplier`` and, a column, its part's ``ssm_multipliers``; a
    dense MLP's gate and output times ``mlp_multipliers``; and a parallel
    layer's ``wo`` as the two mixers' output projections under their own
    multipliers (``_Summed``). Each in float32 on the product, before it is
    rounded. ``params`` itself for every other model: its programs hold
    nothing of this."""
    if not cfg.has_multipliers:
        return params
    view = {**params,
            "embed": _Scaled(params["embed"], cfg.embedding_multiplier),
            "lm_head": _Scaled(params["lm_head"], 1.0 / cfg.logits_scaling)}
    q_scale = cfg.attention_multiplier * cfg.head_dim ** 0.5
    ssm_in = None
    if cfg.ssm_multipliers or cfg.ssm_in_multiplier != 1.0:
        la = cfg.linear
        groups = la.key_heads * la.key_dim
        ssm_in = cfg.ssm_in_multiplier * np.repeat(
            np.asarray(cfg.ssm_multipliers or (1.0,) * 5, np.float32),
            (la.inner, la.inner, groups, groups, la.value_heads))
    layers = []
    for layer in params["layers"]:
        seen = {}  # what this layer shows in another matrix's place
        if q_scale and "wq" in layer:
            seen["wq"] = _Scaled(layer["wq"], q_scale)
        elif q_scale and "w_qkv" in layer:
            seen["w_qkv"] = _Scaled(layer["w_qkv"], q_scale,
                                    cfg.num_heads * cfg.head_dim)
        if ssm_in is not None and "w_in" in layer:
            seen["w_in"] = _Scaled(layer["w_in"], ssm_in)
        if cfg.mlp_multipliers and "router" not in layer:
            gate, out = cfg.mlp_multipliers
            if "w_gate_up" in layer:
                seen["w_gate_up"] = _Scaled(
                    layer["w_gate_up"], gate, layer["w_gate_up"].shape[1] // 2)
            else:
                seen["w_gate"] = _Scaled(layer["w_gate"], gate)
            seen["w_down"] = _Scaled(layer["w_down"], out)
        if "w_ssm_out" in layer:
            seen["wo"] = _Summed(
                (layer["w_ssm_out"], cfg.ssm_out_multiplier),
                (layer["wo"], cfg.attention_out_multiplier))
        layers.append({**layer, **seen} if seen else layer)
    view["layers"] = layers
    return view


def _last_ragged_row(_table, row_starts, _ctx_lens):
    """The last row that holds tokens: the prefill chunk, when a ragged
    step carries one."""
    return jnp.sum(row_starts[1:] > row_starts[:-1]) - 1


def with_state(body):
    """``body`` (a forward above) as a model with linear layers is stepped:
    its state pool behind the page pools (donated with them), each row's
    slot and the chunk's snapshot request (``[block, slot, end_slot]``;
    zeros on a decode step) behind its per-step arrays; the parameters as
    ``multiplied`` views them (themselves, but for a model with Granite's
    scalars). Under the body's own name: a trace tells programs by it."""
    def stateful(params, cfg, tokens, k_cache, v_cache, recurrent, conv,
                 page_table, ctx_lens, new_lens, slots, snap, counters=None,
                 **kw):
        logits, k_cache, v_cache, (recurrent, conv) = body(
            multiplied(params, cfg), cfg, tokens, k_cache, v_cache,
            page_table, ctx_lens,
            new_lens, counters=counters,
            state=(recurrent, conv, slots,
                   snap if tokens.shape[1] > 1 else None), **kw)
        return logits, k_cache, v_cache, recurrent, conv

    stateful.__name__ = stateful.__qualname__ = body.__name__
    return stateful


def with_pages_in_state(body):
    """``with_state(body)`` for a model whose layers keep a state AND pages
    (``cfg.parallel_layers``). The shared body runs such a layer as a
    linear one and touches no page, so the page pools and the rows' page
    table ride where the recurrent pool does in the state it is handed,
    from layer to layer through ``_parallel_block`` and back; the pools the
    body itself hands back are the ones it was given and are dropped."""
    stateful = with_state(body)

    def paged(params, cfg, tokens, k_cache, v_cache, recurrent, conv,
              page_table, *rest, counters=None, **kw):
        logits, _, _, (recurrent, k_cache, v_cache, _), conv = stateful(
            params, cfg, tokens, k_cache, v_cache,
            (recurrent, k_cache, v_cache, page_table), conv, page_table,
            *rest, counters=counters, **kw)
        return logits, k_cache, v_cache, recurrent, conv

    paged.__name__ = paged.__qualname__ = body.__name__
    return paged


@partial(jax.jit, donate_argnames=("state",))
def copy_state_slot(state: tuple, src_dst: jax.Array) -> tuple:
    """Slot ``src_dst[0]`` of every pool of ``state`` (``init_state_pool``)
    copied over slot ``src_dst[1]``, in place: a snapshot into the working
    slot of the row that was admitted on it."""
    return tuple(pool.at[:, src_dst[1]].set(pool[:, src_dst[0]])
                 for pool in state)


step_forward = step_program(forward.__wrapped__, ("last_only",))
step_forward_state = step_program(
    with_state(forward.__wrapped__), ("last_only",))
step_decode_pallas_state = step_program(
    with_state(forward_decode_pallas.__wrapped__),
    ("interpret", "mesh", "batch_rows"))
step_prefill_pallas_state = step_program(
    with_state(forward_prefill_pallas.__wrapped__),
    ("interpret", "mesh", "last_only"))
step_forward_paged_state = step_program(
    with_pages_in_state(forward.__wrapped__), ("last_only",))
step_decode_pallas_paged_state = step_program(
    with_pages_in_state(forward_decode_pallas.__wrapped__),
    ("interpret", "mesh", "batch_rows"))
step_prefill_pallas_paged_state = step_program(
    with_pages_in_state(forward_prefill_pallas.__wrapped__),
    ("interpret", "mesh", "last_only"))
step_forward_hybrid = step_program(
    forward_hybrid.__wrapped__, ("last_only",))
step_decode_pallas = step_program(
    forward_decode_pallas.__wrapped__, ("interpret", "mesh", "batch_rows"))
step_prefill_pallas = step_program(
    forward_prefill_pallas.__wrapped__, ("interpret", "mesh", "last_only"))
step_ragged = step_program(
    forward_ragged.__wrapped__, ("interpret",), kept_row=_last_ragged_row)


# -- a latent layer's attention: absorbed, or per head ----------------------
# Everything of the per-head form lives down here, in functions of their
# own, and reaches the shared body as an attribute of ``attention_fn``: no
# new parameter, local or longer expression in ``_forward_impl_grouped``,
# ``_forward_impl`` or ``forward_prefill_pallas``, and the latter's
# ``attention_fn`` left as it was (the selection's lines stand twice). Those
# frames are under every op of every program of every model while it is
# traced, and their sizes decide which call sites of tracing and lowering
# fall on the edge of one of the interpreter's 16 KiB frame chunks: two slots
# more in each cost the dense cells, which run none of this, 2-4 s of set-up
# (PERF.md §6, PR 47; ``hack/frame_sizes.py`` compares two trees).


def _latent_attention(attention_fn, queries, layer, *pages_and_lens, **extra):
    """The heads' values ``[b, seq, heads, v]`` of a latent-attention
    layer from ``queries = (q_eff, q_nope, q_rope)``. Absorbed:
    ``attention_fn`` on the absorbed query ``q_eff`` over the latent, the
    context un-absorbed through ``w_uv``. Per head, where ``attention_fn``
    brings a ``per_head_fn`` (``_offer_per_head``): that, on the heads' own
    queries."""
    q_eff, q_nope, q_rope = queries
    per_head_fn = getattr(attention_fn, "per_head_fn", None)
    if per_head_fn is not None:
        return per_head_fn(q_nope, q_rope, layer, *pages_and_lens, **extra)
    ctx = attention_fn(q_eff, *pages_and_lens, None, **extra)
    return jnp.einsum("bshr,hrv->bshv", ctx[..., :layer["w_uv"].shape[1]],
                      layer["w_uv"])


def prefill_per_head(cfg: LlamaConfig, seq: int, mesh=None) -> bool:
    """Whether ``forward_prefill_pallas`` attends a chunk padded to ``seq``
    queries per head, on keys and values expanded from the latents inside
    the kernel (``ops.pallas_latent_prefill``), and not in the absorbed
    form: a latent model, no mesh, and queries enough that expanding a key
    once a head costs less than multiplying the page's width for every
    query. From shapes alone; a program holds one of the two kernels."""
    from ..ops.pallas_latent_prefill import per_head_min_queries

    return (cfg.is_mla and mesh is None
            and seq >= per_head_min_queries(
                cfg.kv_cache_head_dim, cfg.kv_lora_rank, cfg.head_dim,
                cfg.head_dim))


def _offer_per_head(attention_fn, cfg, seq, mesh, ctx_lens, interpret):
    """Hang the chunk's per-head form on ``forward_prefill_pallas``'s
    ``attention_fn`` where the rule (``prefill_per_head``) takes it."""
    if prefill_per_head(cfg, seq, mesh):
        attention_fn.per_head_fn = partial(
            _per_head_prefill_attention, cfg=cfg, ctx_lens=ctx_lens,
            interpret=interpret)


def _per_head_prefill_attention(q_nope, q_rope, layer, k_stack, v_stack,
                                layer_idx, table, positions, total_lens,
                                index=None, *, cfg, ctx_lens, interpret,
                                bias=None):
    from ..ops.pallas_latent_prefill import pallas_per_head_prefill_attention

    if index is not None and table.shape[1] * cfg.page_size > cfg.index_topk:
        # The chunk's selection, as the absorbed form's ``attention_fn``
        # makes it: v_stack is the index stream.
        with jax.named_scope(SCOPE_INDEX):
            scores = sparse_index.dsa_index_scores(
                *index, sparse_index.gather_index_keys(
                    v_stack, layer_idx, table),
                total_lens, interpret=interpret)
        with jax.named_scope(SCOPE_SELECT):
            bias = sparse_index.dsa_keep_bias(
                scores, positions, total_lens, topk=cfg.index_topk,
                interpret=interpret)
    return pallas_per_head_prefill_attention(
        q_nope, q_rope, layer["w_uk"], layer["w_uv"], k_stack, table,
        ctx_lens, total_lens,
        scale=((cfg.head_dim + cfg.qk_rope_head_dim) ** -0.5
               * cfg.softmax_scale_mult),
        layer_idx=layer_idx, bias=bias, interpret=interpret)


# -- a model that drafts: its prediction module, and the steps that run it ---
# A model with ``cfg.num_nextn_predict_layers`` is stepped by programs of
# their own (``drafting_step_program``), under the names of the forwards they
# stand for. Everything of them lives down here, in functions of their own:
# the shared body above is called as it is, on views of the parameters, and
# no frame that lies under another model's programs changes (see the section
# above).
#
# Main position ``i`` (token ``t_i``, hidden state ``h_i`` after the final
# norm) gives the module the row ``u_i = [norm_e(Emb(t_{i+1})) ; norm_h(h_i)]
# W_eh``; the module's block attends ``u_0..u_i`` and its head (the model's
# own, behind the module's norm) gives the logits of ``t_{i+2}``. The block's
# latent of ``u_i`` is written at slot ``i + 1`` of the row's pages, the slot
# of the last token it was computed from, and rotated as that position
# (RoPE's scores depend on distances alone): every slot of a page, in all its
# layers, is then a function of the tokens up to that slot, which is what a
# block's hash covers, and a prefix hit reads the module's cache as it reads
# the model's. Slot 0 holds nothing of the module and its layer never
# attends it (``first_key``, a bias over a chunk's keys).

SCOPE_DRAFT = "mtp_draft"


class _HeadTap:
    """Stands where the shared body looks for ``lm_head``: keeps what the
    head is handed, the hidden states after the final norm ``[b, seq, h]``,
    for the prediction module, and answers with the logits of position ``at
    [b]`` of every row alone (None: of every position)."""

    def __init__(self, head, at=None):
        self.head, self.at, self.hidden = head, at, None

    def __rmatmul__(self, x):
        self.hidden = x
        if self.at is not None:
            x = jnp.take_along_axis(x, self.at[:, None, None], axis=1)
        return x @ self.head


def _module_view(params: Params, cfg: LlamaConfig, rows: jax.Array):
    """The prediction module as the shared body runs it: a model of one
    layer whose "embedding" is the module's input ``rows [n, h]`` (looked up
    by row number), with the module's norm ahead of the model's own head.
    Its one layer is layer 0 of the pool (``LlamaConfig.page_layers``)."""
    module = params["mtp"]
    view = {"layers": [module["layer"]], "embed": rows,
            "final_norm": module["final_norm"], "lm_head": params["lm_head"]}
    return view, replace(cfg, num_layers=1, num_nextn_predict_layers=0,
                         moe_layers=())


def _past_first(q, table, page_size):
    """bool ``[b, seq, keys]``: every key of a row's pages but slot 0."""
    keys = table.shape[1] * page_size
    return jnp.broadcast_to(jnp.arange(keys) >= 1, (*q.shape[:2], keys))


def _module_attention(cfg, backend, ctx_lens, new_lens, seq, interpret):
    """The module's layer's ``attention_fn`` on ``backend`` (``"xla"``,
    ``"decode"``: the Pallas decode kernel over the step's positions,
    ``"prefill"``: the chunk's kernels), over slots 1 and up of the row's
    pages. ``ctx_lens``: the slot the first of the ``seq`` rows is written
    at."""
    if backend == "decode":
        return _positions_attention(cfg, ctx_lens, new_lens, seq, interpret,
                                    first_key=1)
    if backend == "xla":
        def attention_fn(q, k_stack, v_stack, layer_idx, table, positions,
                         total_lens, window):
            return paged_attention(
                q, k_stack, v_stack, table, positions, total_lens,
                layer_idx=layer_idx,
                keep=_past_first(q, table, cfg.page_size))
        return attention_fn

    from ..ops.pallas_paged_attention import pallas_paged_prefill_attention

    def bias(q, table):
        return jnp.where(_past_first(q, table, cfg.page_size), 0.0, -1e30)

    def attention_fn(q, k_stack, v_stack, layer_idx, table, positions,
                     total_lens, window):
        return pallas_paged_prefill_attention(
            q, k_stack, v_stack, table, ctx_lens, total_lens,
            q_tile=_prefill_q_tile(cfg, seq), shared_kv=True,
            layer_idx=layer_idx, bias=bias(q, table), interpret=interpret)

    def per_head(q_nope, q_rope, layer, k_stack, v_stack, layer_idx, table,
                 positions, total_lens):
        return _per_head_prefill_attention(
            q_nope, q_rope, layer, k_stack, v_stack, layer_idx, table,
            positions, total_lens, cfg=cfg, ctx_lens=ctx_lens,
            interpret=interpret, bias=bias(q_nope, table))

    if prefill_per_head(cfg, seq):
        attention_fn.per_head_fn = per_head
    return attention_fn


def _prefill_q_tile(cfg: LlamaConfig, seq: int) -> int:
    """``forward_prefill_pallas``'s rule for a chunk's query tile (its own
    lines stand there: that frame is under every model's programs), with
    the rows a group may take rounded down to a power of two: the same
    tile for 1, 2, 4, ... query heads a key/value head, and for 5 (20 over
    4) a tile of 128 where the gcd of 512 and 1024 // 5 would leave 4."""
    group = cfg.num_heads // max(1, cfg.kv_cache_heads)
    q_tile = math.gcd(seq, max(128, 1 << (
        (1024 // max(1, group)).bit_length() - 1)))
    if group * q_tile > 4096:
        q_tile = math.gcd(seq, max(16, 2048 // group))
    return q_tile


def _positions_attention(cfg, ctx_lens, new_lens, seq, interpret,
                         first_key=0):
    """An ``attention_fn`` over the Pallas decode kernel for rows of ``seq``
    positions each: position ``j`` of a live row attends the keys below
    ``ctx_lens + j + 1`` (from ``first_key`` on), the row's latents streamed
    once for all of them. A row with nothing new streams nothing."""
    from ..ops.pallas_paged_attention import pallas_paged_decode_attention

    lens = jnp.where(new_lens > 0, ctx_lens + seq, 0)

    def attention_fn(q, k_stack, v_stack, layer_idx, table, positions,
                     total_lens, window):
        return pallas_paged_decode_attention(
            q, k_stack, k_stack, table, lens, shared_kv=True,
            shared_stream=cfg.mla_decode_stream, layer_idx=layer_idx,
            first_key=first_key, interpret=interpret)
    return attention_fn


def draft_logits(params, cfg, hidden, next_tokens, k_cache, v_cache,
                 page_table, ctx_lens, new_lens, backend, interpret=False,
                 counters=None, last_only=True):
    """The prediction module over ``seq`` main positions a row from
    ``ctx_lens`` on (the first ``new_lens`` of them real): ``hidden [b, seq,
    h]`` the main model's hidden states there (after its final norm),
    ``next_tokens [b, seq]`` the token after each. Returns ``(float32 logits
    of the token after next [b, 1, vocab] at each row's last real position
    (``last_only``; else of every position), k_cache, v_cache)`` with the
    module's latents written at slots ``ctx_lens + 1`` and up of layer 0."""
    module = params["mtp"]
    b, seq, h = hidden.shape
    with jax.named_scope(SCOPE_DRAFT):
        rows = jnp.concatenate(
            [_rms_norm(params["embed"][next_tokens], module["enorm"],
                       cfg.norm_eps, cfg.norm_offset),
             _rms_norm(hidden, module["hnorm"], cfg.norm_eps,
                       cfg.norm_offset)], axis=-1) @ module["w_eh"]
        view, view_cfg = _module_view(params, cfg, rows.reshape(b * seq, h))
        return _forward_impl(
            view, view_cfg, jnp.arange(b * seq).reshape(b, seq), k_cache,
            v_cache, page_table, ctx_lens + 1, new_lens,
            _module_attention(cfg, backend, ctx_lens + 1, new_lens, seq,
                              interpret),
            last_only=last_only,
            kernel=None if backend == "xla" else {"interpret": interpret},
            counters=counters)


def _main_forward(params, cfg, tokens, k_cache, v_cache, page_table,
                  ctx_lens, new_lens, backend, interpret, counters, at=None):
    """The main model over ``tokens [b, seq]`` on ``backend``: ``(float32
    logits (of position ``at [b]`` alone, or of every position), hidden
    states after the final norm [b, seq, h], k_cache, v_cache)``."""
    tap = _HeadTap(params["lm_head"], at)
    view = {**params, "lm_head": tap}
    if backend == "xla":
        out = forward.__wrapped__(
            view, cfg, tokens, k_cache, v_cache, page_table, ctx_lens,
            new_lens, counters=counters)
    elif backend == "prefill":
        out = forward_prefill_pallas.__wrapped__(
            view, cfg, tokens, k_cache, v_cache, page_table, ctx_lens,
            new_lens, interpret=interpret, counters=counters)
    else:
        out = _forward_impl(
            view, cfg, tokens, k_cache, v_cache, page_table, ctx_lens,
            new_lens, _positions_attention(
                cfg, ctx_lens, new_lens, tokens.shape[1], interpret),
            kernel={"interpret": interpret}, counters=counters)
    logits, k_cache, v_cache = out
    return logits, tap.hidden, k_cache, v_cache


def _verify_and_draft(params, cfg, tokens, k_cache, v_cache, page_table,
                      ctx_lens, new_lens, drafts, backend, interpret,
                      counters):
    """One speculative decode step. A live row (``new_lens`` 1) holds its
    last accepted token ``tokens [b, 1]`` at position ``ctx_lens`` and the
    module's draft of the next. The main model runs both positions against
    the row's pages; its own choices ``g1, g2`` there are what the row
    emits: both where the draft was ``g1`` (the second position then saw the
    right token), else ``g1`` alone, and the draft's latents at position
    ``ctx_lens + 1`` stay behind to be written over by the next step. The
    module then runs over the position(s) emitted and leaves the next draft.
    Returns ``(int32 [4, b]: g1, g2, how many of them count, the next draft;
    k_cache, v_cache)``."""
    logits, hidden, k_cache, v_cache = _main_forward(
        params, cfg, jnp.concatenate([tokens, drafts[:, None]], axis=1),
        k_cache, v_cache, page_table, ctx_lens, 2 * new_lens, backend,
        interpret, counters)
    chosen = greedy_tokens(logits).T                              # [2, b]
    if backend == "xla":
        count = (new_lens > 0) * (1 + (chosen[0] == drafts))
    else:
        # The kernels' programs: acceptance is the named op a trace tells
        # the module's part of the step by (``ops.draft_accept``).
        from ..ops.draft_accept import mtp_accept

        chosen, count = mtp_accept(chosen, drafts, new_lens > 0,
                                   interpret=interpret)
    draft, k_cache, v_cache = draft_logits(
        params, cfg, hidden, chosen.T, k_cache, v_cache, page_table,
        ctx_lens, count, backend, interpret, counters)
    return (jnp.concatenate([chosen, count[None],
                             greedy_tokens(draft[:, 0])[None]]),
            k_cache, v_cache)


def _chunk_and_draft(params, cfg, tokens, k_cache, v_cache, page_table,
                     ctx_lens, new_lens, following, backend, interpret,
                     counters):
    """One prefill chunk of a drafting model: the main model over the
    chunk, sampling its last position, and the module over the same
    positions, each with the token after it: the chunk's next, then
    ``following [b]``, the prompt's token after the chunk, or where that is
    -1 (the prompt ends here) the token just sampled, so that the module's
    cache stands at the prompt's end and the first draft leaves with the
    first token. Returns ``(int32 [2, b]: the token sampled, the draft of
    the one after it; the sampled position's logits [b, vocab]; k_cache,
    v_cache)``."""
    last = jnp.maximum(new_lens - 1, 0)
    logits, hidden, k_cache, v_cache = _main_forward(
        params, cfg, tokens, k_cache, v_cache, page_table, ctx_lens, new_lens,
        backend, interpret, counters, at=last)
    picked = greedy_tokens(logits[:, 0])
    after = jnp.where(
        jnp.arange(tokens.shape[1])[None, :] == last[:, None],
        jnp.where(following >= 0, following, picked)[:, None],
        jnp.roll(tokens, -1, axis=1))
    draft, k_cache, v_cache = draft_logits(
        params, cfg, hidden, after, k_cache, v_cache, page_table, ctx_lens,
        new_lens, backend, interpret, counters)
    return (jnp.stack([picked, greedy_tokens(draft[:, 0])]), logits[:, 0],
            k_cache, v_cache)


def drafting_step_program(name: str, backend: str, chunk: bool):
    """The step form of a drafting model's forward: what ``step_program`` is
    to the others, jitted under ``name`` (the forward it stands for: a trace
    tells programs by it). Called ``(params, cfg, packed, pools, shapes=,
    interpret=)`` with ``pools = (k_cache, v_cache)``, donated.

    ``chunk``: a prefill chunk. ``packed`` holds ``(tokens [1, seq], the page
    table, ctx_lens, new_lens, following)`` and the result is ``(int32 [2 +
    counters]: the sampled token, the first draft, what the model counts;
    the sampled position's logits [vocab]; pools)``
    (``_chunk_and_draft``).

    Else a decode step (``_verify_and_draft``). ``packed`` holds ``(tokens
    [rows, 1], the page table, ctx_lens, new_lens, drafts, src)`` and the
    result is ``(int32 [4 * rows + counters]: every row's first token, then
    every row's second, how many count, the next drafts; None; pools)``.
    ``prev`` is what the last program of this form returned, still on the
    device: where ``src`` is not -1 a row takes from row ``src`` of it what
    the host has not read yet: its last token (the second where two count),
    its draft, and that many positions more of context."""
    def program(params, cfg, packed, pools, shapes, prev=None,
                interpret=False):
        tokens, table, ctx_lens, new_lens, *rest = unpack_inputs(
            packed, shapes)
        counters = {} if cfg.step_counters else None
        row = None
        if chunk:
            picked, row, *pools = _chunk_and_draft(
                params, cfg, tokens, *pools, table, ctx_lens, new_lens,
                rest[0], backend, interpret, counters)
            row = row[0]
        else:
            drafts, src = rest
            rows = src.shape[0]
            at = jnp.maximum(src, 0)
            two = prev[2 * rows + at] == 2
            taken = src >= 0
            tokens = jnp.where(
                taken, jnp.where(two, prev[rows + at], prev[at]),
                tokens[:, 0])[:, None]
            drafts = jnp.where(taken, prev[3 * rows + at], drafts)
            ctx_lens = ctx_lens + jnp.where(taken, prev[2 * rows + at], 0)
            picked, *pools = _verify_and_draft(
                params, cfg, tokens, *pools, table, ctx_lens, new_lens,
                drafts, backend, interpret, counters)
        picked = picked.reshape(-1)
        if counters is not None:
            picked = jnp.concatenate([picked, jnp.stack(
                [jnp.asarray(counters.get(n, 0), jnp.int32)
                 for n in cfg.step_counters])])
        return picked, row, tuple(pools)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, static_argnames=("cfg", "shapes", "interpret"),
                   donate_argnames=("pools",))


# ``(pallas, chunk)`` -> the program: the XLA forward serves both phases of
# an engine without the kernels, as ``step_forward`` does.
DRAFTING_PROGRAMS = {
    (False, True): drafting_step_program("forward", "xla", True),
    (False, False): drafting_step_program("forward", "xla", False),
    (True, True): drafting_step_program(PROGRAM_PREFILL, "prefill", True),
    (True, False): drafting_step_program(PROGRAM_DECODE, "decode", False),
}


# -- a window pool beside a global pool, through the kernels ----------------
# A model of window layers and full layers (``cfg.is_hybrid``) keeps two
# page pools, each with its own page table; the kernels take one pool, one
# table and one window a call, and the shared body already hands each layer
# its own group's. These are the two programs that join them, as forms of
# their own: ``forward_decode_pallas`` / ``forward_prefill_pallas`` and the
# frames under every other model's programs stay what they are (their
# ``attention_fn``s' kernel calls stand here again, without the mesh, the
# sinks and the latent forms a two-pool model is refused with). A slot of
# the window pool's table that fell out of the window points at the garbage
# page; the kernels never attend it. Under the pinned programs' names: a
# trace tells programs by them.


def forward_decode_pallas_pools(params, cfg, tokens, k0, v0, k1, v1, table0,
                                table1, ctx_lens, new_lens, interpret=False,
                                batch_rows=1, counters=None):
    """``forward_decode_pallas`` over group 0's pool (``k0``, ``v0``,
    ``table0``: the full layers) and group 1's (the window layers)."""
    from ..ops.pallas_paged_attention import pallas_paged_decode_attention

    def attention_fn(q, k_stack, v_stack, layer_idx, table, _positions,
                     total_lens, window):
        return pallas_paged_decode_attention(
            q[:, 0], k_stack, v_stack, table, total_lens,
            sliding_window=window, layer_idx=layer_idx,
            batch_rows=batch_rows, interpret=interpret)[:, None]

    logits, ks, vs = _forward_impl_grouped(
        params, cfg, tokens, (k0, k1), (v0, v1), (table0, table1), ctx_lens,
        new_lens, attention_fn, kernel={"interpret": interpret},
        counters=counters)
    return logits, ks[0], vs[0], ks[1], vs[1]


def forward_prefill_pallas_pools(params, cfg, tokens, k0, v0, k1, v1, table0,
                                 table1, ctx_lens, new_lens, interpret=False,
                                 last_only=False, counters=None):
    """``forward_prefill_pallas`` over the two pools, as above."""
    from ..ops.pallas_paged_attention import pallas_paged_prefill_attention

    q_tile = _prefill_q_tile(cfg, tokens.shape[1])

    def attention_fn(q, k_stack, v_stack, layer_idx, table, _positions,
                     total_lens, window):
        return pallas_paged_prefill_attention(
            q, k_stack, v_stack, table, ctx_lens, total_lens, q_tile=q_tile,
            sliding_window=window, layer_idx=layer_idx, interpret=interpret)

    logits, ks, vs = _forward_impl_grouped(
        params, cfg, tokens, (k0, k1), (v0, v1), (table0, table1), ctx_lens,
        new_lens, attention_fn, last_only=last_only,
        kernel={"interpret": interpret}, counters=counters)
    return logits, ks[0], vs[0], ks[1], vs[1]


forward_decode_pallas_pools.__name__ = PROGRAM_DECODE
forward_prefill_pallas_pools.__name__ = PROGRAM_PREFILL
step_decode_pallas_pools = step_program(
    forward_decode_pallas_pools, ("interpret", "batch_rows"))
step_prefill_pallas_pools = step_program(
    forward_prefill_pallas_pools, ("interpret", "last_only"))
