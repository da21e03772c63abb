"""Pipeline parallelism: layer-sharded training over a ``pp`` mesh axis.

Two schedules:

1. **Sequential stacked scan** (``make_pp_train_step``): layers stacked
   into leading-axis arrays, scanned, the layer axis sharded over ``pp``.
   Distributes parameters + optimizer state across stages; the whole
   batch flows through the stages one layer-block at a time, so the
   bubble fraction is (P-1)/P. Composes with dp AND tp.

2. **Microbatched rotating-buffer pipeline**
   (``make_pp_pipelined_train_step``): an explicit shard_map schedule —
   the batch splits into M microbatches that stream through the stages,
   activations hopping stage→stage via ``ppermute`` each tick, so up to P
   microbatches are in flight at once and the bubble fraction drops to
   (P-1)/(M+P-1) (``pipeline_bubble_fraction``). This is the SPMD
   formulation of pipelined microbatching on TPU (collectives ride ICI;
   the autodiff transpose replays the schedule in reverse, so memory is
   GPipe-shaped: all forwards live until backwards drain —
   ``remat=True`` rematerializes each tick's forward in the backward
   pass, bounding live activations to the rotating buffer at the cost of
   one extra forward). Composes with dp AND tp: inside shard_map, XLA
   cannot derive collectives from sharding annotations, so the tp path is
   hand-written Megatron — column-parallel wq/wk/wv/w_gate/w_up on local
   heads/columns, ``psum`` after the row-parallel wo/w_down, a
   vocab-parallel embedding (mask + psum) and a vocab-parallel
   cross-entropy (``pmax``/``psum`` log-sum-exp) over the tp-sharded
   lm_head.

Dense layers only (MoE layers scale across ``ep`` instead).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig, Params, _mlp, _rms_norm


def stack_layer_params(params: Params) -> dict:
    """Convert the per-layer list tree into stacked [L, ...] arrays."""
    layers = params["layers"]
    stacked = {
        key: jnp.stack([layer[key] for layer in layers])
        for key in layers[0]
    }
    return {
        "embed": params["embed"],
        "layers_stacked": stacked,
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
    }


def unstack_layer_params(stacked_params: dict) -> Params:
    """Inverse of ``stack_layer_params`` (checkpoint interop)."""
    stacked = stacked_params["layers_stacked"]
    num_layers = next(iter(stacked.values())).shape[0]
    layers = [
        {key: stacked[key][i] for key in stacked} for i in range(num_layers)
    ]
    return {
        "embed": stacked_params["embed"],
        "layers": layers,
        "final_norm": stacked_params["final_norm"],
        "lm_head": stacked_params["lm_head"],
    }


def stacked_param_pspecs(has_tp: bool, pp_axis: Optional[str],
                         qk_norm: bool = False) -> dict:
    """PartitionSpecs for the stacked tree: layer axis over ``pp``, the
    Megatron tp layout within each layer."""
    tp = "tp" if has_tp else None
    qk = ({"q_norm": P(pp_axis, None), "k_norm": P(pp_axis, None)}
          if qk_norm else {})
    return {
        "embed": P(tp, None),
        "layers_stacked": {
            **qk,
            "attn_norm": P(pp_axis, None),
            "wq": P(pp_axis, None, tp),
            "wk": P(pp_axis, None, tp),
            "wv": P(pp_axis, None, tp),
            "wo": P(pp_axis, tp, None),
            "mlp_norm": P(pp_axis, None),
            "w_gate": P(pp_axis, None, tp),
            "w_up": P(pp_axis, None, tp),
            "w_down": P(pp_axis, tp, None),
        },
        "final_norm": P(),
        "lm_head": P(None, tp),
    }


def forward_train_pp(stacked_params: dict, cfg: LlamaConfig,
                     tokens: jax.Array) -> jax.Array:
    """Causal-LM forward scanning stacked (pipeline-sharded) layers.

    The per-layer body is ``_scan_layers`` (``train.attention_block`` +
    ``_mlp``) — shared with the pipelined schedule and the python-loop
    formulation so the paths cannot drift.
    """
    batch, seq = tokens.shape
    positions = jnp.arange(seq)[None, :].repeat(batch, axis=0)
    x = stacked_params["embed"][tokens]
    x = _scan_layers(stacked_params["layers_stacked"], cfg, x, positions)
    x = _rms_norm(x, stacked_params["final_norm"], cfg.norm_eps)
    return (x @ stacked_params["lm_head"]).astype(jnp.float32)


def pp_loss_fn(stacked_params, cfg, tokens):
    logits = forward_train_pp(stacked_params, cfg, tokens)
    targets = tokens[:, 1:]
    logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


@partial(jax.jit, static_argnames=("cfg", "opt"))
def pp_train_step(stacked_params, opt_state, cfg: LlamaConfig,
                  opt: optax.GradientTransformation, tokens: jax.Array):
    loss, grads = jax.value_and_grad(pp_loss_fn)(stacked_params, cfg, tokens)
    updates, opt_state = opt.update(grads, opt_state, stacked_params)
    stacked_params = optax.apply_updates(stacked_params, updates)
    return stacked_params, opt_state, loss


def pipeline_bubble_fraction(pp_size: int, num_microbatches: int) -> float:
    """Idle fraction of the microbatched schedule: (P-1)/(M+P-1). The
    sequential stacked scan is the M=1 case, (P-1)/P."""
    return (pp_size - 1) / (num_microbatches + pp_size - 1)


def _scan_layers(layers_stacked, cfg: LlamaConfig, x: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """Scan a stacked layer slab over activations ``x`` — the ONE per-layer
    body shared by the sequential and pipelined schedules (and built from
    ``train.attention_block`` + ``_mlp`` so the python-loop formulation
    cannot drift either)."""
    from .train import attention_block

    def layer_step(x, layer):
        x = x + attention_block(x, layer, cfg, positions)
        mlp_in = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        x = x + _mlp(mlp_in, layer, cfg)
        return x, None

    x, _ = jax.lax.scan(layer_step, x, layers_stacked)
    return x


def _tp_embed(embed_local: jax.Array, token_ids: jax.Array,
              tp_axis: str) -> jax.Array:
    """Vocab-parallel embedding lookup: each tp shard holds a contiguous
    row slice; out-of-slice ids contribute zero and the ``psum`` assembles
    the full vectors (Megatron VocabParallelEmbedding)."""
    rows = embed_local.shape[0]
    shard = jax.lax.axis_index(tp_axis)
    local_ids = token_ids - shard * rows
    ok = (local_ids >= 0) & (local_ids < rows)
    e = embed_local[jnp.clip(local_ids, 0, rows - 1)]
    return jax.lax.psum(jnp.where(ok[..., None], e, 0), tp_axis)


def _tp_layer_step(x: jax.Array, layer: dict, cfg: LlamaConfig,
                   positions: jax.Array, tp_axis: str) -> jax.Array:
    """One dense layer on tp-local weight shards with explicit collectives.

    Column-parallel wq/wk/wv give each shard ``num_heads/tp`` query heads
    (heads are attention-independent, so no collective until the output
    projection); the row-parallel wo/w_down products are partial sums over
    the hidden/intermediate slices, fixed by one ``psum`` each — the
    hand-written form of what XLA derives from sharding annotations in the
    sequential schedule.
    """
    from ..models.llama import _rope

    batch, seq = x.shape[0], x.shape[1]
    attn_in = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = (attn_in @ layer["wq"]).reshape(batch, seq, -1, cfg.head_dim)
    k = (attn_in @ layer["wk"]).reshape(batch, seq, -1, cfg.head_dim)
    v = (attn_in @ layer["wv"]).reshape(batch, seq, -1, cfg.head_dim)
    if cfg.qk_norm:  # Qwen3: per-head RMS over head_dim, pre-RoPE
        q = _rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.norm_eps)
    q = _rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = _rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if cfg.num_heads != cfg.num_kv_heads:
        rep = cfg.num_heads // cfg.num_kv_heads  # per-shard ratio unchanged
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (cfg.head_dim ** -0.5)
    scores = jnp.where(causal[None, None], scores, -1e30)
    attn = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
        v.astype(jnp.float32),
    ).astype(x.dtype)
    attn_out = attn.reshape(batch, seq, -1) @ layer["wo"]
    x = x + jax.lax.psum(attn_out, tp_axis)

    mlp_in = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    h = jax.nn.silu(mlp_in @ layer["w_gate"]) * (mlp_in @ layer["w_up"])
    return x + jax.lax.psum(h @ layer["w_down"], tp_axis)


def _tp_vocab_parallel_nll(h: jax.Array, lm_head_local: jax.Array,
                           targets: jax.Array, tp_axis: str) -> jax.Array:
    """Cross-entropy over a vocab-sharded head without materializing the
    full logits on any shard: a ``pmax``/``psum`` log-sum-exp plus a
    masked ``psum`` gather of each target's logit (Megatron
    vocab-parallel cross-entropy). ``h`` is [b, s, hidden] (positions
    already shifted); ``targets`` is [b, s]."""
    logits = (h @ lm_head_local).astype(jnp.float32)  # [b, s, vocab/tp]
    v_local = logits.shape[-1]
    # The stability shift is gradient-free (it cancels in lse - tgt), and
    # pmax has no differentiation rule — detach before the collective.
    m = jax.lax.pmax(
        jnp.max(jax.lax.stop_gradient(logits), axis=-1), tp_axis)  # [b, s]
    lse = jnp.log(jax.lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), tp_axis)) + m
    shard = jax.lax.axis_index(tp_axis)
    local_t = targets - shard * v_local
    ok = (local_t >= 0) & (local_t < v_local)
    tgt = jnp.take_along_axis(
        logits, jnp.clip(local_t, 0, v_local - 1)[..., None], axis=-1
    )[..., 0]
    tgt = jax.lax.psum(jnp.where(ok, tgt, 0.0), tp_axis)
    return lse - tgt  # [b, s] per-token NLL


def make_pp_pipelined_train_step(mesh: Mesh, cfg: LlamaConfig, params: Params,
                                 opt, num_microbatches: int,
                                 remat: bool = False):
    """Microbatched rotating-buffer pipeline over ``mesh``'s ``pp`` axis
    (× optional ``dp``).

    Returns ``(step_fn, stacked_params, opt_state, data_sharding)`` like
    ``make_pp_train_step``; the two produce identical losses/gradients for
    the same params (the schedule changes wall-clock shape, not math).
    """
    from jax import shard_map

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "pp" not in axis_sizes:
        raise ValueError("pipelined training requires a 'pp' mesh axis")
    if cfg.num_experts > 0:
        raise ValueError("pipeline path supports dense layers (MoE uses ep)")
    P_size = axis_sizes["pp"]
    M = num_microbatches
    if cfg.num_layers % P_size != 0:
        raise ValueError(
            f"num_layers ({cfg.num_layers}) must divide by pp size ({P_size})")
    dp = "dp" if "dp" in axis_sizes else None
    tp_size = axis_sizes.get("tp", 1)
    tp = "tp" if tp_size > 1 else None
    if tp is not None:
        if cfg.num_kv_heads % tp_size or cfg.vocab_size % tp_size:
            raise ValueError(
                f"tp={tp_size} must divide num_kv_heads "
                f"({cfg.num_kv_heads}) and vocab_size ({cfg.vocab_size})")

    stacked = stack_layer_params(params)
    # has_tp=True already places the embedding vocab-parallel (P(tp, None))
    # and lm_head column-parallel — the Megatron layout the hand-written
    # collectives below assume.
    param_specs = stacked_param_pspecs(tp is not None, "pp",
                                       qk_norm=cfg.qk_norm)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    stacked = jax.device_put(stacked, shardings)
    opt_state = opt.init(stacked)
    data_sharding = NamedSharding(mesh, P(dp, None))

    perm = [(i, i + 1) for i in range(P_size - 1)]

    def pipeline_loss(sp, tokens):
        # tokens: microbatch-local [b_mb, S] per (dp shard); split into M
        # microbatches along batch.
        b, S = tokens.shape
        if b % M != 0:
            raise ValueError(f"local batch {b} must divide by M={M}")
        mbs = tokens.reshape(M, b // M, S)
        positions = jnp.arange(S)[None, :].repeat(b // M, axis=0)
        stage = jax.lax.axis_index("pp")
        layers_local = sp["layers_stacked"]

        def embed(ids):
            if tp is not None:
                return _tp_embed(sp["embed"], ids, tp)
            return sp["embed"][ids]

        def run_layers(x):
            if tp is not None:
                def layer_step(x, layer):
                    return _tp_layer_step(x, layer, cfg, positions, tp), None

                x, _ = jax.lax.scan(layer_step, x, layers_local)
                return x
            return _scan_layers(layers_local, cfg, x, positions)

        def head_nll(y, mb_out):
            h = _rms_norm(y, sp["final_norm"], cfg.norm_eps)
            if tp is not None:
                return _tp_vocab_parallel_nll(
                    h[:, :-1], sp["lm_head"], mb_out[:, 1:], tp)
            logits = (h @ sp["lm_head"]).astype(jnp.float32)
            logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            return -jnp.take_along_axis(
                logprobs, mb_out[:, 1:][..., None], axis=-1)[..., 0]

        # Streams padded to M+P-1 ticks: stage 0 consumes microbatch t;
        # the last stage emits microbatch t-(P-1), so its target stream is
        # pre-shifted by P-1.
        pad = jnp.zeros((P_size - 1,) + mbs.shape[1:], mbs.dtype)
        in_stream = jnp.concatenate([mbs, pad], axis=0)           # [T,...]
        out_stream = jnp.concatenate([pad, mbs], axis=0)          # [T,...]

        def tick(carry, xs):
            x_prev, loss_acc = carry
            t, mb_in, mb_out = xs
            # Activations hop one stage forward; stage 0's slot is then
            # replaced by the fresh microbatch's embedding.
            recv = jax.lax.ppermute(x_prev, "pp", perm)
            injected = embed(mb_in)
            x_in = jnp.where(stage == 0, injected, recv)
            y = run_layers(x_in)
            # Last stage: head + NLL for the microbatch leaving the pipe.
            nll = head_nll(y, mb_out)
            # Count only drain ticks (t >= P-1): earlier ticks see the
            # zero-initialized buffer, not a real microbatch.
            valid = jnp.logical_and(stage == P_size - 1, t >= P_size - 1)
            loss_acc = loss_acc + jnp.where(valid, nll.mean(), 0.0)
            return (y, loss_acc), None

        if remat:
            # Bound activation memory to the rotating buffer: the backward
            # pass replays each tick's forward instead of keeping all
            # M+P-1 tick activations live (GPipe memory → ~1F1B memory,
            # paid with one extra forward).
            tick = jax.checkpoint(tick)

        x0 = jnp.zeros((b // M, S, cfg.hidden_size),
                       sp["embed"].dtype)
        ticks = jnp.arange(M + P_size - 1)
        # The loss accumulator rides the scan carry as shape (1,), not a
        # scalar: under value_and_grad, shard_map's partial-eval saves the
        # carry output as a residual, and this jax release's scalar-residual
        # promotion misses forwarded scan outputs — a float32[] residual
        # then fails the {0: axes} out-spec rank check (_SpecError).
        (_, loss_sum), _ = jax.lax.scan(
            tick, (x0, jnp.zeros((1,), jnp.float32)),
            (ticks, in_stream, out_stream))
        # Valid losses accumulated on the last stage only, for ticks
        # t >= P-1 … M+P-2 → exactly M microbatches. Average over M, then
        # across the pipeline (sum picks up the last stage's value) and
        # data shards.
        loss = jax.lax.psum(loss_sum[0] / M, "pp")
        # (Already replicated across tp: every shard computed the same
        # post-psum NLL, so no tp collective is needed here.)
        if dp is not None:
            loss = jax.lax.pmean(loss, dp)
        return loss

    mapped = shard_map(
        pipeline_loss,
        mesh=mesh,
        in_specs=(param_specs, P(dp, None)),
        out_specs=P(),
        check_vma=False,
    )

    def train_step(sp, opt_state, tokens):
        loss, grads = jax.value_and_grad(mapped)(sp, tokens)
        updates, opt_state = opt.update(grads, opt_state, sp)
        sp = optax.apply_updates(sp, updates)
        return sp, opt_state, loss

    return jax.jit(train_step), stacked, opt_state, data_sharding


def make_pp_train_step(mesh: Mesh, cfg: LlamaConfig, params: Params, opt):
    """Prepare pipeline-sharded training over ``mesh``'s ``pp`` axis.

    Returns ``(step_fn, stacked_params, opt_state, data_sharding)``.
    ``num_layers`` must divide evenly by the pp axis size.
    """
    if "pp" not in mesh.axis_names:
        raise ValueError("pipeline training requires a 'pp' mesh axis")
    if cfg.num_experts > 0:
        raise ValueError("pipeline path supports dense layers (MoE uses ep)")
    pp_size = dict(zip(mesh.axis_names, mesh.devices.shape))["pp"]
    if cfg.num_layers % pp_size != 0:
        raise ValueError(
            f"num_layers ({cfg.num_layers}) must divide by pp size ({pp_size})"
        )
    dp = "dp" if "dp" in mesh.axis_names else None
    has_tp = "tp" in mesh.axis_names

    stacked = stack_layer_params(params)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        stacked_param_pspecs(has_tp, "pp", qk_norm=cfg.qk_norm),
        is_leaf=lambda x: isinstance(x, P),
    )
    stacked = jax.device_put(stacked, shardings)
    opt_state = opt.init(stacked)
    data_sharding = NamedSharding(mesh, P(dp, None))

    def step(p, s, tokens):
        return pp_train_step(p, s, cfg, opt, tokens)

    return jax.jit(step), stacked, opt_state, data_sharding
