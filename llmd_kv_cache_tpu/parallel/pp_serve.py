"""Pipeline-parallel SERVING over a ``pp`` mesh axis.

Training pp exists in two schedules (``parallel.pipeline``); this
module adds the inference counterpart: layer blocks sharded across
stages, **paged KV caches sharded on their layer axis** (each stage owns
the cache slabs for its layers — the memory reason pp exists: a model +
cache too big for one chip), and a GPipe rotating-buffer schedule where
M microbatches of the serving batch stream through the stages with
activations hopping stage→stage via ``ppermute``.

Reference counterpart: the reference fingerprints pp topology into its
offload store layout (``file_mapper.py`` keys files by parallel rank)
but delegates the engines to vLLM; here the engine is in-tree, so pp
serving is implemented, not just fingerprinted.

TPU-first design notes:
- The tick loop is a PYTHON unroll, not ``lax.scan``: the carries would
  include each stage's cache slab, and XLA TPU copies large scan
  carries every iteration (measured ~300 GB/s r+w — the round-4
  burst-tail finding). Unrolled straight-line code lets XLA update the
  donated cache slabs in place. M + P - 1 ticks with L/P layers each
  keep the program ~(M+P-1)/M × one model forward.
- Collectives are explicit (``ppermute`` for the activation hop, one
  final ``psum`` to replicate the departing logits) because inside
  ``shard_map`` XLA does not derive collectives from shardings.
- Scope: dense uniform-attention models (incl. uniform SWA + sinks and
  Qwen-bias families), XLA attention backend, single-token decode.
  Composes with ``tp`` on the same mesh (Megatron column/row shards +
  kv-head-sharded cache slabs within each stage, explicit psums) and
  with ``dp`` outside; ``sp`` is not composed yet.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig, Params, _mlp, _rms_norm, _rope
from ..ops.kv_pages import scatter_kv_pages
from ..ops.paged_attention import paged_attention
from .pipeline import stack_layer_params


def pp_size_of(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get("pp", 1)


def _uniform_window(cfg: LlamaConfig):
    """The single per-layer window of a uniform config (None = full
    attention everywhere). Mixed layouts raise — that's the hybrid
    family, which pp v1 does not cover."""
    windows = {cfg.layer_window(li) for li in range(cfg.num_layers)}
    if len(windows) > 1:
        raise ValueError(
            "pp serving v1 needs a uniform attention layout (mixed "
            "full/SWA layers are the hybrid family)")
    return next(iter(windows))


def validate_pp_serve_config(cfg: LlamaConfig, mesh: Mesh,
                             microbatches: int, max_batch: int) -> None:
    pp = pp_size_of(mesh)
    if cfg.num_layers % pp != 0:
        raise ValueError(
            f"num_layers ({cfg.num_layers}) must divide by pp ({pp})")
    if cfg.num_experts > 0 or cfg.is_mla or cfg.is_hybrid:
        raise ValueError(
            "pp serving v1 covers dense non-hybrid attention models "
            "(MoE scales over ep; MLA/hybrid compose with tp/sp)")
    _uniform_window(cfg)
    if max_batch % microbatches != 0:
        raise ValueError(
            f"max_batch ({max_batch}) must divide by microbatches "
            f"({microbatches}) — every tick moves one microbatch")


# Megatron placement within each stage when a ``tp`` axis is present:
# column-parallel in-projections (their biases follow the columns),
# row-parallel out-projections (one psum each in _pp_layer).
_TP_COL = {"wq", "wk", "wv", "w_gate", "w_up", "bq", "bk", "bv"}
_TP_ROW = {"wo", "w_down"}


def pp_param_pspecs(stacked: dict, tp: bool = False) -> dict:
    """Stacked-tree specs DERIVED from the tree itself: every stacked
    layer leaf shards its leading (layer) axis over ``pp``, whatever the
    key — qk norms, Qwen2 QKV biases, future additions — so the spec
    tree can never drift from the parameter tree (review r5). With
    ``tp``, the known Megatron keys additionally shard within the stage.
    Embed and head replicate: stage 0 embeds, the last stage projects,
    which keeps the schedule collective-free at the ends for one matrix
    copy each."""
    def leaf_spec(path, a):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        rest = [None] * (a.ndim - 1)
        if tp and key in _TP_COL:
            rest[-1] = "tp"  # biases are 1-D: their only axis follows
        elif tp and key in _TP_ROW:
            rest[0] = "tp"
        return P("pp", *rest)

    return {
        "embed": P(),
        "layers_stacked": jax.tree_util.tree_map_with_path(
            leaf_spec, stacked["layers_stacked"]),
        "final_norm": P(),
        "lm_head": P(),
    }


def kv_pp_axes(tp: bool = False) -> P:
    """[layers, pages, kvh, ps, hd]: layer axis over pp, kv heads over
    tp when present (each tp shard owns whole kv heads, like
    parallel.serve.shard_kv_pool)."""
    return P("pp", None, "tp" if tp else None, None, None)


def shard_pp_state(mesh: Mesh, cfg: LlamaConfig, params: Params,
                   k_cache: jax.Array, v_cache: jax.Array):
    """(stacked_params, k, v) placed for pp serving: stacked layer trees
    with the layer axis over ``pp``; cache slabs likewise (+ the kv-head
    axis over ``tp`` when the mesh has one)."""
    tp = mesh.shape.get("tp", 1) > 1
    stacked = stack_layer_params(params)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        pp_param_pspecs(stacked, tp),
        is_leaf=lambda x: isinstance(x, P),
    )
    stacked = jax.device_put(stacked, shardings)
    kv_sharding = NamedSharding(mesh, kv_pp_axes(tp))
    return (stacked, jax.device_put(k_cache, kv_sharding),
            jax.device_put(v_cache, kv_sharding))


def _pp_layer(x, layer, cfg, k_layer, v_layer, table, positions,
              total_lens, valid, window, tp_axis=None):
    """One dense layer with paged attention over this stage's cache slab.

    Scatters the microbatch's K/V into the LOCAL layer cache (functional
    update — straight-line code, so XLA keeps it in place), then runs the
    XLA paged-attention reference over cached prefix + the new tokens.
    Mirrors the per-layer body of ``models.llama._forward_impl_grouped``
    for the dense path: qk-norm, GQA, QKV biases, uniform SWA windows,
    and StreamingLLM sinks.

    ``tp_axis``: Megatron within the stage — the projections are local
    column/row shards (head counts derive from the LOCAL weight shapes,
    the GQA group ratio is shard-invariant), attention runs on local
    heads over the kv-head-sharded cache slab, and the row-parallel
    wo/w_down partial sums are fixed by one ``psum`` each (the explicit
    form ``parallel.pipeline._tp_layer_step`` uses for training —
    inside shard_map XLA does not derive collectives).
    """
    batch, seq = x.shape[0], x.shape[1]
    attn_in = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = attn_in @ layer["wq"]
    k = attn_in @ layer["wk"]
    v = attn_in @ layer["wv"]
    if "bq" in layer:  # Qwen2-lineage QKV projection biases
        q = q + layer["bq"]
        k = k + layer["bk"]
        v = v + layer["bv"]
    q = q.reshape(batch, seq, -1, cfg.head_dim)
    k = k.reshape(batch, seq, -1, cfg.head_dim)
    v = v.reshape(batch, seq, -1, cfg.head_dim)
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.norm_eps)
    q = _rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = _rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    k_layer = scatter_kv_pages(k_layer, k, table, positions, valid)
    v_layer = scatter_kv_pages(v_layer, v, table, positions, valid)
    attn = paged_attention(q, k_layer, v_layer, table, positions,
                           total_lens, sliding_window=window,
                           attention_sinks=cfg.attention_sinks or None)
    attn_out = attn.reshape(batch, seq, -1) @ layer["wo"]
    if tp_axis is not None:
        attn_out = jax.lax.psum(attn_out, tp_axis)
    x = x + attn_out
    mlp_in = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    # _mlp's dense branch on the local column shards; the row-parallel
    # w_down product is a partial sum under tp, fixed by one psum.
    down = _mlp(mlp_in, layer, cfg)
    if tp_axis is not None:
        down = jax.lax.psum(down, tp_axis)
    return x + down, k_layer, v_layer


def make_pp_serve_forward(mesh: Mesh, cfg: LlamaConfig,
                          stacked_params: dict,
                          microbatches: Optional[int] = None):
    """The pp forward, in ``llama.forward``'s convention and not yet
    jitted: ``forward_pp(sp, cfg, tokens, k, v, table, ctx, new,
    last_only=True) -> (last_logits [b, 1, vocab], k, v)``. The engine
    jits its step form (``llama.step_program``), which donates the pools.

    One call serves a prefill chunk (seq > 1) or a decode step (seq == 1)
    for the whole batch; the batch is split into ``microbatches`` (default
    = pp size) row groups that stream through the stages. Logits are each
    sequence's LAST valid position (``new - 1``), replicated on every
    stage by the final psum — the only logits serving ever needs.
    ``stacked_params`` supplies the tree structure the shard_map specs
    derive from (the call passes the same tree).
    """
    P_size = pp_size_of(mesh)
    M = microbatches or P_size
    local_layers = cfg.num_layers // P_size
    perm = [(i, i + 1) for i in range(P_size - 1)]
    window = _uniform_window(cfg)
    tp = mesh.shape.get("tp", 1) > 1
    tp_axis = "tp" if tp else None
    param_specs = pp_param_pspecs(stacked_params, tp)
    kv_axes = kv_pp_axes(tp)

    def staged(sp, k_all, v_all, tokens, table, ctx_lens, new_lens):
        # Everything except the cache slabs and layer stack is replicated.
        b, seq = tokens.shape
        mb = b // M
        stage = jax.lax.axis_index("pp")
        layers = sp["layers_stacked"]  # [local_layers, ...] on this stage

        positions_all = ctx_lens[:, None] + jnp.arange(seq)[None, :]
        valid_all = jnp.arange(seq)[None, :] < new_lens[:, None]
        total_all = ctx_lens + new_lens

        def mb_slice(a, m):
            return jax.lax.dynamic_slice_in_dim(a, m * mb, mb, axis=0)

        x_buf = jnp.zeros((mb, seq, cfg.hidden_size), sp["embed"].dtype)
        out = jnp.zeros((b, cfg.vocab_size), jnp.float32)
        k_all = k_all  # [local_layers, pages, kvh, ps, hd] local slab
        v_all = v_all

        for t in range(M + P_size - 1):
            inject = min(t, M - 1)      # microbatch entering stage 0
            depart = max(t - P_size + 1, 0)  # microbatch leaving the end
            recv = jax.lax.ppermute(x_buf, "pp", perm)
            injected = sp["embed"][mb_slice(tokens, inject)]
            x_in = jnp.where(stage == 0, injected, recv)
            # Every stage processes the microbatch resident in its slot
            # this tick: stage s holds microbatch t - s. Slices of the
            # control tensors are picked per stage.
            mine = jnp.clip(t - stage, 0, M - 1)
            tab = mb_slice(table, mine)
            pos = mb_slice(positions_all, mine)
            val = mb_slice(valid_all, mine)
            tot = mb_slice(total_all, mine)
            # Ticks where this stage holds no real microbatch (t < s or
            # t - s >= M) write via a garbage-masked valid.
            live = jnp.logical_and(t - stage >= 0, t - stage < M)
            val = jnp.logical_and(val, live)
            x = x_in
            for j in range(local_layers):
                layer = jax.tree.map(lambda a: a[j], layers)
                x, k_j, v_j = _pp_layer(
                    x, layer, cfg, k_all[j], v_all[j], tab, pos, tot, val,
                    window, tp_axis=tp_axis)
                k_all = k_all.at[j].set(k_j)
                v_all = v_all.at[j].set(v_j)
            x_buf = x
            # Departing microbatch: last-token logits on the last stage.
            h = _rms_norm(x, sp["final_norm"], cfg.norm_eps)
            last_idx = jnp.clip(mb_slice(new_lens, depart) - 1, 0, seq - 1)
            h_last = jnp.take_along_axis(
                h, last_idx[:, None, None].repeat(cfg.hidden_size, -1),
                axis=1)[:, 0]
            logits = (h_last @ sp["lm_head"]).astype(jnp.float32)
            emit = jnp.logical_and(stage == P_size - 1, t >= P_size - 1)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(emit, logits, mb_slice(out, depart)),
                depart * mb, axis=0)

        # Replicate the assembled logits (only the last stage wrote real
        # values; other stages hold zeros at emitted rows).
        out = jax.lax.psum(
            jnp.where(stage == P_size - 1, out, jnp.zeros_like(out)), "pp")
        return out, k_all, v_all

    mapped = shard_map(
        staged,
        mesh=mesh,
        in_specs=(param_specs, kv_axes, kv_axes,
                  P(), P(), P(), P()),
        out_specs=(P(), kv_axes, kv_axes),
        check_vma=False,
    )

    def forward_pp(sp, _cfg, tokens, k, v, table, ctx_lens, new_lens,
                   last_only=True):
        if not last_only:
            raise ValueError("the pp forward computes last-position logits")
        logits, k, v = mapped(sp, k, v, tokens, table,
                              ctx_lens.astype(jnp.int32),
                              new_lens.astype(jnp.int32))
        return logits[:, None, :], k, v

    return forward_pp
