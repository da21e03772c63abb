"""The plain reference of ``deepseek-v3.2-exp-ep16-l5``: DeepSeek-V3.2-Exp's
layer as its model card and ``inference/model.py`` write it, one chip's
share of it, in ``jax.numpy`` and float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching, no dispatch, and nothing of the program but the weight tree it is
handed (fused by ``maybe_fuse_params`` or not) and the numbers of ``cfg``.

``x`` is a layer's input after its RMSNorm, ``t`` a position, ``s <= t``.

- Queries: ``cQ = RMSNorm(W_DQ x)``; per head ``q = W_UQ cQ = [qN (nope);
  qR (rope)]``, ``qR <- RoPE_yarn(qR, t)``.
- Latent: ``[cKV; kR] = W_DKV x``, ``cKV <- RMSNorm(cKV)``, ``kR <-
  RoPE_yarn(kR, t)``, one ``kR`` for all heads. Textbook attention: every
  head's key is ``[W_UK cKV_s; kR_s]``, its value ``W_UV cKV_s``; scores
  times ``(nope + rope)^-1/2 * mscale^2`` (``cfg.softmax_scale_mult``),
  softmax over the selected ``s`` only, then ``W_O``. The program folds
  ``W_UK`` and ``W_UV`` into the query and the output and attends the
  latent itself: the same function, which this checks.
- Indexer: ``qI_j = W_IQ,j cQ`` for each light head ``j``, RoPE on its
  leading rope dims; ``kI = LayerNorm(W_IK x)``, RoPE likewise; ``w = W_Iw
  x * heads^-1/2 * width^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])``. Query ``t`` attends the ``index_topk`` positions ``s <= t``
  with the largest ``I[t, s]`` (all while ``t < index_topk``; equal scores:
  the lower position, as a sort does), everything else masked to ``-inf``.
- Feed-forward: SwiGLU in the dense layers. In a routed layer ``sigma =
  sigmoid(x W_g)`` over the router's whole width; the choice is made on
  ``sigma + b``: a group's score is the sum of its two largest, the best
  ``topk_group`` groups are kept, then the ``k`` largest in them; ``g_e =
  factor * sigma_e / sum_chosen sigma``, the sum over all ``k`` chosen,
  held or not; ``y = shared(x) + sum_{e chosen and held} g_e expert_e(x)``
  with the experts ``cfg.experts_held`` says this chip holds. What the
  absent experts would add is left out, here as in the program.
- RoPE rotates half-split (dim ``i`` with ``i + d/2``), for attention and
  indexer alike, yarn by parts (``cfg.rope_scaling``). A checkpoint's
  interleaved columns are permuted at load (``hf_loader._deinterleave``);
  with random weights the layout is a relabelling. The published indexer
  also rotates ``qI`` and ``kI`` by a Hadamard matrix, which is orthogonal
  and changes no score, and quantises them to fp8, which this deployment
  does not (the configuration's ``assumed``).
- Computed in blocks of queries, so that 4 k positions of 128 heads fit
  beside the served model; an expert at a time.

**A top-k router needs more than one answer** (``kvbench/README.md``,
``tests/fixtures/routed_reference.py``): the program computes in bfloat16,
so where the scores that decide a position's choice lie closer than that
rounding moves them, program and reference choose differently, both by
right. ``alternatives_at`` returns ``logits_at``'s row first and then the
full forward's logits under the other choices the definition admits at
that position (scores within ``MARGIN``; groups within ``2 * MARGIN``,
their score being a sum of two), over the routed layers as a tree: the
nearest first, by how far the scores must move along the path, and
``LIMIT`` rows at most, because the probe calls more a fault and a
position with four near-ties has 16. Choices at earlier positions are not
branched. The selection of keys is a top-k too, with a dozen keys of 2048
on the other side of a query's threshold in bfloat16: too many to
enumerate, and each about 1/2048 of a row's weight. What they do to the
logits is under ``TOLERANCE`` only where the residual they are added to is
not smaller than attention's own output (``embed_init_scale`` in the
configuration's ``assumed``; ``tests/test_sparse_attention.py`` measures
the overlap of the two sets).

``TOLERANCE`` and ``MARGIN``: see the constants, each with the readings it
was set from.
"""

from __future__ import annotations

import heapq
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The accepted configurations' limit. It lies between two readings taken on
# one v5e at the published widths with ``harness/correct.py: probe`` (PERF.md
# section 6, PR 34; largest difference over the reference's largest logit,
# 4096 positions and 8 decoded):
# - the served program against this reference: 0.008-0.022 at the last
#   prompt position over 16 seeds, a decoded token at most 0.015 short;
# - THIS file served in the engine's place with its activations rounded to
#   the nearest type below the served one (``Control``, float8_e4m3fn):
#   0.114-0.174 over 8 seeds (a decoded token up to 0.10 short), not
#   correct on every seed.
# Planted faults read: the first 2048 keys selected instead of the best
# 0.315; a prefix hit without its index keys 0.196; the held experts' terms
# dropped 0.129 (or a decoded token 0.083 short); weights normalised over
# the held experts 0.68.
# With the embedding drawn at 0.02 like every other matrix the same program
# read 0.03-0.20 (35 seeds): layer 0's residual was then smaller than what
# its attention adds, an average of 2048 random values, so the dozen keys
# that rounding moves across a query's threshold (sqrt(2 x 12 / 2048) = 11%
# of that average) turned the hidden state by several per cent. The
# configuration draws the embedding at 0.3 (``embed_init_scale``).
TOLERANCE = 0.05
# In units of a score (a sigmoid's output). Measured on one v5e at the
# published widths (PR 34: 8 seeds x 512 positions x 4 routed layers, this
# file's forward with its activations rounded to bfloat16 against itself in
# float32): rounding moves the gap between the 8th and 9th score by 9.2e-4
# (median), 2.7e-3 (90th percentile), 6.9e-3 (99th). At 6.6% of positions
# the rounded forward chooses other experts held here in some layer; of
# those first departures the margin leaves out 83 of 4096 positions at 1e-3,
# 15 at 3e-3, 5 at 4e-3, 2 at 5e-3 and none at 6e-3, and one left out is a
# held expert's term, 0.1 of the largest logit. At 6e-3 one position in a
# hundred has more than 8 answers (42 once); cut at the nearest 8, none of
# the 4096 departures was cut away.
MARGIN = 6e-3
# The answers a position is given: the probe refuses more than 8, so the
# nearest 8 (``alternatives_at``). A layer's enumeration stops at twice that.
LIMIT = 8
# Queries a block of attention and of the indexer; rows a block of a
# feed-forward (a whole 4 k sequence's gate and up outputs at the dense
# layer's width would be a gigabyte beside the served model).
BLOCK = 128
HEADS = 32
ROWS = 1024
COLUMNS = 4608


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _done(x):
    """Wait for a block's result before the next block is enqueued: the
    runtime allocates a computation's buffers when it is enqueued, and a
    loop of blocks enqueued at once holds all their float32 copies at once
    (4 GB beside the served model, measured; PERF.md, PR 34)."""
    return jax.block_until_ready(x)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w) + _f32(b)


def _swiglu(h, gate, up, down):
    """``COLUMNS`` of the inner width at a time: the float32 copies of a
    dense layer's three matrices would be 1.6 GB at once."""
    out = 0.0
    for lo in range(0, gate.shape[-1], COLUMNS):
        hi = lo + COLUMNS
        out = _done(out + (jax.nn.silu(h @ _f32(gate[:, lo:hi]))
                           * (h @ _f32(up[:, lo:hi]))) @ _f32(down[lo:hi]))
    return out


def _gate_up(layer, suffix=""):
    """A SwiGLU's gate and up matrices from the unfused tree or the fused
    (``w_gate_up`` = ``[gate | up]``)."""
    fused = layer.get("w_gate_up" + suffix)
    if fused is None:
        return layer["w_gate" + suffix], layer["w_up" + suffix]
    half = fused.shape[-1] // 2
    return fused[:, :half], fused[:, half:]


def _feed_forward(h, layer, suffix=""):
    """SwiGLU over every position, ``ROWS`` at a time."""
    gate, up = _gate_up(layer, suffix)
    return jnp.concatenate(
        [_swiglu(h[lo:lo + ROWS], gate, up, layer["w_down" + suffix])
         for lo in range(0, h.shape[0], ROWS)], 0)


def rope_tables(cfg, n: int, dims: int):
    """cos, sin ``[n, 1, dims / 2]`` for positions ``0..n-1``: plain RoPE,
    or yarn by parts (dims below the ``beta_fast`` bound keep their
    frequency, above the ``beta_slow`` bound divide it by ``factor``, a
    linear ramp between; cos and sin times the attention factor)."""
    half = dims // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(half, dtype=np.float64) / half))
    att = 1.0
    if cfg.rope_scaling:
        kind, factor, beta_fast, beta_slow, orig, att = cfg.rope_scaling
        if kind != "yarn":
            raise NotImplementedError(f"rope scaling {kind!r}")

        def bound(rotations):
            return (dims * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))

        low = max(math.floor(bound(beta_fast)), 0)
        high = min(math.ceil(bound(beta_slow)), dims - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001),
                       0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    angles = np.arange(n, dtype=np.float64)[:, None] * freqs
    return (jnp.asarray(np.cos(angles)[:, None, :] * att, jnp.float32),
            jnp.asarray(np.sin(angles)[:, None, :] * att, jnp.float32))


def _rope(x, cos, sin):
    """x ``[s, heads, d]``, rotate-half over all of ``d``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_inputs(h, layer, cfg):
    """``(q_latent, c_kv, k_rope_in)`` from the unfused or the fused tree
    (``w_mla_in`` = ``[w_dq | w_dkv | w_kr]``)."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    if "w_mla_in" in layer:
        fused = h @ _f32(layer["w_mla_in"])
        qc = fused.shape[-1] - r - dr
        down, c_kv, k_rope_in = (fused[:, :qc], fused[:, qc:qc + r],
                                 fused[:, qc + r:])
    else:
        down, c_kv, k_rope_in = (h @ _f32(layer["w_dq"]),
                                 h @ _f32(layer["w_dkv"]),
                                 h @ _f32(layer["w_kr"]))
    return (_rms_norm(down, layer["q_latent_norm"], cfg.norm_eps),
            _rms_norm(c_kv, layer["latent_norm"], cfg.norm_eps), k_rope_in)


@jax.jit
def _index_block(q_idx, w_idx, k_idx):
    """``I`` for a block of queries: ``[block, keys]``."""
    return jnp.einsum("qhk,qh->qk", jax.nn.relu(
        jnp.einsum("qhd,kd->qhk", q_idx, k_idx)), w_idx)


@jax.jit
def _attend_block(q, k, v, keep, scale):
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khv->qhv", probs, v)


def selected(index_scores: np.ndarray, first: int, topk: int) -> np.ndarray:
    """bool ``[block, keys]``: what the queries at positions ``first..``
    keep, from their index scores: the ``topk`` largest among ``s <= t``
    (the lower position of equals), all while there are no more."""
    n_q, n_k = index_scores.shape
    t = first + np.arange(n_q)[:, None]
    causal = np.arange(n_k)[None, :] <= t
    if n_k <= topk:
        return causal
    scores = np.where(causal, index_scores, -np.inf)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :topk]
    keep = np.zeros_like(causal)
    np.put_along_axis(keep, order, True, axis=1)
    return keep & causal


def _attention(h, layer, cfg, tables, picks=None):
    """Multi-head latent attention over one sequence with the indexer's
    selection, keys and values materialised from the latent, ``HEADS``
    heads at a time (all 128 at once are a gigabyte of float32 queries,
    keys and values beside the served model). ``picks``: a list that
    receives each block's selection (bool arrays), for the test that
    compares the program's set with this one."""
    s = h.shape[0]
    heads, hd, dr = cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    cos, sin = tables  # attention's rope dims and the indexer's: as many
    q_lat, c_kv, k_rope_in = _attention_inputs(h, layer, cfg)
    k_rope = _rope(k_rope_in[:, None, :], cos, sin)             # [s, 1, dr]
    scale = (hd + dr) ** -0.5 * cfg.softmax_scale_mult
    blocks = [(lo, min(lo + BLOCK, s)) for lo in range(0, s, BLOCK)]

    if cfg.index_topk:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        q_idx = (q_lat @ _f32(layer["w_iq"])).reshape(s, hi, di)
        q_idx = jnp.concatenate(
            [_rope(q_idx[..., :dr], cos, sin), q_idx[..., dr:]], -1)
        k_idx = _layer_norm(h @ _f32(layer["w_ik"]), layer["index_norm"],
                            layer["index_norm_bias"], cfg.norm_eps)
        k_idx = jnp.concatenate(
            [_rope(k_idx[:, None, :dr], cos, sin)[:, 0], k_idx[:, dr:]],
            -1)
        w_idx = (h @ _f32(layer["w_iw"])) * (hi ** -0.5 * di ** -0.5)
        keeps = [selected(np.asarray(_index_block(q_idx[lo:up], w_idx[lo:up],
                                                  k_idx)), lo, cfg.index_topk)
                 for lo, up in blocks]
        del q_idx, k_idx, w_idx
    else:
        keeps = [np.arange(s)[None, :] <= np.arange(lo, up)[:, None]
                 for lo, up in blocks]
    if picks is not None:
        picks.extend(keeps)

    out = 0.0
    for g in range(0, heads, HEADS):
        n = min(HEADS, heads - g)
        q = (q_lat @ _f32(layer["wq"][:, g * (hd + dr):(g + n) * (hd + dr)])
             ).reshape(s, n, hd + dr)
        q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], cos, sin)], -1)
        k = jnp.concatenate(
            [jnp.einsum("sr,hrd->shd", c_kv, _f32(layer["w_uk"][g:g + n])),
             jnp.broadcast_to(k_rope, (s, n, dr))], -1)
        v = jnp.einsum("sr,hrv->shv", c_kv, _f32(layer["w_uv"][g:g + n]))
        attn = jnp.concatenate(
            [_done(_attend_block(q[lo:up], k, v, jnp.asarray(keep), scale))
             for (lo, up), keep in zip(blocks, keeps)], 0)
        out = _done(out + attn.reshape(s, n * hd)
                    @ _f32(layer["wo"][g * hd:(g + n) * hd]))
    return out


# -- the router ---------------------------------------------------------------


def admitted(values: np.ndarray, k: int, margin: float) -> list:
    """The top ``k`` of ``values`` as sorted index tuples: first the
    definition's own (equal values: the lower index, as ``lax.top_k``),
    then every other set that is the top k once each value has moved by
    under ``margin / 2``: the largest it leaves out is less than ``margin``
    above the smallest it takes."""
    order = np.argsort(-values, kind="stable")
    top = tuple(sorted(int(i) for i in order[:k]))
    if k >= len(values) or margin <= 0.0:
        return [top]
    kth, nxt = values[order[k - 1]], values[order[k]]
    ins = [int(i) for i in order[:k] if values[i] - nxt < margin]
    outs = [int(i) for i in order[k:] if kth - values[i] < margin]
    sets = [top]
    for j in range(1, min(len(ins), len(outs)) + 1):
        for drop in itertools.combinations(ins, j):
            for add in itertools.combinations(outs, j):
                took = (set(top) - set(drop)) | set(add)
                left = max(v for i, v in enumerate(values) if i not in took)
                if left - min(values[i] for i in took) < margin:
                    sets.append(tuple(sorted(took)))
                if len(sets) > 2 * LIMIT:
                    return sets
    return sets


def _group_scores(choice: np.ndarray, n_group: int) -> np.ndarray:
    """A group's score: the sum of its two best."""
    return np.sort(choice.reshape(n_group, -1), axis=1)[:, -2:].sum(1)


def _within(choice: np.ndarray, n_group: int, groups) -> np.ndarray:
    """As the model card has it: an expert outside the kept groups scores
    0 (a sigmoid's output never does)."""
    per = len(choice) // n_group
    return np.where(np.isin(np.arange(len(choice)) // per, groups),
                    choice, 0.0)


def _need(values: np.ndarray, took) -> float:
    """How far the scores have to move for ``took`` to be the top of
    ``values``: the largest it leaves out less the smallest it takes."""
    inside = np.zeros(len(values), bool)
    inside[list(took)] = True
    return float(values[~inside].max() - values[inside].min())


def choices(scores: np.ndarray, bias: np.ndarray, router: tuple, k: int,
            margin: float, held: tuple) -> list:
    """Every choice of experts one position's scores admit, as ``(need,
    experts)``: the definition's own first, the others by how far the
    scores must move for them (``_need``; a group's score is a sum of two,
    so half its need). ``held = (first, count)``: choices that differ only
    in experts another chip holds give this chip the same terms but for the
    sum they are normalised by, which two scores within ``margin`` of each
    other move by under ``margin`` of some 5: the nearest of them stands for
    all."""
    _kind, n_group, topk_group, _norm, _factor = router
    choice = scores + bias
    group_scores = _group_scores(choice, n_group)
    own, others = None, {}
    for groups in admitted(group_scores, topk_group, 2 * margin):
        within = _within(choice, n_group, groups)
        for experts in admitted(within, k, margin):
            if own is None:
                own = experts
                continue
            need = max(_need(group_scores, groups) / 2,
                       _need(within, experts))
            here = tuple(e for e in experts
                         if held[0] <= e < held[0] + held[1])
            if here not in others or need < others[here][0]:
                others[here] = (need, experts)
    others.pop(tuple(e for e in own if held[0] <= e < held[0] + held[1]),
               None)
    return [(-np.inf, own)] + sorted(others.values())


def _own_choice(scores: np.ndarray, bias: np.ndarray, router: tuple,
                k: int) -> np.ndarray:
    """The definition's choice at every position at once: ``[s, k]``."""
    _kind, n_group, topk_group, _norm, _factor = router
    choice = scores + bias[None, :]
    s, e = choice.shape
    per = e // n_group
    group = np.sort(choice.reshape(s, n_group, per), axis=2)[:, :, -2:].sum(2)
    kept = np.argsort(-group, axis=1, kind="stable")[:, :topk_group]
    ok = np.zeros((s, n_group), bool)
    np.put_along_axis(ok, kept, True, axis=1)
    masked = np.where(np.repeat(ok, per, axis=1), choice, 0.0)
    return np.argsort(-masked, axis=1, kind="stable")[:, :k]


def _deciding_gaps(scores, bias, router, k) -> tuple:
    """(k-th minus next expert's score within the kept groups, last kept
    minus next group's score)."""
    _kind, n_group, topk_group, _norm, _factor = router
    choice = scores + bias
    group = _group_scores(choice, n_group)
    kept = np.argsort(-group, kind="stable")[:topk_group]
    g = np.sort(group)[::-1]
    e = np.sort(_within(choice, n_group, kept))[::-1]
    return float(e[k - 1] - e[k]), float(g[topk_group - 1] - g[topk_group])


@jax.jit
def _expert(h, weight, gate, up, down):
    return weight[:, None] * _swiglu(h, gate, up, down)


def _routed(h, layer, cfg, li, positions, forced, ties, gaps, margin):
    """A routed layer's experts and shared expert over every position."""
    router, k = cfg.moe_router, cfg.num_experts_per_token
    first, held = cfg.experts_held or (0, cfg.num_experts)
    scores = np.asarray(jax.nn.sigmoid(h @ _f32(layer["router"])))
    bias = np.asarray(layer["router_bias"], np.float32)
    took = _own_choice(scores, bias, router, k)                   # [s, k]
    ties[li], gaps[li] = {}, {}
    for p in positions:
        ties[li][p] = choices(scores[p], bias, router, k, margin,
                              (first, held))
        gaps[li][p] = _deciding_gaps(scores[p], bias, router, k)
        took[p] = ties[li][p][0][1]
    for p, experts in forced.get(li, {}).items():
        took[p] = experts
    w = np.take_along_axis(scores, took, axis=1)
    if router[3]:
        w = w / (w.sum(1, keepdims=True) + 1e-20)
    weights = np.zeros(scores.shape, np.float32)
    np.put_along_axis(weights, took, w * router[4], axis=1)
    weights = jnp.asarray(weights[:, first:first + held])        # [s, held]
    out = _feed_forward(h, layer, "_sh")
    for e in range(held):
        out = _done(out + _expert(h, weights[:, e], layer["w_gate"][e],
                                  layer["w_up"][e], layer["w_down"][e]))
    return out


# -- the forward --------------------------------------------------------------


def _check(params, cfg):
    if not (cfg.is_mla and cfg.q_lora_rank):
        raise NotImplementedError(
            "this reference covers latent attention with q-LoRA")
    if cfg.num_experts and not (cfg.moe_router
                                and cfg.moe_router[0] == "deepseek_v3"):
        raise NotImplementedError(
            "this reference covers DeepSeek-V3's router")


def _forward(params, cfg, tokens, positions, forced=None, rounded=None,
             margin=None, picks=None):
    """One full forward over ``tokens``. Returns ``(logits, ties, gaps)``:
    float32 logits at ``positions``; ``ties[layer][position]`` the admitted
    choices there (the definition's first); ``gaps[layer][position]`` the
    distance between the k-th and the next score and between the last kept
    and the next group, for ``margin_readings``. ``forced`` is ``{layer:
    {position: experts}}``. ``rounded`` (a type's name) rounds activations
    to that type where the served type rounds them, to measure ``MARGIN``
    (``"bfloat16"``) and the control below the stated precision
    (``"float8_e4m3fn"``, ``Control``); nothing that decides
    ``correct`` sets it. ``picks``: ``{layer: [bool blocks]}`` of the keys
    every query kept, for the tests."""
    forced = forced or {}
    margin = MARGIN if margin is None else margin
    act = ((lambda x: x.astype(jnp.dtype(rounded)).astype(jnp.float32))
           if rounded else (lambda x: x))
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    tables = rope_tables(cfg, n, cfg.qk_rope_head_dim)
    ties: dict = {}
    gaps: dict = {}
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"][tokens].astype(jnp.float32))
        for li, layer in enumerate(params["layers"]):
            h = act(_rms_norm(x, layer["attn_norm"], cfg.norm_eps))
            got = None if picks is None else picks.setdefault(li, [])
            x = act(x + _attention(h, layer, cfg, tables, got))
            h = act(_rms_norm(x, layer["mlp_norm"], cfg.norm_eps))
            if "router" in layer:
                x = act(x + _routed(h, layer, cfg, li, positions, forced,
                                    ties, gaps, margin))
            else:
                x = act(x + _feed_forward(h, layer))
        x = _rms_norm(x[jnp.asarray(positions)], params["final_norm"],
                      cfg.norm_eps)
        head = params["lm_head"]
        out = jnp.concatenate(
            [_done(x @ _f32(head[:, lo:lo + COLUMNS]))
             for lo in range(0, head.shape[1], COLUMNS)], -1)
    return np.asarray(out, np.float32), ties, gaps


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions, every position
    taking the definition's own choice of experts."""
    _check(params, cfg)
    return _forward(params, cfg, tokens, list(positions))[0]


def alternatives_at(params, cfg, tokens, positions) -> list:
    """One float32 array ``[A_i, vocab]`` per position: row 0 is
    ``logits_at``'s row; the others the full forward's logits there under
    the other admitted choices of experts at that position (see the
    module's text), the nearest first and ``LIMIT`` rows at most. One more
    forward a round, for every position at once: each takes its nearest
    path not yet run, and what that forward finds below the path's last
    choice joins the position's queue."""
    _check(params, cfg)
    positions = list(positions)
    base, ties, _ = _forward(params, cfg, tokens, positions)
    routed = sorted(ties)
    rows = {p: [base[i]] for i, p in enumerate(positions)}
    queues: dict = {p: [] for p in positions}
    order = itertools.count()

    def push(p, path, need, path_ties, start):
        for at in range(start, len(routed)):
            for further, experts in path_ties[routed[at]][p][1:]:
                heapq.heappush(queues[p], (
                    max(need, further), next(order),
                    {**path, routed[at]: experts}, at + 1))

    for p in positions:
        push(p, {}, -np.inf, ties, 0)
    while True:
        picked = {p: heapq.heappop(q) for p, q in queues.items()
                  if q and len(rows[p]) < LIMIT}
        if not picked:
            return [np.stack(rows[p]) for p in positions]
        forced: dict = {}
        for p, (_, _, path, _) in picked.items():
            for li, experts in path.items():
                forced.setdefault(li, {})[p] = experts
        logits, below, _ = _forward(params, cfg, tokens, positions, forced)
        for p, (need, _, path, start) in picked.items():
            rows[p].append(logits[positions.index(p)])
            push(p, path, need, below, start)


def margin_readings(params, cfg, tokens, positions) -> list:
    """How far rounding the activations to bfloat16 moves the two gaps that
    decide each routed layer's choice at each position: ``(expert gap's
    change, group gap's change)``, in score units. What ``MARGIN`` is set
    from."""
    _check(params, cfg)
    positions = list(positions)
    _, _, exact = _forward(params, cfg, tokens, positions)
    _, _, low = _forward(params, cfg, tokens, positions,
                         rounded="bfloat16")
    return [(abs(exact[li][p][0] - low[li][p][0]),
             abs(exact[li][p][1] - low[li][p][1]))
            for li in exact for p in positions]


class Control:
    """The upper reading ``TOLERANCE`` is set under: this file's forward
    with its activations rounded to the nearest type below the served one
    (``rounded``), standing where ``harness/correct.py: probe`` expects an
    engine (``enqueue``, ``step``, a request's ``done``, ``last_logits``,
    ``output``, ``cached_len``), so that the control comes out as not
    correct by the cell's own comparison. Greedy, one full forward a token;
    a prompt seen before is answered from what was kept, as a prefix hit
    (``hack/kvbench_probe_readings.py --control`` drives it)."""

    offload_handlers = None

    def __init__(self, params, cfg, rounded="float8_e4m3fn"):
        _check(params, cfg)
        self.params, self.cfg, self.rounded = params, cfg, rounded
        self._seen: dict = {}
        self._req = None

    def enqueue(self, _rid, prompt, max_new_tokens):
        from types import SimpleNamespace

        self._req = SimpleNamespace(
            prompt=list(prompt), want=max_new_tokens, output=[],
            last_logits=None, done=False,
            cached_len=len(prompt) - 1 if tuple(prompt) in self._seen else 0)
        return self._req

    def step(self):
        req = self._req
        tokens = tuple(req.prompt + req.output)
        if tokens not in self._seen:
            # One length for every token of a request (no position looks
            # ahead, so what pads the sequence changes nothing): one
            # compilation, and the probe's own reference run shares it.
            padded = tokens + (0,) * (len(req.prompt) + req.want - 1
                                      - len(tokens))
            self._seen[tokens] = _forward(
                self.params, self.cfg, padded, [len(tokens) - 1],
                margin=0.0, rounded=self.rounded)[0][0]
        if req.last_logits is None:
            req.last_logits = self._seen[tokens]
        req.output.append(int(np.argmax(self._seen[tokens])))
        req.done = len(req.output) >= req.want


def kept_keys(params, cfg, tokens) -> dict:
    """``{layer: bool [positions, positions]}``: the keys every query of a
    full forward keeps. For the test of the program's selection."""
    picks: dict = {}
    _forward(params, cfg, tokens, [len(tokens) - 1], margin=0.0, picks=picks)
    return {li: np.concatenate(blocks, 0) for li, blocks in picks.items()}
