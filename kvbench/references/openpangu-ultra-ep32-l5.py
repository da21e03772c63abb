"""The plain reference of ``openpangu-ultra-ep32-l5``: openPangu-Ultra-MoE's
layer and its multi-token-prediction module as the model's ``config.json``
and its family's published description (DeepSeek-V3's report, sections 2.1
and 2.2) write them, one chip's share of it, in ``jax.numpy`` and float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching, no dispatch, no drafting, and nothing of the program but the
weight tree it is handed (fused by ``maybe_fuse_params`` or not) and the
numbers of ``cfg``.

``x`` is a layer's input after its RMSNorm, ``t`` a position, ``s <= t``.

- Attention: ``cQ = RMSNorm(W_DQ x)``; per head ``q = W_UQ cQ = [qN (nope);
  qR (rope)]``, ``qR <- RoPE(qR, t)``; ``[cKV; kR] = W_DKV x``, ``cKV <-
  RMSNorm(cKV)``, ``kR <- RoPE(kR, t)``, one ``kR`` for all heads. Textbook
  attention: every head's key is ``[W_UK cKV_s; kR_s]``, its value ``W_UV
  cKV_s``; scores times ``(nope + rope)^-1/2``, causal softmax, then
  ``W_O``. The program folds ``W_UK`` and ``W_UV`` into the query and the
  output and attends the latent itself: the same function, which this
  checks. RoPE is plain (``rope_theta`` 25.6e6, no scaling) and rotates
  half-split; a checkpoint's interleaved columns are permuted at load, which
  random weights make a relabelling.
- Block (``sandwich_norm``): ``h = x + norm(Attn(norm(x)))``, ``y = h +
  norm(FF(norm(h)))``: four RMSNorms a layer.
- Feed-forward: SwiGLU in the dense layer. In a routed layer ``sigma =
  sigmoid(x W_g)`` over the router's whole width (one group); the ``k``
  largest of ``sigma + b`` are chosen; ``g_e = factor * sigma_e / sum_chosen
  sigma``, the sum over all ``k`` chosen, held or not; ``y = shared(x) +
  sum_{e chosen and held} g_e expert_e(x)`` with the experts
  ``cfg.experts_held`` says this chip holds. What the absent experts would
  add is left out, here as in the program.
- The prediction module (``draft_logits_at``): for position ``i`` with the
  main model's hidden state ``h_i`` AFTER its final norm and the next token
  ``t_{i+1}``: ``u_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, one
  more block of the model's kind (an expert layer) over ``u_0..u_i`` at
  positions ``0..i``, the module's norm, and the model's own head: the logits
  of ``t_{i+2}``. The embedding and the head are the main model's.

Departures from the published description: the chip's share (experts held,
the vocabulary's slice) and nothing else. Readings the ``config.json`` does
not settle are the configuration's ``assumed`` (the order of the halves under
``W_eh``, ``h_i`` after the final norm, sigmoid scores and a correction
bias): this file implements the same readings. The served program keeps the
module's latent of ``u_i`` at slot ``i + 1`` of a page and rotates it as that
position; scores under RoPE depend on distances alone, so this file's
positions ``0..i`` give the same function (``tests/test_mtp.py`` holds the
two together).

Computed in blocks of queries and of heads, an expert at a time, so that it
fits beside the served model at the published widths.

**A top-k router needs more than one answer** (``kvbench/README.md``):
``alternatives_at`` returns ``logits_at``'s row first and then the full
forward's logits under the other choices of experts the definition admits
at that position (scores within ``MARGIN``), over the routed layers as a
tree, the nearest first and ``LIMIT`` rows at most. Choices at earlier
positions are not branched.

``TOLERANCE`` and ``MARGIN``: see the constants, each with the readings it
was set from.
"""

from __future__ import annotations

import heapq
import itertools

import jax
import jax.numpy as jnp
import numpy as np

# Largest difference over the reference's largest logit, as
# ``harness/correct.py: probe`` takes it (2048 positions and 8 decoded, one
# v5e, the published widths; my chip runs, PR 53; PERF.md section 6). It lies
# between two readings:
# - the served program against this reference: 0.0080-0.0097 at the last
#   prompt position over five seeds (a hit 0.0081-0.0099, a decoded token at
#   most 0.0004 short); with the embedding drawn at 0.02 0.0087-0.0099 (three
#   seeds, a token up to 0.0027 short); in ``hack/mtp_accept_path.py`` both
#   verified positions of a step that accepts 0.0109 and 0.0098, the module's
#   logits at both 0.0077 and 0.0072;
# - THIS file served in the engine's place with its activations rounded to
#   the nearest type below the served one (``Control``, float8_e4m3fn): 0.138
#   (a decoded token 0.097 short), not correct.
# One planted fault reads as sound and is NOT told by this limit: the verify
# step's mask off by one (the first position sees the draft's key too:
# ``hack/kvbench_probe_readings.py --fault verify-mask``) reads 0.0090, a
# token 0.0040 short. One key more among 2,049 of a nearly flat softmax
# moves a logit by a two-thousandth; no limit between the sound runs and the
# control catches it. ``tests/test_mtp.py`` does, at toy widths in float32,
# where the tokens must be the one-token decode's (a dozen keys a row).
TOLERANCE = 0.05
# In units of a score (a sigmoid's output): how near the k-th and the next
# score may lie for both choices to be admitted. The accepted configurations
# of this router at these widths (hidden 7168-7680, 256 outputs, 8 a token)
# measured a bfloat16 rounding's move of that gap at 9.2e-4 (median) and
# 6.9e-3 (99th percentile) over 16,384 readings and set 6e-3 (PR 34). This
# model's own (my chip runs, PR 53: 3 seeds x 9 positions x 4 routed layers,
# ``hack/kvbench_probe_readings.py --margins``): 3.1e-4 to 3.9e-4 (median),
# 8.1e-4 to 1.1e-3 (90th), 1.8e-3 the largest of 108: too few for a tail, so
# the limit stays where 16 k readings put it. At 6e-3 the probe's 54
# positions of six seeds had 1.44 answers each and 4 at most.
MARGIN = 6e-3
# The answers a position is given: the probe refuses more than 8, so the
# nearest 8. A layer's enumeration stops at twice that.
LIMIT = 8
# Queries a block of attention; heads at a time; rows a block of a
# feed-forward; columns of an inner width at a time.
BLOCK = 128
HEADS = 32
ROWS = 1024
COLUMNS = 4608


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _done(x):
    """Wait for a block's result before the next block is enqueued: the
    runtime allocates a computation's buffers when it is enqueued, and a
    loop of blocks enqueued at once holds all their float32 copies at
    once."""
    return jax.block_until_ready(x)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _swiglu(h, gate, up, down):
    """``COLUMNS`` of the inner width at a time: the float32 copies of a
    dense layer's three matrices would be 1.7 GB at once."""
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
    """cos, sin ``[n, 1, dims / 2]`` for positions ``0..n-1``: plain RoPE."""
    if cfg.rope_scaling:
        raise NotImplementedError("this model's RoPE is not scaled")
    half = dims // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(half, dtype=np.float64) / half))
    angles = np.arange(n, dtype=np.float64)[:, None] * freqs
    return (jnp.asarray(np.cos(angles)[:, None, :], jnp.float32),
            jnp.asarray(np.sin(angles)[:, None, :], jnp.float32))


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
def _attend_block(q, k, v, first, scale):
    """Causal attention of the queries at positions ``first..`` over the
    sequence's keys."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    keep = (jnp.arange(k.shape[0])[None, :]
            <= first + jnp.arange(q.shape[0])[:, None])
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khv->qhv", probs, v)


def _attention(h, layer, cfg, tables):
    """Multi-head latent attention over one sequence, keys and values
    materialised from the latent, ``HEADS`` heads at a time."""
    s = h.shape[0]
    heads, hd, dr = cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    cos, sin = tables
    q_lat, c_kv, k_rope_in = _attention_inputs(h, layer, cfg)
    k_rope = _rope(k_rope_in[:, None, :], cos, sin)             # [s, 1, dr]
    scale = (hd + dr) ** -0.5 * cfg.softmax_scale_mult
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
            [_done(_attend_block(q[lo:lo + BLOCK], k, v, lo, scale))
             for lo in range(0, s, BLOCK)], 0)
        out = _done(out + attn.reshape(s, n * hd)
                    @ _f32(layer["wo"][g * hd:(g + n) * hd]))
    return out


# -- the router ---------------------------------------------------------------


def admitted(values: np.ndarray, k: int, margin: float) -> list:
    """The top ``k`` of ``values`` as ``(need, sorted index tuple)``: first
    the definition's own (equal values: the lower index, as ``lax.top_k``;
    its need ``-inf``), then every other set that is the top k once each
    value has moved by under ``margin / 2``, with how far the scores must
    move for it: the largest it leaves out less the smallest it takes."""
    order = np.argsort(-values, kind="stable")
    top = tuple(sorted(int(i) for i in order[:k]))
    sets = [(-np.inf, top)]
    if k >= len(values) or margin <= 0.0:
        return sets
    kth, nxt = values[order[k - 1]], values[order[k]]
    ins = [int(i) for i in order[:k] if values[i] - nxt < margin]
    outs = [int(i) for i in order[k:] if kth - values[i] < margin]
    for j in range(1, min(len(ins), len(outs)) + 1):
        for drop in itertools.combinations(ins, j):
            for add in itertools.combinations(outs, j):
                took = (set(top) - set(drop)) | set(add)
                need = (max(v for i, v in enumerate(values) if i not in took)
                        - min(values[i] for i in took))
                if need < margin:
                    sets.append((float(need), tuple(sorted(took))))
                if len(sets) > 2 * LIMIT:
                    return sets
    return sets


def choices(scores: np.ndarray, bias: np.ndarray, k: int, margin: float,
            held: tuple) -> list:
    """Every choice of experts one position's scores admit, as ``(need,
    experts)``: the definition's own first, the others by how far the
    scores must move for them. ``held = (first, count)``: choices that
    differ only in experts another chip holds give this chip the same terms
    but for the sum they are normalised by, which two scores within
    ``margin`` of each other move by under ``margin`` of some 5: the nearest
    of them stands for all."""
    def here(experts):
        return tuple(e for e in experts if held[0] <= e < held[0] + held[1])

    own, *rest = admitted(scores + bias, k, margin)
    others: dict = {}
    for need, experts in rest:
        if here(experts) not in others or need < others[here(experts)][0]:
            others[here(experts)] = (need, experts)
    others.pop(here(own[1]), None)
    return [own] + sorted(others.values())


def _deciding_gap(scores, bias, k) -> float:
    """The k-th minus the next expert's score."""
    e = np.sort(scores + bias)[::-1]
    return float(e[k - 1] - e[k])


@jax.jit
def _expert(h, weight, gate, up, down):
    return weight[:, None] * _swiglu(h, gate, up, down)


def _routed(h, layer, cfg, li, positions, forced, ties, gaps, margin):
    """A routed layer's experts and shared expert over every position."""
    _kind, n_group, _topk_group, norm, factor = cfg.moe_router
    if n_group != 1:
        raise NotImplementedError("this model's router has one group")
    k = cfg.num_experts_per_token
    first, held = cfg.experts_held or (0, cfg.num_experts)
    scores = np.asarray(jax.nn.sigmoid(h @ _f32(layer["router"])))
    bias = np.asarray(layer["router_bias"], np.float32)
    took = np.argsort(-(scores + bias[None, :]), axis=1,
                      kind="stable")[:, :k]                        # [s, k]
    ties[li], gaps[li] = {}, {}
    for p in positions:
        ties[li][p] = choices(scores[p], bias, k, margin, (first, held))
        gaps[li][p] = _deciding_gap(scores[p], bias, k)
        took[p] = ties[li][p][0][1]
    for p, experts in forced.get(li, {}).items():
        took[p] = experts
    w = np.take_along_axis(scores, took, axis=1)
    if norm:
        w = w / (w.sum(1, keepdims=True) + 1e-20)
    weights = np.zeros(scores.shape, np.float32)
    np.put_along_axis(weights, took, w * factor, axis=1)
    weights = jnp.asarray(weights[:, first:first + held])        # [s, held]
    out = _feed_forward(h, layer, "_sh")
    for e in range(held):
        out = _done(out + _expert(h, weights[:, e], layer["w_gate"][e],
                                  layer["w_up"][e], layer["w_down"][e]))
    return out


# -- the forward --------------------------------------------------------------


def _check(params, cfg):
    if not (cfg.is_mla and cfg.q_lora_rank and cfg.post_norms):
        raise NotImplementedError(
            "this reference covers latent attention with q-LoRA in a block "
            "normed before and after each sub-layer")
    if cfg.num_experts and not (cfg.moe_router
                                and cfg.moe_router[0] == "deepseek_v3"):
        raise NotImplementedError(
            "this reference covers DeepSeek-V3's router")


def _block(x, layer, cfg, li, tables, act, routing):
    """One block over the whole sequence: ``x [s, h]`` in, the same out."""
    eps = cfg.norm_eps
    h = act(_rms_norm(x, layer["attn_norm"], eps))
    x = act(x + _rms_norm(_attention(h, layer, cfg, tables),
                          layer["attn_post_norm"], eps))
    h = act(_rms_norm(x, layer["mlp_norm"], eps))
    y = (_routed(h, layer, cfg, li, *routing) if "router" in layer
         else _feed_forward(h, layer))
    return act(x + _rms_norm(y, layer["mlp_post_norm"], eps))


def _head(x, norm, params, cfg):
    x = _rms_norm(x, norm, cfg.norm_eps)
    head = params["lm_head"]
    return jnp.concatenate(
        [_done(x @ _f32(head[:, lo:lo + COLUMNS]))
         for lo in range(0, head.shape[1], COLUMNS)], -1)


def _forward(params, cfg, tokens, positions, forced=None, rounded=None,
             margin=None, module=False):
    """One full forward over ``tokens``. Returns ``(logits, ties, gaps)``:
    float32 logits at ``positions``; ``ties[layer][position]`` the admitted
    choices there (the definition's first); ``gaps[layer][position]`` the
    distance between the k-th and the next score, for ``margin_readings``.
    ``forced`` is ``{layer: {position: experts}}``. ``rounded`` (a type's
    name) rounds activations to that type where the served type rounds
    them, to measure ``MARGIN`` (``"bfloat16"``) and the control below the
    stated precision (``"float8_e4m3fn"``, ``Control``); nothing that
    decides ``correct`` sets it. ``module``: the logits are the prediction
    module's, of the token after next (``draft_logits_at``); the module's
    layer is numbered behind the main ones."""
    forced = forced or {}
    margin = MARGIN if margin is None else margin
    act = ((lambda x: x.astype(jnp.dtype(rounded)).astype(jnp.float32))
           if rounded else (lambda x: x))
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    tables = rope_tables(cfg, n, cfg.qk_rope_head_dim)
    ties: dict = {}
    gaps: dict = {}
    routing = (positions, forced, ties, gaps, margin)
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"][tokens].astype(jnp.float32))
        for li, layer in enumerate(params["layers"]):
            x = _block(x, layer, cfg, li, tables, act, routing)
        if not module:
            out = _head(x[jnp.asarray(positions)], params["final_norm"],
                        params, cfg)
            return np.asarray(out, np.float32), ties, gaps
        mtp = params["mtp"]
        hidden = act(_rms_norm(x, params["final_norm"], cfg.norm_eps))
        # Row i: the token after position i beside position i's hidden
        # state. The last position has no next token: its row is unused.
        after = jnp.concatenate([tokens[1:], tokens[:1]])
        u = act(jnp.concatenate(
            [_rms_norm(params["embed"][after].astype(jnp.float32),
                       mtp["enorm"], cfg.norm_eps),
             _rms_norm(hidden, mtp["hnorm"], cfg.norm_eps)], -1)
            @ _f32(mtp["w_eh"]))
        u = _block(u, mtp["layer"], cfg, len(params["layers"]), tables, act,
                   routing)
        out = _head(u[jnp.asarray(positions)], mtp["final_norm"], params,
                    cfg)
    return np.asarray(out, np.float32), ties, gaps


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions, every position
    taking the definition's own choice of experts."""
    _check(params, cfg)
    return _forward(params, cfg, tokens, list(positions))[0]


def draft_logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of the prediction module
    at the given positions ``i`` (each below ``len(tokens) - 1``): of the
    token after next, ``t_{i+2}``, from the full forward's hidden state
    ``h_i`` and the token ``t_{i+1}``, every layer taking the definition's
    own choice of experts. The harness does not call this; the tests and
    ``hack/mtp_accept_path.py`` do."""
    _check(params, cfg)
    positions = list(positions)
    if positions and max(positions) >= len(tokens) - 1:
        raise ValueError("the module's row at a position needs its next "
                         "token")
    return _forward(params, cfg, tokens, positions, module=True)[0]


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
    """How far rounding the activations to bfloat16 moves the gap that
    decides each routed layer's choice at each position, in score units:
    ``(the gap's change, 0.0)`` (one group: no group's gap). What
    ``MARGIN`` is set from."""
    _check(params, cfg)
    positions = list(positions)
    _, _, exact = _forward(params, cfg, tokens, positions)
    _, _, low = _forward(params, cfg, tokens, positions,
                         rounded="bfloat16")
    return [(abs(exact[li][p] - low[li][p]), 0.0)
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
