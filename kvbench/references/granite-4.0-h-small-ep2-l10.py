"""The plain reference of ``granite-4.0-h-small-ep2-l10``: Granite 4.0-H
Small's block as its ``config.json`` sizes it, one chip's share of it, in
``jax.numpy`` and float32 at ``jax.default_matmul_precision("highest")``. No
kernels, no cache, no batching, no chunks, no blocks: the Mamba-2 layers run
their recurrence a token at a time. Nothing of the program is imported; it
is handed the program's weight tree (fused by ``maybe_fuse_params`` or not)
and reads the numbers of ``cfg``.

- ``h = embedding_multiplier E[token]``. Layer ``i``: ``u = N(h)``; ``h +=
  residual_multiplier Mixer_i(u)``; ``v = N(h)``; ``h += residual_multiplier
  (Shared(v) + sum_{e in top k} g_e Expert_e(v))``. ``logits = N(h) W_head /
  logits_scaling``. ``N(x) = x / rms(x) * w`` at ``rms_norm_eps``.
- Mamba-2 mixer (``cfg.linear_layers``; ``H`` heads of ``P`` channels, a
  state ``N`` wide, one group): ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(causal depthwise conv of conv_kernel taps with a bias, zeros before
  the first token)``; ``[x | B | C] = xBC``; per head ``d_t = softplus(dt_t
  + dt_bias)``, ``a_t = exp(d_t A)``, ``A = -exp(A_log)``; ``S_t = a_t
  S_{t-1} + d_t x_t (x) B_t`` (float32, ``[P, N]`` a head, from ``S = 0``);
  ``y_t = S_t C_t + D x_t``; ``out = N_all(y * silu(z)) W_out`` with
  ``N_all`` over all ``H x P`` channels at the layers' eps.
- Attention mixer (every other layer): GQA, ``q = u W_q``, ``k = u W_k``,
  ``v = u W_v``, NO positional encoding, causal ``softmax(q k^T x
  attention_multiplier) v``, then ``W_o``.
- Feed-forward, every layer: ``r = v W_r`` over all experts, the ``k``
  largest (equal logits: the lower index), ``g = softmax`` over those ``k``;
  ``y = Shared(v) + sum_{e chosen and held} g_e Expert_e(v)`` with the
  experts ``cfg.experts_held`` says this chip holds, each a SwiGLU, and
  ``Shared`` the always-on SwiGLU. What the absent experts would add is
  left out, here as in the program.

**Departures from the published description**: none in the equations; one
chip's share of the experts and of the vocabulary is what is computed, and
the head is a matrix of its own (the configuration's ``reduced``).

**A top-k router needs more than one answer** (``kvbench/README.md``): the
program computes in bfloat16, so where the logits that decide a position's
choice lie closer than that rounding moves them, program and reference
choose differently, both by right. ``alternatives_at`` returns
``logits_at``'s row first and then the full forward's logits under the other
choices the definition admits at that position (logits within ``MARGIN``),
over the routed layers as a tree, the nearest first and ``LIMIT`` rows at
most. **A position's answer hangs on its neighbours' choices too**: a
Mamba-2 layer's conv hands a position the hidden states of the three before
it at its own weight, and its state those of the tokens a head remembers,
so a choice one to ``REACH`` positions back moves a position's logits as
its own does. Below a Mamba-2 layer those choices are branched with the
position's own (``_sites``); choices further back, and attention's, one
term among thousands, are not.

``TOLERANCE`` and ``MARGIN``: see the constants, each with its readings.
"""

from __future__ import annotations

import heapq
import itertools

import jax
import jax.numpy as jnp
import numpy as np

# Between two readings taken on one v5e at the published widths with
# ``harness/correct.py: probe`` (largest difference over the reference's
# largest logit; 4098 positions and 8 decoded, the hit through a snapshot;
# PERF.md section 6, PR 57, has every number). The served program against
# this reference: 0.018-0.021 over the first three seeds (the cell's runs
# since: PERF.md); under a residual multiplier of 0.22 what bfloat16
# rounds off the stream is not amplified layer by layer as under plain
# pre-norm, and the embedding's multiplier 12 anchors every layer's input
# (the other two hybrids read 0.02-0.06 at their best). And THIS file
# served in the engine's place with its activations rounded to
# float8_e4m3fn (``Control``), which has to come out as not correct:
# 0.208-0.248 over three seeds. The limit is about their geometric mean:
# three times of room on either side. **A state kept in bfloat16
# (``state:bfloat16``) reads 0.018, the sound runs' own reading: no limit
# can tell it from them** (a state rounded every token is a smaller fault
# than the bfloat16 stream the model is served in; tests/test_mamba2.py
# holds the type on the CPU). Planted faults: a stale state 1.01, a
# dropped conv tail 0.92, no residual multiplier 0.75.
TOLERANCE = 0.07
# In units of a router logit (72 logits of standard deviation about 1.3,
# the 10 largest chosen): bfloat16 moves the gap between the 10th and the
# 11th by 1.0e-2 at the median, 2.5-3.0e-2 at the 90th percentile,
# 3.9-5.1e-2 at the 99th and 6.9e-2 at most (180 readings on the chip,
# PR 57, two seeds): the 99th percentile, as solar-open2-ep16-l8 set its.
MARGIN = 5e-2
# The answers a position is given: the probe refuses more than 8.
LIMIT = 8
# Positions back whose routed choices are branched with a position's own
# below a Mamba-2 layer (``_sites``).
REACH = 32
# Queries a block of attention, rows a block of a feed-forward, columns of
# a matrix at a time: so that 4 k positions fit beside the served model.
BLOCK = 128
ROWS = 1024
COLUMNS = 4096


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _done(x):
    """Wait for a block's result before the next is enqueued: a loop of
    blocks enqueued at once holds all their float32 copies at once."""
    return jax.block_until_ready(x)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _matmul(h, w):
    """``h @ w`` in float32, ``COLUMNS`` of ``w`` at a time: at "highest" a
    float32 product keeps several copies of both operands."""
    return jnp.concatenate(
        [_done(h @ _f32(w[:, lo:lo + COLUMNS]))
         for lo in range(0, w.shape[1], COLUMNS)], -1)


def _gate_up(layer, suffix=""):
    """A SwiGLU's gate and up matrices from the unfused tree or the fused
    (``w_gate_up`` = ``[gate | up]``)."""
    fused = layer.get("w_gate_up" + suffix)
    if fused is None:
        return layer["w_gate" + suffix], layer["w_up" + suffix]
    half = fused.shape[-1] // 2
    return fused[:, :half], fused[:, half:]


def _shared_expert(h, layer):
    gate, up = _gate_up(layer, "_sh")
    return jnp.concatenate(
        [_done((jax.nn.silu(h[lo:lo + ROWS] @ _f32(gate))
                * (h[lo:lo + ROWS] @ _f32(up))) @ _f32(layer["w_down_sh"]))
         for lo in range(0, h.shape[0], ROWS)], 0)


def _qkv(h, layer, cfg):
    """GQA's q, k, v from the unfused tree or the fused (``w_qkv`` = ``[q
    | k | v]``, the canonical order)."""
    nq = cfg.num_heads * cfg.head_dim
    nk = cfg.num_kv_heads * cfg.head_dim
    if "w_qkv" in layer:
        qkv = _matmul(h, layer["w_qkv"])
        return qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:]
    return (_matmul(h, layer["wq"]), _matmul(h, layer["wk"]),
            _matmul(h, layer["wv"]))


@jax.jit
def _attend_block(q, k, v, first, scale):
    """``q [n, kv heads, group, d]``, ``k, v [s, kv heads, d]``."""
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) * scale
    keep = (jnp.arange(k.shape[0])[None, :]
            <= first + jnp.arange(q.shape[0])[:, None])
    probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -jnp.inf), -1)
    return jnp.einsum("hgqk,khv->qhgv", probs, v)


def _attention(h, layer, cfg):
    """Grouped-query attention over one sequence, no positional encoding,
    scores times ``cfg.attention_multiplier``."""
    s = h.shape[0]
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _qkv(h, layer, cfg)
    q = q.reshape(s, kvh, heads // kvh, hd)
    k, v = k.reshape(s, kvh, hd), v.reshape(s, kvh, hd)
    attn = jnp.concatenate(
        [_done(_attend_block(q[lo:lo + BLOCK], k, v, lo,
                             cfg.attention_multiplier))
         for lo in range(0, s, BLOCK)], 0).reshape(s, heads * hd)
    return _done(_matmul(attn, layer["wo"]))


@jax.jit
def _conv_silu(mixed, w, bias):
    """A depthwise causal conv with a bias (zeros before the first token)
    and SiLU: ``mixed [s, channels]``, ``w [taps, channels]``."""
    taps, s = w.shape[0], mixed.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mixed.shape[1]), mixed.dtype), mixed], 0)
    return jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps))
                       + bias)


@jax.jit
def _recurrence(x, b, c, d, a, skip, state_type):
    """``S_t = exp(d_t A) S_{t-1} + d_t x_t (x) B_t``, ``y_t = S_t C_t + D
    x_t``, a token at a time from ``S = 0``: ``x [s, H, P]``, ``b, c [s,
    N]``, ``d [s, H]`` the steps, ``a, skip [H]``. ``state_type``: a zero
    of the type the state is rounded to between tokens (float32: not)."""
    def token(S, at):
        x_t, b_t, c_t, d_t = at
        S = (jnp.exp(d_t * a)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if state_type.dtype != jnp.float32:
            # Not a pair of casts: the compiler may keep the excess
            # precision of float32 -> bfloat16 -> float32 and drop both.
            kind = jnp.finfo(state_type.dtype)
            S = jax.lax.reduce_precision(S, kind.nexp, kind.nmant)
        return S, jnp.einsum("hpn,n->hp", S, c_t) + skip[:, None] * x_t

    S0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), jnp.float32)
    return jax.lax.scan(token, S0, (x, b, c, d))[1]


def _mamba(h, layer, cfg, state_type):
    """A Mamba-2 mixer over one sequence ``h [s, hidden]``. The heads do
    not meet before the norm, so they are run ``COLUMNS`` channels at a
    time (conv, recurrence, gate): what is alive at once stays a few
    hundred MB at 4 k positions."""
    la = cfg.linear
    s = h.shape[0]
    heads, p, n = la.value_heads, la.value_dim, la.key_dim
    inner = heads * p
    w_in, conv_w, conv_b = layer["w_in"], layer["conv_w"], layer["conv_b"]

    def conv(lo, hi):
        """Channels ``[lo, hi)`` of ``silu(conv(x B C))``."""
        return _done(_conv_silu(h @ _f32(w_in[:, inner + lo:inner + hi]),
                                _f32(conv_w[:, lo:hi]), _f32(conv_b[lo:hi])))

    bc = conv(inner, inner + 2 * n)
    d = jax.nn.softplus(h @ _f32(w_in[:, 2 * inner + 2 * n:])
                        + _f32(layer["dt_bias"]))
    a, skip = -jnp.exp(_f32(layer["A_log"])), _f32(layer["D"])
    gated = []
    for lo in range(0, inner, COLUMNS):
        hi = min(inner, lo + COLUMNS)
        of = slice(lo // p, hi // p)                     # these heads
        y = _done(_recurrence(
            conv(lo, hi).reshape(s, -1, p), bc[:, :n], bc[:, n:], d[:, of],
            a[of], skip[of], state_type))
        gated.append(_done(y.reshape(s, hi - lo)
                           * jax.nn.silu(h @ _f32(w_in[:, lo:hi]))))
    y = _norm(jnp.concatenate(gated, -1), layer["o_norm"], la.norm_eps)
    return _done(_matmul(y, layer["wo"]))


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


def _need(values: np.ndarray, took) -> float:
    """How far the scores have to move for ``took`` to be the top of
    ``values``: the largest it leaves out less the smallest it takes."""
    inside = np.zeros(len(values), bool)
    inside[list(took)] = True
    return float(values[~inside].max() - values[inside].min())


def choices(logits: np.ndarray, k: int, margin: float, held: tuple) -> list:
    """Every choice of experts one position's router logits admit, as
    ``(need, experts)``: the definition's own first, the others by how far
    the logits must move for them. Choices that differ only in experts
    another chip holds give this chip the same terms but for the sum they
    are normalised by: the nearest of them stands for all."""
    own, others = None, {}
    for experts in admitted(logits, k, margin):
        if own is None:
            own = experts
            continue
        here = tuple(e for e in experts if held[0] <= e < held[0] + held[1])
        need = _need(logits, experts)
        if here not in others or need < others[here][0]:
            others[here] = (need, experts)
    others.pop(tuple(e for e in own if held[0] <= e < held[0] + held[1]),
               None)
    return [(-np.inf, own)] + sorted(others.values())


@jax.jit
def _expert(h, weight, gate, up, down):
    return weight[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)


def _routed(h, layer, cfg, li, positions, forced, ties, gaps, margin):
    """A routed layer's experts and always-on MLP over every position."""
    k = cfg.num_experts_per_token
    first, held = cfg.experts_held or (0, cfg.num_experts)
    logits = np.asarray(h @ _f32(layer["router"]))
    took = np.argsort(-logits, axis=1, kind="stable")[:, :k]
    ties[li], gaps[li] = {}, {}
    for p in positions:
        ties[li][p] = choices(logits[p], k, margin, (first, held))
        ranked = np.sort(logits[p])[::-1]
        gaps[li][p] = float(ranked[k - 1] - ranked[k])
        took[p] = ties[li][p][0][1]
    for p, experts in forced.get(li, {}).items():
        took[p] = experts
    chosen = np.take_along_axis(logits, took, axis=1)
    w = np.exp(chosen - chosen.max(1, keepdims=True))
    weights = np.zeros(logits.shape, np.float32)
    np.put_along_axis(weights, took, w / w.sum(1, keepdims=True), axis=1)
    weights = jnp.asarray(weights[:, first:first + held])        # [s, held]
    out = _shared_expert(h, layer)
    for e in range(held):
        out = _done(out + _expert(
            h, weights[:, e], _f32(layer["w_gate"][e]),
            _f32(layer["w_up"][e]), _f32(layer["w_down"][e])))
    return out


# -- the forward --------------------------------------------------------------


def _check(params, cfg):
    if (cfg.is_mla or cfg.rope_theta or not cfg.linear_layers
            or cfg.linear.decay != "mamba2" or cfg.linear.key_heads != 1
            or cfg.norm_offset or cfg.post_norms or cfg.swiglu_limit
            or cfg.attn_output_gate or cfg.qk_norm):
        raise NotImplementedError(
            "this reference covers GQA without positional encoding in some "
            "layers and Mamba-2 with one group in the others, plain "
            "pre-norm")
    if not (tuple(cfg.moe_router) == ("softmax_topk", 1)
            and all("router" in layer for layer in params["layers"])):
        raise NotImplementedError(
            "this reference covers the softmax over the chosen logits in "
            "every layer")


def _forward(params, cfg, tokens, positions, forced=None, rounded=None,
             margin=None, state="float32", watched=None):
    """One full forward over ``tokens``. Returns ``(logits, ties, gaps)``:
    float32 logits at ``positions``; ``ties[layer][position]`` the admitted
    choices (the definition's first) at the ``watched`` positions, which
    are ``positions`` unless given; ``gaps[layer][position]`` the distance
    between the k-th and the next router logit there, for
    ``margin_readings``. ``forced`` is ``{layer: {position: experts}}``.
    ``rounded`` (a type's name) rounds activations to that type where the
    served type rounds them, ``state`` names the type the Mamba-2 layers'
    state is kept in between tokens: the controls below the stated
    precision (``Control``) and ``MARGIN``'s readings; nothing that decides
    ``correct`` sets either."""
    forced = forced or {}
    margin = MARGIN if margin is None else margin
    watched = positions if watched is None else watched
    act = ((lambda x: x.astype(jnp.dtype(rounded)).astype(jnp.float32))
           if rounded else (lambda x: x))
    state_type = jnp.zeros((), jnp.dtype(state))
    tokens = jnp.asarray(tokens, jnp.int32)
    eps, res = cfg.norm_eps, cfg.residual_multiplier
    ties: dict = {}
    gaps: dict = {}
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"][tokens].astype(jnp.float32)
                * cfg.embedding_multiplier)
        for li, layer in enumerate(params["layers"]):
            h = act(_norm(x, layer["attn_norm"], eps))
            if li in cfg.linear_layers:
                y = _mamba(h, layer, cfg, state_type)
            else:
                y = _attention(h, layer, cfg)
            x = act(x + res * y)
            h = act(_norm(x, layer["mlp_norm"], eps))
            x = act(x + res * _routed(h, layer, cfg, li, watched, forced,
                                      ties, gaps, margin))
        x = _norm(x[jnp.asarray(positions)], params["final_norm"], eps)
        out = _matmul(x, params["lm_head"]) / cfg.logits_scaling
    return np.asarray(out, np.float32), ties, gaps


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions, every position
    taking the definition's own choice of experts."""
    _check(params, cfg)
    return _forward(params, cfg, tokens, list(positions))[0]


def _sites(params, cfg, positions) -> list:
    """The routed choices the answers at ``positions`` hang on, as ``(layer,
    position)`` in the order a forward meets them: each position's own in
    every routed layer and, in a routed layer with a Mamba-2 layer
    after it, those of the ``REACH`` positions before it."""
    sites = set()
    for li, layer in enumerate(params["layers"]):
        if "router" in layer:
            reach = REACH if any(j > li for j in cfg.linear_layers) else 0
            sites.update((li, q) for p in positions
                         for q in range(max(0, p - reach), p + 1))
    return sorted(sites)


def alternatives_at(params, cfg, tokens, positions) -> list:
    """One float32 array ``[A, vocab]`` per position: row 0 is
    ``logits_at``'s row; every further row is one more full forward, under
    another admitted choice of experts at the positions' ``_sites`` (see
    the module's text), the nearest first and ``LIMIT`` rows at most: the
    forward takes the nearest path not yet run, and what it finds below the
    path's last choice joins the queue. Every position reads its row from
    every forward; a path that touches nothing a position hangs on leaves
    it its first row again."""
    _check(params, cfg)
    positions = list(positions)
    sites = _sites(params, cfg, positions)
    watched = sorted({q for _, q in sites})
    base, ties, _ = _forward(params, cfg, tokens, positions, watched=watched)
    rows, queue, order = [base], [], itertools.count()

    def push(path, need, path_ties, start):
        for at in range(start, len(sites)):
            li, q = sites[at]
            for further, experts in path_ties[li][q][1:]:
                heapq.heappush(queue, (max(need, further), next(order),
                                       {**path, (li, q): experts}, at + 1))

    push({}, -np.inf, ties, 0)
    while queue and len(rows) < LIMIT:
        need, _, path, start = heapq.heappop(queue)
        forced: dict = {}
        for (li, q), experts in path.items():
            forced.setdefault(li, {})[q] = experts
        logits, below, _ = _forward(params, cfg, tokens, positions, forced,
                                    watched=watched)
        rows.append(logits)
        push(path, need, below, start)
    return [np.stack([r[i] for r in rows]) for i in range(len(positions))]


def margin_readings(params, cfg, tokens, positions) -> list:
    """How far rounding the activations to bfloat16 moves the gap that
    decides each routed layer's choice at each position, in units of a
    router logit (a pair, as the probe-readings tool prints them: the
    second is the group gap, which this router does not have). What
    ``MARGIN`` is set from."""
    _check(params, cfg)
    positions = list(positions)
    _, _, exact = _forward(params, cfg, tokens, positions)
    _, _, low = _forward(params, cfg, tokens, positions, rounded="bfloat16")
    return [(abs(exact[li][p] - low[li][p]), 0.0)
            for li in exact for p in positions]


class Control:
    """The upper reading ``TOLERANCE`` is set under: this file's forward in
    a precision below the served one, standing where ``harness/correct.py:
    probe`` expects an engine (``enqueue``, ``step``, a request's ``done``,
    ``last_logits``, ``output``, ``cached_len``), so that the control comes
    out as not correct by the cell's own comparison. ``rounded`` names a
    type its activations are rounded to (``float8_e4m3fn``), or, as
    ``state:<type>``, the type the Mamba-2 layers' state is kept in
    between tokens (``state:bfloat16``: what a pool of bfloat16 states
    would serve). Greedy, one full forward a token; a prompt seen before is
    answered from what was kept, as a prefix hit."""

    offload_handlers = None

    def __init__(self, params, cfg, rounded="float8_e4m3fn"):
        _check(params, cfg)
        self.params, self.cfg = params, cfg
        kind, _, value = rounded.partition(":")
        self.low = {"state": value} if kind == "state" else {
            "rounded": rounded}
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
                margin=0.0, **self.low)[0][0]
        if req.last_logits is None:
            req.last_logits = self._seen[tokens]
        req.output.append(int(np.argmax(self._seen[tokens])))
        req.done = len(req.output) >= req.want
