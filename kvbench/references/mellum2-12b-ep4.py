"""The plain reference of ``mellum2-12b-ep4``: Mellum 2's block as its
``config.json`` sizes it, one chip's share of it, in ``jax.numpy`` and
float32 at ``jax.default_matmul_precision("highest")``. No kernels, no
cache, no pools, no pages, no batching, no chunks: one sequence, every
position attending what the definition says it sees. Nothing of the
program is imported; it is handed the program's weight tree (fused by
``maybe_fuse_params`` or not) and reads the numbers of ``cfg``.

- ``h = E[token]``. Layer ``l``: ``u = N(h)``; ``h += Attn_l(u)``; ``v =
  N(h)``; ``h += sum_{e in top k, held} g_e Expert_e(v)``. ``logits = N(h)
  W_head``. ``N(x) = x / rms(x) * w`` at ``rms_norm_eps``.
- ``Attn_l``: GQA, ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; where
  ``cfg.qk_norm`` (the configuration's one assumption about the block), a
  per-head RMSNorm on q and k; RoPE over all of a head's dims at
  ``cfg.rope_theta`` BY THE LAYER'S KIND: a window layer (``cfg.swa_layers``)
  takes ``cfg.swa_rope_scaling`` where the model has one (``("default",)``:
  plain), every other layer ``cfg.rope_scaling`` (yarn: dims below the
  ``beta_fast`` bound keep their frequency, above the ``beta_slow`` bound
  divide it by ``factor``, a linear ramp between, bounds truncated; cos
  and sin times ``attention_factor``); scores ``q k / sqrt(head_dim)``;
  causal, and in a window layer a query at ``i`` sees keys ``j > i -
  sliding_window``; softmax; ``W_o``.
- Feed-forward, every layer: ``r = v W_r`` over all experts, the ``k``
  largest (equal logits: the lower index), ``g = softmax`` over those ``k``
  (``norm_topk_prob``); ``y = sum_{e chosen and held} g_e Expert_e(v)``
  with the experts ``cfg.experts_held`` says this chip holds, each a
  SwiGLU; no shared expert. What the absent experts would add is left out,
  here as in the program.

**Departures from the published description**: one chip's share of the
experts and of the vocabulary is what is computed (the configuration's
``reduced``); the norm on q and k is the ``qwen3_moe`` config class's,
whose keys the config carries, and the config has no key for it
(``assumed``).

**A top-k router needs more than one answer** (``kvbench/README.md``): the
program computes in bfloat16, so where the logits that decide a position's
choice lie closer than that rounding moves them, program and reference
choose differently, both by right. ``alternatives_at`` returns
``logits_at``'s row first and then the full forward's logits under the other
choices the definition admits at that position (logits within ``MARGIN``),
over the routed layers as a tree, the nearest first and ``LIMIT`` rows at
most. Only a position's OWN choices are branched: attention hands a
position its predecessors' hidden states as one term among hundreds or
thousands, and nothing else does.

``TOLERANCE`` and ``MARGIN``: see the constants, each with its readings.
"""

from __future__ import annotations

import heapq
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Between two readings taken on one v5e at the published widths with
# ``harness/correct.py: probe`` (largest difference over the reference's
# largest logit; 4098 positions and 8 decoded through both pools, the hit
# on a trailing window; PERF.md section 6, PR 63, has every number). The
# served program against this reference: 0.0057-0.0069 over the first five
# seeds (the cell's runs since: PERF.md), a quarter of what the two dense
# cells read at the same depth (why is not known). And THIS file served in the engine's place with its
# activations rounded to float8_e4m3fn (``Control``), which has to come out
# as not correct: 0.128 and 0.142 over two seeds. The limit is about their
# geometric mean, four times of room on either side. The three ways to
# serve the wrong model that the cell exists to tell (``hack/
# kvbench_probe_readings.py --serve``): yarn in every layer 0.27, yarn in
# none 0.19, the window ignored in the window layers 0.99.
TOLERANCE = 0.03
# In units of a router logit (64 logits of standard deviation about 1, the
# 8 largest chosen): bfloat16 moves the gap between the 8th and the 9th by
# 6.1e-3 at the median, 1.4e-2 at the 90th percentile, 2.7e-2 at the 99th
# and 3.5e-2 at most (504 readings on the chip, PR 63, two seeds): the
# 99th percentile, as granite-4.0-h-small's and solar-open2's references
# set theirs.
MARGIN = 3e-2
# The answers a position is given: the probe refuses more than 8.
LIMIT = 8
# Queries a block of attention, rows a block of a feed-forward, columns of
# a matrix at a time: so that 4 k positions fit beside the served model.
BLOCK = 256
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


def rope_frequencies(head_dim: int, theta: float, rule: tuple):
    """``(frequencies [head_dim / 2], factor on cos and sin)`` of one
    layer kind's rule: ``()`` plain, ``("yarn", factor, beta_fast,
    beta_slow, original_max, attention_factor)``."""
    half = head_dim // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    if not rule:
        return freqs, 1.0
    if rule[0] != "yarn":
        raise NotImplementedError(f"rope rule {rule!r}")
    _, factor, beta_fast, beta_slow, orig, att = rule

    def bound(rotations):
        return (head_dim * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(bound(beta_fast)), 0)
    high = min(math.ceil(bound(beta_slow)), head_dim - 1)
    keep = 1.0 - np.clip((np.arange(half) - low) / max(high - low, 0.001),
                         0.0, 1.0)
    return freqs / factor * (1.0 - keep) + freqs * keep, att


def _rope(x, freqs, att):
    """``x [s, heads, d]`` rotated by position, the half-split pairing
    (dim ``i`` with ``i + d / 2``)."""
    half = x.shape[-1] // 2
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(freqs, jnp.float32)[None, :])[:, None, :]
    cos, sin = jnp.cos(angles) * att, jnp.sin(angles) * att
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@jax.jit
def _attend_block(q, k, v, first, window):
    """``q [n, kv heads, group, d]`` at positions ``first ...``, ``k, v
    [s, kv heads, d]``; ``window`` 0: every earlier key."""
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) * q.shape[-1] ** -0.5
    at = first + jnp.arange(q.shape[0])[:, None]
    keys = jnp.arange(k.shape[0])[None, :]
    keep = (keys <= at) & ((window == 0) | (keys > at - window))
    probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -jnp.inf), -1)
    return jnp.einsum("hgqk,khv->qhgv", probs, v)


def _layer_rule(cfg, li):
    """``(window or 0, rope rule)`` of layer ``li``, by its kind."""
    windowed = cfg.sliding_window is not None and li in cfg.swa_layers
    rule = cfg.rope_scaling
    if windowed and cfg.swa_rope_scaling:
        rule = cfg.swa_rope_scaling
    return (cfg.sliding_window if windowed else 0,
            () if tuple(rule) == ("default",) else tuple(rule))


def _attention(h, layer, cfg, li):
    s = h.shape[0]
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window, rule = _layer_rule(cfg, li)
    q, k, v = _qkv(h, layer, cfg)
    q, k = q.reshape(s, heads, hd), k.reshape(s, kvh, hd)
    if cfg.qk_norm:
        q = _norm(q, layer["q_norm"], cfg.norm_eps)
        k = _norm(k, layer["k_norm"], cfg.norm_eps)
    freqs, att = rope_frequencies(hd, cfg.rope_theta, rule)
    q = _rope(q, freqs, att).reshape(s, kvh, heads // kvh, hd)
    k, v = _rope(k, freqs, att), v.reshape(s, kvh, hd)
    attn = jnp.concatenate(
        [_done(_attend_block(q[lo:lo + BLOCK], k, v, lo, window))
         for lo in range(0, s, BLOCK)], 0).reshape(s, heads * hd)
    return _done(_matmul(attn, layer["wo"]))


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
    """A routed layer's held experts over every position."""
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
    out = jnp.zeros_like(h)
    for e in range(held):
        out = _done(out + _expert(
            h, weights[:, e], _f32(layer["w_gate"][e]),
            _f32(layer["w_up"][e]), _f32(layer["w_down"][e])))
    return out


# -- the forward --------------------------------------------------------------


def _check(params, cfg):
    if (cfg.is_mla or cfg.linear_layers or cfg.norm_offset or cfg.post_norms
            or cfg.swiglu_limit or cfg.attn_output_gate
            or cfg.attention_sinks or cfg.has_multipliers):
        raise NotImplementedError(
            "this reference covers GQA layers, windowed or full, under "
            "plain pre-norm")
    if not (tuple(cfg.moe_router) == ("softmax_topk", 1)
            and all("router" in layer and "w_gate_sh" not in layer
                    and "w_gate_up_sh" not in layer
                    for layer in params["layers"])):
        raise NotImplementedError(
            "this reference covers the softmax over the chosen logits in "
            "every layer and no shared expert")


def _forward(params, cfg, tokens, positions, forced=None, rounded=None,
             margin=None):
    """One full forward over ``tokens``. Returns ``(logits, ties, gaps)``:
    float32 logits at ``positions``; ``ties[layer][position]`` the admitted
    choices (the definition's first) there; ``gaps[layer][position]`` the
    distance between the k-th and the next router logit there, for
    ``margin_readings``. ``forced`` is ``{layer: {position: experts}}``.
    ``rounded`` (a type's name) rounds activations to that type where the
    served type rounds them: the control below the stated precision
    (``Control``) and ``MARGIN``'s readings; nothing that decides
    ``correct`` sets it."""
    forced = forced or {}
    margin = MARGIN if margin is None else margin
    act = ((lambda x: x.astype(jnp.dtype(rounded)).astype(jnp.float32))
           if rounded else (lambda x: x))
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = cfg.norm_eps
    ties: dict = {}
    gaps: dict = {}
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"][tokens].astype(jnp.float32))
        for li, layer in enumerate(params["layers"]):
            h = act(_norm(x, layer["attn_norm"], eps))
            x = act(x + _attention(h, layer, cfg, li))
            h = act(_norm(x, layer["mlp_norm"], eps))
            x = act(x + _routed(h, layer, cfg, li, positions, forced, ties,
                                gaps, margin))
        x = _norm(x[jnp.asarray(positions)], params["final_norm"], eps)
        out = _matmul(x, params["lm_head"])
    return np.asarray(out, np.float32), ties, gaps


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions, every position
    taking the definition's own choice of experts."""
    _check(params, cfg)
    return _forward(params, cfg, tokens, list(positions))[0]


def alternatives_at(params, cfg, tokens, positions) -> list:
    """One float32 array ``[A, vocab]`` per position: row 0 is
    ``logits_at``'s row; every further row is one more full forward, under
    another admitted choice of experts at one or more of the positions'
    own routed layers, the nearest first and ``LIMIT`` rows at most: the
    forward takes the nearest path not yet run, and what it finds below the
    path's last choice joins the queue. Every position reads its row from
    every forward; a path that touches nothing a position hangs on leaves
    it its first row again (to rounding)."""
    _check(params, cfg)
    positions = list(positions)
    sites = sorted((li, p) for li in range(len(params["layers"]))
                   for p in positions)
    base, ties, _ = _forward(params, cfg, tokens, positions)
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
        logits, below, _ = _forward(params, cfg, tokens, positions, forced)
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
    a precision below the served one (``rounded``: the type its activations
    are rounded to, ``float8_e4m3fn``), standing where ``harness/correct.py:
    probe`` expects an engine (``enqueue``, ``step``, a request's ``done``,
    ``last_logits``, ``output``, ``cached_len``), so that the control comes
    out as not correct by the cell's own comparison. Greedy, one full
    forward a token; a prompt seen before is answered from what was kept,
    as a prefix hit."""

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
