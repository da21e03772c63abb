"""A test fixture's plain reference for a model that routes: latent
attention as the DeepSeek-V2 paper writes it (``latent_reference.py``; not
absorbed) and, after the first dense layer, DeepSeek-V3's routed experts as
its model card writes them, in float32.

A routed layer: ``s = sigmoid(h @ router)``; a group's score is the sum of
its two best ``s + bias``; the best ``topk_group`` groups are kept; of their
experts the best ``num_experts_per_token`` by ``s + bias`` are chosen; the
chosen experts' ``s`` (without the bias), normalised to sum to 1 and
multiplied by the routed scaling factor, weigh their SwiGLU outputs; the
shared expert is added. No kernels, no cache, no batching, no dispatch:
every expert runs over every position and the weights pick.
``jax.default_matmul_precision("highest")``. The weights are the engine's
own tree, upcast; nothing else is taken from the program.

**Why one answer is not enough, and what ``alternatives_at`` returns.** A
top-k is discontinuous. The program computes ``h`` in bfloat16, this file
in float32, so the two see scores that differ by the rounding of ``h``; where
the scores that decide a choice (the 4th and 5th expert, the 2nd and 3rd
group) lie closer than that, program and reference pick different experts,
both by right, and a whole expert's output (a quarter of 8 times the routed
output here) is in one answer and not in the other: far more than
``TOLERANCE``. So at every position asked for, ``alternatives_at`` returns
row 0 = ``logits_at``'s row and one more row for every other choice of
experts that the definition admits there: a choice is admitted where moving
every deciding score by under ``MARGIN / 2`` makes it the top k (groups: a
group's score is a sum of two, so ``MARGIN`` each way). The rows form a
tree: a different choice in one layer changes the scores of the next, whose
near-ties are looked for again along that branch. Choices at *earlier*
positions are not branched: they reach this position only as one key and
value among the attended. Another expert at one of the earlier positions
moved the last position's logits by at most 0.43% of their largest, at the
position itself by at least 23% (8 seeds x 3 positions;
``test_routed_probe.py``, which prints both).

**MARGIN, measured.** ``MARGIN`` is in units of a score (a sigmoid's
output). ``margin_readings`` (200 seeds x 4 positions x 2 routed layers at
this fixture's widths, CPU): with the activations rounded to bfloat16 where
the configuration's ``torch_dtype`` rounds them (each layer's normed input
and its residual), the gap between the two scores that decide a position's
top 4 moves by 1.9e-4 (median) and 1.1e-3 (99th percentile), the gap
between two groups' scores by 2.7e-4 and 1.35e-3; the few readings above
are positions whose earlier layer had itself chosen otherwise. ``MARGIN``
stands above the 99th percentile. Against the served program
(``correct.probe``, CPU, seeds 1000-1299, of which the test runs the first
200): at ``MARGIN`` 0, which is one answer a position, 2 seeds of the first
100 fail; at 5e-4, 1 of 100; at 1e-3, none of 300, with 1.20 alternatives a
position; at 1.5e-3, none of 300, 1.32 a position, at most 7; at 2e-3,
1.44-1.5 a position. So 1.5e-3: half as much again as the smallest that let
every seed through.

Tolerance: as ``kvbench/reference.py`` reasons for bf16 activations.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.05
MARGIN = 1.5e-3
# Enumeration stops here: the probe refuses more than 8 anyway.
LIMIT = 16


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * jnp.asarray(w, jnp.float32)


def _rope(x, cos, sin):
    """x: [s, heads, d]; cos, sin: [s, 1, d/2] (rotate-half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, layer, cos, sin, cfg):
    """Multi-head latent attention over one sequence, keys and values
    materialised from the latent (``latent_reference.py``)."""
    def f32(name):
        return jnp.asarray(layer[name], jnp.float32)

    s = h.shape[0]
    heads, hd, dr = cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    q = (h @ f32("wq")).reshape(s, heads, hd + dr)
    q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], cos, sin)], -1)
    c_kv = h @ f32("w_dkv")
    k_rope = _rope((h @ f32("w_kr"))[:, None, :], cos, sin)
    k_nope = jnp.einsum("sr,hrd->shd", c_kv, f32("w_uk"))
    v = jnp.einsum("sr,hrv->shv", c_kv, f32("w_uv"))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (s, heads, dr))], -1)
    scores = (jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd + dr)
              * cfg.softmax_scale_mult)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khv->qhv", probs, v).reshape(s, heads * hd)
    return attn @ f32("wo")


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


# -- the router ---------------------------------------------------------------


def admitted(values: np.ndarray, k: int, margin: float) -> list:
    """The top ``k`` of ``values`` as sorted index tuples: first the
    definition's own (equal values: the lower index, as ``lax.top_k``),
    then every other set that is the top k once each value has moved by
    under ``margin / 2``: the largest it leaves out is less than ``margin``
    above the smallest it takes."""
    order = np.argsort(-values, kind="stable")
    top = tuple(sorted(int(i) for i in order[:k]))
    if k >= len(values):
        return [top]
    kth, nxt = values[order[k - 1]], values[order[k]]
    ins = [int(i) for i in order[:k] if values[i] - nxt < margin]
    outs = [int(i) for i in order[k:] if kth - values[i] < margin]
    sets = [top]
    for j in range(1, min(len(ins), len(outs)) + 1):
        for drop in itertools.combinations(ins, j):
            for add in itertools.combinations(outs, j):
                took = (set(top) - set(drop)) | set(add)
                left = [v for i, v in enumerate(values) if i not in took]
                if max(left) - min(values[i] for i in took) < margin:
                    sets.append(tuple(sorted(took)))
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


def choices(scores: np.ndarray, bias: np.ndarray, router: tuple, k: int,
            margin: float) -> list:
    """Every choice of experts one position's scores admit, the
    definition's own first."""
    _kind, n_group, topk_group, _norm, _factor = router
    choice = scores + bias
    out: list = []
    for groups in admitted(_group_scores(choice, n_group), topk_group,
                           2 * margin):
        for experts in admitted(_within(choice, n_group, groups), k, margin):
            if experts not in out:
                out.append(experts)
    return out


# -- the forward --------------------------------------------------------------


def _check(params, cfg):
    if (not cfg.is_mla or not cfg.num_experts or cfg.rope_scaling
            or not cfg.moe_router or cfg.moe_router[0] != "deepseek_v3"):
        raise NotImplementedError(
            "this fixture covers latent attention with plain RoPE and "
            "DeepSeek-V3's router")
    if any(k in layer for layer in params["layers"]
           for k in ("w_mla_in", "w_gate_up_sh", "w_dq", "latent_norm")):
        raise NotImplementedError("this fixture reads the unfused tree, "
                                  "without q-LoRA or a latent norm")


def _forward(params, cfg, tokens, positions, forced=None, rounded=False):
    """One full forward over ``tokens``. Returns ``(logits, ties, gaps)``:
    float32 logits at ``positions``; ``ties[layer][position]`` the admitted
    choices there (the definition's first); ``gaps[layer][position]`` the
    distance between the 4th and 5th score and between the 2nd and 3rd
    group, for ``margin_readings``. ``forced`` is ``{layer: (position,
    experts)}``: that position takes those experts in that layer.
    ``rounded`` rounds activations to bfloat16 where the served type does,
    to measure ``MARGIN``; nothing that decides ``correct`` sets it."""
    forced = forced or {}
    act = ((lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))
           if rounded else (lambda x: x))
    tokens = jnp.asarray(tokens, jnp.int32)
    half = cfg.qk_rope_head_dim // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(half, dtype=np.float64) / half))
    angles = np.arange(tokens.shape[0], dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles)[:, None, :], jnp.float32)
    sin = jnp.asarray(np.sin(angles)[:, None, :], jnp.float32)
    router, k = cfg.moe_router, cfg.num_experts_per_token
    ties: dict = {}
    gaps: dict = {}
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"][tokens].astype(jnp.float32))
        for li, layer in enumerate(params["layers"]):
            def f32(name, layer=layer):
                return jnp.asarray(layer[name], jnp.float32)

            h = act(_rms_norm(x, layer["attn_norm"], cfg.norm_eps))
            x = act(x + _attention(h, layer, cos, sin, cfg))
            h = act(_rms_norm(x, layer["mlp_norm"], cfg.norm_eps))
            if "router" not in layer:
                x = act(x + _swiglu(h, f32("w_gate"), f32("w_up"),
                                    f32("w_down")))
                continue
            scores = np.asarray(jax.nn.sigmoid(h @ f32("router")))
            bias = np.asarray(layer["router_bias"], np.float32)
            weights = np.zeros(scores.shape, np.float32)
            ties[li], gaps[li] = {}, {}
            for p in range(scores.shape[0]):
                if p in positions:
                    ties[li][p] = choices(scores[p], bias, router, k, MARGIN)
                    gaps[li][p] = _deciding_gaps(scores[p], bias, router, k)
                    took = ties[li][p][0]
                else:
                    took = choices(scores[p], bias, router, k, 0.0)[0]
                if li in forced and forced[li][0] == p:
                    took = forced[li][1]
                w = scores[p, list(took)]
                if router[3]:
                    w = w / (w.sum() + 1e-20)
                weights[p, list(took)] = w * router[4]
            every = jax.vmap(lambda g, u, d: _swiglu(h, g, u, d))(
                f32("w_gate"), f32("w_up"), f32("w_down"))     # [E, s, h]
            routed = jnp.einsum("se,esh->sh", jnp.asarray(weights), every)
            x = act(x + routed + _swiglu(h, f32("w_gate_sh"), f32("w_up_sh"),
                                         f32("w_down_sh")))
        x = _rms_norm(x[jnp.asarray(positions)], params["final_norm"],
                      cfg.norm_eps)
        out = x @ params["lm_head"].astype(jnp.float32)
    return np.asarray(out, np.float32), ties, gaps


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


def logits_at(params, cfg, tokens, positions) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of a full forward over
    ``tokens`` (one sequence), at the given positions, every position
    taking the definition's own choice of experts."""
    _check(params, cfg)
    return _forward(params, cfg, tokens, list(positions))[0]


def alternatives_at(params, cfg, tokens, positions) -> list:
    """One float32 array ``[A_i, vocab]`` per position: row 0 is
    ``logits_at``'s row; the others the full forward's logits there under
    every other admitted choice of experts at that position (see the
    module's text)."""
    _check(params, cfg)
    positions = list(positions)
    base, ties, _ = _forward(params, cfg, tokens, positions)
    routed = sorted(ties)

    def explore(i, p, forced, path_ties, start, rows):
        for at, li in enumerate(routed[start:], start):
            for other in path_ties[li][p][1:]:
                if len(rows) > LIMIT:
                    return
                took = {**forced, li: (p, other)}
                logits, below, _ = _forward(params, cfg, tokens, positions,
                                            took)
                rows.append(logits[i])
                explore(i, p, took, below, at + 1, rows)

    out = []
    for i, p in enumerate(positions):
        rows = [base[i]]
        explore(i, p, {}, ties, 0, rows)
        out.append(np.stack(rows))
    return out


def margin_readings(params, cfg, tokens, positions) -> list:
    """How far rounding the activations to bfloat16 moves the two gaps that
    decide each routed layer's choice at each position: ``(expert gap's
    change, group gap's change)``, in score units. What ``MARGIN`` is set
    from."""
    _check(params, cfg)
    positions = list(positions)
    _, _, exact = _forward(params, cfg, tokens, positions)
    _, _, low = _forward(params, cfg, tokens, positions, rounded=True)
    return [(abs(exact[li][p][0] - low[li][p][0]),
             abs(exact[li][p][1] - low[li][p][1]))
            for li in exact for p in positions]
