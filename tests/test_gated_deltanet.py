"""Gated DeltaNet layers beside latent attention (``gigachat3.5-ep16-l5``),
at toy widths on the CPU: the recurrence's two kernels against the
definition a token at a time, the engine against the benchmark's float32
reference (logits, never tokens) through chunks of unequal size, the pool,
and a hit through each rule that leaves a snapshot, an expert layer's
shares against the uncut layer, and the loader's refusals."""

import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import live_rows
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from kvbench.harness import fleet as F, names  # noqa: E402
from llmd_kv_cache_tpu.models import llama  # noqa: E402
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine  # noqa: E402
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf  # noqa: E402
from llmd_kv_cache_tpu.ops import gated_deltanet as gd  # noqa: E402
from llmd_kv_cache_tpu.ops.gated_deltanet import gdn_scan, gdn_step  # noqa: E402

CONFIG = "gigachat3.5-ep16-l5"
# Tighter than the probe's own limit (``kvbench/references``: TOLERANCE
# 0.085, set on the chip at the published widths): the served model
# computes in bfloat16 and the reference in float32; at these widths the
# difference reads 0.01-0.03 of the largest logit.
TOLERANCE = 0.05
# A hit recomputes a suffix from a snapshot and cached pages; what it
# differs by from the cold prefill is the rounding of other chunk shapes.
SAME = 0.02


# -- the kernels against the recurrence ---------------------------------------


def recurrence(q, k, v, g, beta, state):
    """``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
    ``o_t = S_t q_t`` a token at a time, in float64; ``state`` and what is
    returned are transposed (``[heads, key_dim, value_dim]``)."""
    rep = v.shape[1] // q.shape[1]
    q, k = np.repeat(q, rep, 1).astype(np.float64), np.repeat(k, rep, 1)
    S = state.astype(np.float64).transpose(0, 2, 1)        # [H, dv, dk]
    outs, states = [], []
    for t in range(q.shape[0]):
        for h in range(v.shape[1]):
            S[h] = np.exp(g[t, h]) * S[h]
            S[h] = S[h] + beta[t, h] * np.outer(v[t, h] - S[h] @ k[t, h],
                                                k[t, h])
        outs.append(np.einsum("hvk,hk->hv", S, q[t]))
        states.append(S.transpose(0, 2, 1).copy())
    return np.stack(outs), states


def inputs(tokens, valid, seed=0, hk=2, hv=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(rng.normal(size=(tokens, hk, dk))) * dk ** -0.5).astype(
        np.float32)
    k = unit(rng.normal(size=(tokens, hk, dk))).astype(np.float32)
    v = rng.normal(size=(tokens, hv, dv)).astype(np.float32)
    live = (np.arange(tokens) < valid)[:, None]
    g = np.where(live, -np.abs(rng.normal(size=(tokens, hv))) * 0.7, 0.0)
    beta = np.where(live, rng.uniform(size=(tokens, hv)), 0.0)
    state = rng.normal(size=(hv, dk, dv)).astype(np.float32)
    return q, k, v, g.astype(np.float32), beta.astype(np.float32), state


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("block", [4, 16])
def test_chunked_scan_is_the_recurrence(kernel, block):
    """A padded chunk (the last 7 tokens are not real): outputs of the
    real tokens, the state at the chunk's end and at a block boundary
    inside it."""
    tokens, valid = 48, 41
    q, k, v, g, beta, state = inputs(tokens, valid)
    want, states = recurrence(q, k, v, g, beta, state)
    snap_block = 32 // block - 1                 # the boundary at token 32
    o, end, inner = gdn_scan(q, k, v, g, beta, state, snap_block,
                             block=block, kernel=kernel, interpret=True)
    np.testing.assert_allclose(o[:valid], want[:valid], atol=2e-5)
    np.testing.assert_allclose(end, states[valid - 1], atol=2e-5)
    np.testing.assert_allclose(inner, states[31], atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_keys_that_lie_close_together_do_not_break_the_block_inverse(kernel):
    """What a conv and a SiLU leave: every key in one orthant, neighbours
    nearly equal, beta near one and hardly any decay, in blocks of 64. The
    powers of the block's triangular system then reach 1e8 and more; the
    scan must still be the recurrence (it was not: the first chip run read
    NaN where the CPU's random keys had passed)."""
    tokens = 128
    rng = np.random.default_rng(4)
    q, k, v, g, beta, state = inputs(tokens, tokens, seed=4, dk=32, dv=16)
    common = np.abs(rng.normal(size=(1, 2, 32)))
    k = common + 0.15 * rng.normal(size=(tokens, 2, 32))
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    assert (k[1:, 0] * k[:-1, 0]).sum(-1).min() > 0.8
    g = (-1e-3 * rng.uniform(size=g.shape)).astype(np.float32)
    beta = (0.9 + 0.1 * rng.uniform(size=beta.shape)).astype(np.float32)
    want, states = recurrence(q, k, v, g, beta, state)
    o, end, inner = gdn_scan(q, k, v, g, beta, state, 0, block=64,
                             kernel=kernel, interpret=True)
    scale = np.abs(want).max()
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, want, atol=2e-3 * scale)
    np.testing.assert_allclose(end, states[-1], atol=2e-3 * scale)
    np.testing.assert_allclose(inner, states[63], atol=2e-3 * scale)


def test_chunks_of_unequal_size_chain_to_the_whole():
    q, k, v, g, beta, state = inputs(48, 48, seed=1)
    want, states = recurrence(q, k, v, g, beta, state)
    at, outs = 0, []
    for size in (16, 24, 8):
        o, state, _ = gdn_scan(*(x[at:at + size] for x in (q, k, v, g, beta)),
                               state, -1, block=8)
        outs.append(o)
        at += size
    np.testing.assert_allclose(np.concatenate(outs), want, atol=2e-5)
    np.testing.assert_allclose(state, states[-1], atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_decode_step_updates_the_rows_slots_in_place(kernel):
    rows = 3
    q, k, v, g, beta, _ = inputs(rows, rows, seed=2, hv=8)
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(2, 6, 8, 16, 8)).astype(np.float32)
    slots = np.array([4, 2, 5], np.int32)
    o, new = gdn_step(jnp.asarray(pool), 1, slots, q, k, v, g, beta,
                      kernel=kernel, interpret=True)
    want = pool.copy()
    for r, slot in enumerate(slots):
        out, states = recurrence(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                 g[r:r + 1], beta[r:r + 1], pool[1, slot])
        np.testing.assert_allclose(o[r], out[0], atol=2e-5)
        want[1, slot] = states[0]
    np.testing.assert_allclose(new, want, atol=2e-5)  # nothing else moved


@live_rows.CASES
def test_decode_step_moves_the_live_rows_states_and_no_other(slots,
                                                             monkeypatch):
    """A row of the spare slot 0 costs the kernel no state: 16 heads a
    state, 8 a grid step, rows that hand in ``g = beta = 0`` as the
    engine's do."""
    hv, dk, dv = 16, 16, 8
    live_rows.two_groups_a_row(monkeypatch, hv * dk * dv * 4)
    q, k, v, g, beta, _ = inputs(live_rows.ROWS, live_rows.ROWS, seed=7,
                                 hv=hv, dk=dk, dv=dv)
    live = (np.asarray(slots) != 0)[:, None]
    g, beta = g * live, beta * live
    pool = np.random.default_rng(7).normal(
        size=(2, live_rows.SLOTS, hv, dk, dv)).astype(np.float32)

    def step(pool, slots, kernel):
        return gdn_step.__wrapped__(pool, 1, slots, q, k, v, g, beta,
                                    kernel=kernel, interpret=kernel)

    live_rows.check(step, pool, slots)


@pytest.mark.parametrize("slots, slot_at, stand", [
    ((3, 5, 0, 0), (3, 5, 5, 5), (-1, -1, 1, 1)),
    ((3, 0, 5, 0), (3, 3, 5, 5), (-1, 1, -1, 1)),
    ((0, 0, 6, 2), (6, 6, 6, 2), (0, 0, -1, -1)),
    ((4, 2, 5, 6), (4, 2, 5, 6), (-1, -1, -1, -1)),
    ((0, 0, 0, 0), (0, 0, 0, 0), (0, 0, 0, 0))],
    ids=["live_first", "spare_between", "spare_first", "all_live",
         "none_live"])
def test_a_row_that_decodes_nothing_stands_at_a_live_rows_block(
        slots, slot_at, stand):
    """The walk of the three step kernels (``_live_walk``, two groups a
    row): a live row walks its own slot's groups (``stand`` -1); a row of
    the spare slot stands at the last group of the live row before it, or
    at the first group of the live row after it where none is before, so
    its grid steps name no block that is not already in fast memory, and
    the spare slot's is named only where nothing is live."""
    (got_slot, got_stand, layer), vec, out, state = gd._live_walk(
        jnp.asarray(slots, jnp.int32), 1, 2)
    assert tuple(np.asarray(got_slot)) == slot_at
    assert tuple(np.asarray(got_stand)) == stand
    blocks = [tuple(int(i) for i in state(r, j, got_slot, got_stand, layer))
              for r in range(4) for j in range(2)]
    assert [b[1] for b in blocks] == [s for s in slot_at for _ in range(2)]
    # A block index changes only where a live row moves on: as many
    # copies in as live rows x groups, and one at least.
    changes = 1 + sum(a != b for a, b in zip(blocks, blocks[1:]))
    assert changes == max(1, 2 * sum(s != 0 for s in slots))
    assert [tuple(int(i) for i in out(r, j)) for r in range(4)
            for j in range(2)] == [(r, j, 0) for r in range(4)
                                   for j in range(2)]
    assert all(int(vec(r, j, got_slot, got_stand, layer)[0]) == r
               for r in range(4) for j in range(2))


# -- the engine against the reference ----------------------------------------


@pytest.fixture(scope="module")
def model():
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=True)
    cfg, params = F.build_model(conf, 11)
    return SimpleNamespace(cfg=cfg, params=params,
                           reference=names.reference(conf))


def engine(model, **kw) -> MiniEngine:
    return MiniEngine(EngineConfig(**{**dict(
        model=model.cfg, num_pages=64, max_pages_per_seq=16, max_batch=4,
        max_prefill_tokens=64), **kw}), params=model.params)


def serve(eng, rid, prompt, new=1):
    req = eng.enqueue(rid, prompt, max_new_tokens=new)
    logits = None
    while not req.done:
        eng.step()
        if logits is None and req.last_logits is not None:
            logits = np.asarray(req.last_logits, np.float32)
    return req, logits


def nearest(model, tokens, position, got) -> float:
    """``got``'s distance from the nearest answer the reference admits at
    ``position`` (a router's near-ties: ``alternatives_at``), over that
    answer's largest logit: the probe's comparison."""
    (alts,) = model.reference.alternatives_at(model.params, model.cfg,
                                              tokens, [position])
    return min(float(np.abs(got - a).max() / np.abs(a).max()) for a in alts)


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


@pytest.mark.parametrize("pallas", [None, True], ids=["xla", "pallas"])
def test_prefill_in_unequal_chunks_and_decode_through_the_pool(model,
                                                               pallas):
    """150 tokens in chunks of 64, 64 and 22 (padded to 32), then 4 decoded
    through the state pool: the last prompt position's logits agree with
    the reference's, and every decoded token is within the tolerance of
    the reference's best at its position."""
    eng = engine(model, use_pallas_decode=pallas, use_pallas_prefill=pallas)
    prompt = prompt_of(150, 1)
    req, logits = serve(eng, "cold", prompt, new=5)
    assert req.cached_len == 0
    assert nearest(model, prompt, 149, logits) < TOLERANCE
    out = list(req.output)
    alts = model.reference.alternatives_at(
        model.params, model.cfg, prompt + out[:4], range(149, 154))
    for token, answers in zip(out, alts):
        assert min(float((a.max() - a[token]) / np.abs(a).max())
                   for a in answers) < TOLERANCE


def test_a_full_chunk_of_the_gated_layer_attends_per_head(model):
    """400 tokens as one chunk padded to 512: at the toy widths (latent 64
    + rope 32 in pages of 96 lanes, heads of 64) the full layer's chunk
    attends per head from 333 queries on, with no selection and its gate
    on the heads' values as ever. The logits agree with the reference's
    and with the absorbed program's (chunks of 64)."""
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetryConfig)
    from tests.test_telemetry import _recorded

    assert llama.prefill_per_head(model.cfg, 512)
    assert not llama.prefill_per_head(model.cfg, 256)
    prompt = prompt_of(400, 12)
    pallas = dict(use_pallas_decode=True, use_pallas_prefill=True,
                  max_pages_per_seq=32)
    eng = engine(model, max_prefill_tokens=512,
                 telemetry=EngineTelemetryConfig(), **pallas)
    seen = _recorded(eng._phases)
    _, per_head = serve(eng, "per-head", prompt)
    assert [a["expanded_keys"] for n, a, _ in seen
            if n == "step.dispatch" and "prefill_pos" in a] == [512]
    assert nearest(model, prompt, 399, per_head) < TOLERANCE
    _, absorbed = serve(engine(model, **pallas), "absorbed", prompt)
    assert np.abs(per_head - absorbed).max() / np.abs(absorbed).max() < SAME


def test_a_hit_through_each_rule_that_leaves_a_snapshot(model):
    """(a) a prompt's last block boundary, (b) the newest multiple of
    ``state_checkpoint_tokens`` (64 here) a prefill has passed: its one
    periodic checkpoint, (c) the end of the pages a prefill matched beyond
    the snapshot it was admitted on. Each hit is admitted at that depth
    with the state copied from the snapshot, and its logits agree with the
    reference's. A prompt that forks off more than a spacing before the
    first one's end finds pages and no state: it is admitted at 0 and
    leaves (c) for the next."""
    eng = engine(model)
    first = prompt_of(150, 2)
    _, cold = serve(eng, "first", first)
    depths = sorted(len(s.chain) * 16
                    for s in eng.state_pool.snapshots.values())
    assert depths == [128, 144]              # 64 gave its slot to 128
    # (a): the same prompt again resumes at (150 - 1) // 16 * 16.
    again, hit = serve(eng, "again", first)
    assert again.cached_len == 144
    assert np.abs(hit - cold).max() / np.abs(cold).max() < SAME
    # (b): one that shares 140 tokens has 8 blocks of pages (128 tokens)
    # to match and the first's trailing checkpoint at their end.
    near = first[:140] + prompt_of(40, 7)
    req, logits = serve(eng, "near", near)
    assert (req.page_hit_blocks, req.cached_len) == (8, 128)
    assert nearest(model, near, 179, logits) < TOLERANCE
    # The fork at 100: 6 blocks of pages (96 tokens) and no snapshot under
    # them since the checkpoint at 64 was written over.
    second = first[:100] + prompt_of(40, 3)
    req, logits = serve(eng, "second", second)
    assert (req.page_hit_blocks, req.cached_len) == (6, 0)
    assert nearest(model, second, 139, logits) < TOLERANCE
    # (c): passing 96, the second left a snapshot there for the third.
    third = first[:100] + prompt_of(25, 4)
    req, logits = serve(eng, "third", third)
    assert (req.page_hit_blocks, req.cached_len) == (6, 96)
    assert nearest(model, third, 124, logits) < TOLERANCE


def test_a_hit_after_its_snapshot_left_falls_back_to_the_next(model):
    eng = engine(model)
    prompt = prompt_of(150, 5)
    _, cold = serve(eng, "cold", prompt)
    pool = eng.state_pool
    deepest = [h for h, s in pool.snapshots.items() if len(s.chain) == 9]
    pool._remove(deepest[0])                 # as an eviction would
    req, hit = serve(eng, "after", prompt)
    assert req.page_hit_blocks == 9 and req.cached_len == 128
    assert np.abs(hit - cold).max() / np.abs(cold).max() < SAME
    assert nearest(model, prompt, 149, hit) < TOLERANCE


def test_two_replicas_share_the_weights_and_not_the_states(model):
    one, two = engine(model), engine(model)
    prompt = prompt_of(90, 6)
    _, a = serve(one, "a", prompt)
    req, b = serve(two, "b", prompt)
    assert req.cached_len == 0               # the other replica's is cold
    np.testing.assert_array_equal(a, b)
    again, c = serve(two, "c", prompt)
    assert again.cached_len == 80
    assert np.abs(c - b).max() / np.abs(b).max() < SAME


def test_the_reference_admits_a_neighbours_other_choice_below_a_linear_layer(
        model):
    """A linear layer's conv and state hand a position the hidden states
    of the positions before it, so a near-tie the program settled the other
    way a few positions back moves this position's logits as its own would:
    the reference's answers hold the whole forward under that choice. It
    branches no neighbour in the last routed layer, whose choices reach no
    later position."""
    ref, cfg, params = model.reference, model.cfg, model.params
    tokens = prompt_of(96, 8)
    p = 95
    sites = ref._sites(params, cfg, [p])
    assert sites == [(1, q) for q in range(p - ref.REACH, p + 1)] + [(2, p)]
    watched = sorted({q for _, q in sites})
    base, ties, _ = ref._forward(params, cfg, tokens, [p], watched=watched)
    near = sorted((ties[li][q][1][0], li, q) for li, q in sites
                  if q < p and len(ties[li][q]) > 1)
    assert near, "no near-tie in reach: pick another prompt"
    _, li, q = near[0]
    forced = {li: {q: ties[li][q][1][1]}}
    moved = ref._forward(params, cfg, tokens, [p], forced)[0][0]
    assert np.abs(moved - base[0]).max() > 1e-4
    (rows,) = ref.alternatives_at(params, cfg, tokens, [p])
    np.testing.assert_allclose(rows[0], base[0], atol=1e-6)
    assert 1 < len(rows) <= ref.LIMIT
    assert min(np.abs(moved - row).max() for row in rows[1:]) < 1e-6
    # Another choice in the last routed layer changes nothing at p.
    _, last, _ = ref._forward(params, cfg, tokens, [p], watched=[p - 1])
    other = sorted(set(range(32)) - set(last[2][p - 1][0][1]))[:4]
    same = ref._forward(params, cfg, tokens, [p],
                        {2: {p - 1: tuple(other)}})[0][0]
    np.testing.assert_allclose(same, base[0], atol=1e-6)


# -- an expert layer's shares -------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Each of the 4 chips of the toy deployment computes its 8 experts'
    terms (weights normalised over all chosen, the clamp in every expert)
    and the shared expert; the shares' routed parts and the shared expert
    counted once are the uncut reference's layer. float32 weights: what is
    left is the order of the sums."""
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=())
    assert cfg.swiglu_limit == 10 and cfg.num_experts == 32
    layer = llama._init_layer_jit(jax.random.PRNGKey(5), whole, True)
    layer["router_bias"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(6), (32,))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))
    assert float(jnp.abs(x[0] @ layer["w_gate"][0]).max()) > 10  # it clamps

    def shared_only():
        gate, up = x[0] @ layer["w_gate_sh"], x[0] @ layer["w_up_sh"]
        return llama._swiglu(gate, up, 10.0) @ layer["w_down_sh"]

    total = shared_only()
    for rank in range(4):
        held = dataclasses.replace(cfg, experts_held=(rank * 8, 8))
        part = {**layer, **{k: layer[k][rank * 8:rank * 8 + 8]
                            for k in ("w_gate", "w_up", "w_down")}}
        total = total + (llama._moe_deepseek(x, part, held)[0]
                         - shared_only())
    ties, gaps = {}, {}
    with jax.default_matmul_precision("highest"):
        want = model.reference._routed(x[0], layer, whole, 0, [], {}, ties,
                                       gaps, 0.0)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# -- the loader ---------------------------------------------------------------


def published(**changes) -> SimpleNamespace:
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=True)
    return SimpleNamespace(**{**{k: v for k, v in conf.items()
                                 if k != "kvbench"}, **changes})


def test_the_loader_reads_the_layer_table_and_the_state_pool():
    cfg = config_from_hf(published(), page_size=16)
    assert cfg.linear_layers == (0, 2) and cfg.page_layers == (1,)
    assert [cfg.layer_kind(i) for i in range(3)] == [
        "linear", "attention", "linear"]
    assert (cfg.state_slots, cfg.state_checkpoint_tokens) == (12, 64)
    assert (cfg.norm_offset, cfg.post_norms, cfg.attn_output_gate,
            cfg.swiglu_limit) == (1.0, True, True, 10.0)
    assert cfg.moe_router[1] == 1 and cfg.experts_held == (0, 8)


@pytest.mark.parametrize("scale", [0.002, 0.02])
def test_the_routers_bias_is_drawn_at_the_scale_the_file_states(scale):
    """A random correction bias unbalances the experts by its size over the
    gaps between the top scores, and with it how much work falls to the
    experts held: the configuration says how large (0.002), the default
    stays what the other configurations draw (0.02)."""
    cfg = config_from_hf(published(router_bias_init_scale=scale),
                         page_size=16)
    assert config_from_hf(published(), page_size=16
                          ).router_bias_init_scale == 0.002
    bias = [np.asarray(layer["router_bias"])
            for layer in llama.init_params(jax.random.PRNGKey(3), cfg)[
                "layers"] if "router_bias" in layer]
    assert bias and all(0 < np.abs(b).max() <= 2 * scale + 1e-9
                        and np.abs(b).max() > scale / 2 for b in bias)


@pytest.mark.parametrize("key", ["linear_gating_type", "norm_type",
                                 "layernorm_type"])
def test_the_loader_refuses_another_form_by_the_keys_name(key):
    with pytest.raises(NotImplementedError, match=key):
        config_from_hf(published(**{key: "something_else"}), page_size=16)
