"""Kimi-style delta attention (a decay for every key channel) beside GQA
pages without positional encoding (``solar-open2-ep16-l8``), at toy widths
on the CPU: the channel-wise recurrence's two kernels against the
definition a token at a time, the engine against the benchmark's float32
reference (logits, never tokens) through prefill, decode through the pool
and a hit from a snapshot, a snapshot whose page is evicted under it, an
expert layer's shares against the uncut layer, and the loader."""

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
from llmd_kv_cache_tpu.models.engine import (  # noqa: E402
    EngineConfig, MiniEngine)
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf  # noqa: E402
from llmd_kv_cache_tpu.ops.gated_deltanet import (  # noqa: E402
    gdn_scan, kda_scan, kda_step)

CONFIG = "solar-open2-ep16-l8"
# As ``tests/test_gated_deltanet.py``: tighter than the probe's own limit,
# which is set on the chip at the published widths.
TOLERANCE = 0.05
SAME = 0.02


# -- the kernels against the recurrence ---------------------------------------


def recurrence(q, k, v, g, beta, state):
    """``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t`` a token at a time, in float64: ``g [T, H,
    dk]``, ``state [H, dk, dv]``. Returns the outputs and every token's
    state."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    S = np.asarray(state, np.float64).copy()
    outs, states = [], []
    for t in range(q.shape[0]):
        for h in range(v.shape[1]):
            S[h] = np.exp(g[t, h])[:, None] * S[h]
            S[h] = S[h] + np.outer(k[t, h],
                                   beta[t, h] * (v[t, h] - k[t, h] @ S[h]))
        outs.append(np.einsum("hkv,hk->hv", S, q[t]))
        states.append(S.copy())
    return np.stack(outs), states


def inputs(tokens, valid, page, seed=0, heads=2, dk=32, dv=16):
    """Keys that lie in one orthant (what a conv and a SiLU leave), a
    ``beta`` in (0, 2), and for each channel a rate of its own: over a
    page its log-decay runs from about -0.006 to -100."""
    rng = np.random.default_rng(seed)
    k = np.abs(rng.normal(size=(tokens, heads, dk))) + 0.1
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(tokens, heads, dk))
    q *= dk ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    v = rng.normal(size=(tokens, heads, dv))
    live = (np.arange(tokens) < valid)[:, None]
    rate = np.exp(rng.uniform(np.log(1e-4), np.log(100 / page),
                              size=(1, heads, dk)))
    g = np.where(live[..., None],
                 -rate * rng.uniform(0.5, 1.5, size=(tokens, heads, dk)), 0)
    beta = np.where(live, rng.uniform(0, 2, size=(tokens, heads)), 0.0)
    state = rng.normal(size=(heads, dk, dv))
    return tuple(np.asarray(a, np.float32)
                 for a in (q, k, v, g, beta, state))


def neighbouring(tokens):
    """``inputs`` with neighbouring keys nearly parallel, slow decays and
    ``beta`` in (1.7, 2)."""
    rng = np.random.default_rng(4)
    q, k, v, g, beta, state = inputs(tokens, tokens, 64, seed=4)
    common = np.abs(rng.normal(size=(1, 2, 32)))
    k = common + 0.05 * rng.normal(size=(tokens, 2, 32))
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    g = (0.02 * g).astype(np.float32)
    beta = (1.7 + 0.3 * rng.uniform(size=beta.shape)).astype(np.float32)
    return q, k, v, g, beta, state


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_channelwise_scan_is_the_recurrence(kernel, block):
    """A padded chunk (the last 23 tokens are not real) of one-orthant
    keys whose channels lose up to e^-50 and more over a block: outputs of
    the real tokens, the state at the chunk's end and at a block boundary
    inside it. Blocks of 16, 32 and 64 walk two and three levels of the
    pairwise decays."""
    tokens, valid = 192, 169
    q, k, v, g, beta, state = inputs(tokens, valid, block)
    assert g[:block].sum(0).min() < -50 < -1 < g[:block].sum(0).max()
    want, states = recurrence(q, k, v, g, beta, state)
    snap_block = 128 // block - 1                # the boundary at token 128
    o, end, inner = kda_scan(q, k, v, g, beta, state, snap_block,
                             block=block, kernel=kernel, interpret=True)
    assert np.isfinite(o).all() and np.isfinite(end).all()
    scale = np.abs(want).max()
    np.testing.assert_allclose(o[:valid], want[:valid], atol=1e-4 * scale)
    np.testing.assert_allclose(end, states[valid - 1], atol=1e-4)
    np.testing.assert_allclose(inner, states[127], atol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_neighbouring_keys_nearly_equal_and_beta_near_two(kernel):
    """What the model's own keys look like: a conv over 4 tokens and a
    SiLU leave neighbours nearly parallel, and ``beta`` reaches 2. The
    block's triangular system then has entries near 2, the powers of a
    16-token block reach 1e5, and a product of powers returned an inverse
    wrong by 0.1 (the first chip run's probe read 0.05-0.12 with it; random
    one-orthant keys had passed). The nested inverse holds."""
    q, k, v, g, beta, state = neighbouring(128)
    assert (k[1:, 0] * k[:-1, 0]).sum(-1).min() > 0.97
    want, states = recurrence(q, k, v, g, beta, state)
    o, end, inner = kda_scan(q, k, v, g, beta, state, 0, block=64,
                             kernel=kernel, interpret=True)
    scale = np.abs(want).max()
    np.testing.assert_allclose(o, want, atol=2e-4 * scale)
    np.testing.assert_allclose(end, states[-1], atol=2e-4)
    np.testing.assert_allclose(inner, states[63], atol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("block", [16, 32, 64])
@pytest.mark.parametrize("keys", ["decays", "neighbours"])
def test_a_blocks_pairwise_decays_are_the_direct_sum(keys, block, kernel):
    """``_kda_pairs`` alone, a block's queries and keys stacked, against
    ``sum_c x_ic k_jc exp(G_ic - G_jc)`` (``j < i``) summed in float64:
    channels that lose e^-50 and more over the block, and the neighbouring
    keys. The strictly lower part to 1e-5 of its largest entry, zeros on
    and above the diagonal, finite everywhere."""
    from jax.experimental import pallas as pl

    from llmd_kv_cache_tpu.ops.gated_deltanet import _kda_pairs

    q, k, _, g, _, _ = (inputs(block, block, block) if keys == "decays"
                        else neighbouring(block))
    x = np.concatenate([q[:, 0], k[:, 0]])
    gc = np.cumsum(g[:, 0], axis=0, dtype=np.float32)
    if keys == "decays":
        assert gc[-1].min() < -50 < -1 < gc[-1].max()
    if kernel:
        def body(x_ref, k_ref, gc_ref, o_ref):
            o_ref[...] = _kda_pairs(x_ref[...], k_ref[...], gc_ref[...])

        build = pl.pallas_call(body, interpret=True, out_shape=(
            jax.ShapeDtypeStruct((2 * block, block), jnp.float32)))
    else:
        build = jax.jit(_kda_pairs)
    got = np.asarray(build(x, k[:, 0], gc))
    G = gc.astype(np.float64)
    lower = np.tril(np.ones((block, block), bool), -1)
    with np.errstate(over="ignore"):     # above the diagonal: masked below
        decay = np.exp(G[:, None, :] - G[None, :, :])
    decay = np.where(lower[..., None], decay, 0)
    want = np.concatenate([
        np.einsum("ic,jc,ijc->ij", part.astype(np.float64),
                  k[:, 0].astype(np.float64), decay)
        for part in (q[:, 0], k[:, 0])])
    assert np.isfinite(got).all()
    assert (got[~np.tile(lower, (2, 1))] == 0).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_no_product_of_a_block_copies_rows_of_the_running_decay():
    """The reference rows of ``gc`` are taken by slices and rolls: at the
    published block no ``dot_general`` of ``_kda_block_update`` has ``gc``
    itself or a 0/1 matrix (a comparison turned into numbers) for an
    operand, as the eleven gathers of the first form had."""
    from llmd_kv_cache_tpu.ops.gated_deltanet import _kda_block_update

    c, d = 64, 128
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (c, d), (c, d), (c, d), (c, d), (1, c), (d, d))]
    closed = jax.make_jaxpr(_kda_block_update)(*shapes)
    gc = closed.jaxpr.invars[3]

    def products(jaxpr):
        """What made each operand of every ``dot_general``, sub-jaxprs
        (a ``jnp.roll`` is one) included."""
        made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
        for e in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from products(sub)
            if e.primitive.name == "dot_general":
                yield [(v, made_by.get(v)) for v in e.invars]

    def selects(operand, maker):
        return operand is gc or (
            maker is not None
            and maker.primitive.name == "convert_element_type"
            and maker.invars[0].aval.dtype == jnp.bool_)

    found = list(products(closed.jaxpr))
    assert len(found) >= 16
    assert not [p for p in found if any(selects(*o) for o in p)]


def test_channelwise_chunks_of_unequal_size_chain_to_the_whole():
    q, k, v, g, beta, state = inputs(96, 96, 16, seed=1)
    want, states = recurrence(q, k, v, g, beta, state)
    at, outs = 0, []
    for size in (32, 48, 16):
        o, state, _ = kda_scan(*(x[at:at + size] for x in (q, k, v, g, beta)),
                               state, -1, block=16)
        outs.append(o)
        at += size
    np.testing.assert_allclose(np.concatenate(outs), want, atol=1e-4)
    np.testing.assert_allclose(state, states[-1], atol=1e-4)


def test_one_decay_in_every_channel_is_the_scalar_scan():
    """The channel-wise form handed a head's scalar log-decay in every
    channel is Gated DeltaNet's scan."""
    q, k, v, g, beta, state = inputs(64, 64, 32, seed=2)
    g = np.broadcast_to(g[..., :1], g.shape)
    beta = beta / 2
    want = gdn_scan(q, k, v, g[..., 0], beta, state, 0, block=32)
    got = kda_scan(q, k, v, g, beta, state, 0, block=32)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_channelwise_step_updates_the_rows_slots_in_place(kernel):
    rows = 3
    q, k, v, g, beta, _ = inputs(rows, rows, 1, seed=3, heads=8, dv=16)
    pool = np.random.default_rng(3).normal(size=(2, 6, 8, 32, 16)).astype(
        np.float32)
    slots = np.array([4, 2, 5], np.int32)
    o, new = kda_step(jnp.asarray(pool), 1, slots, q, k, v, g, beta,
                      kernel=kernel, interpret=True)
    want = pool.copy()
    for r, slot in enumerate(slots):
        out, states = recurrence(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                 g[r:r + 1], beta[r:r + 1], pool[1, slot])
        np.testing.assert_allclose(o[r], out[0], atol=2e-5)
        want[1, slot] = states[0]
    np.testing.assert_allclose(new, want, atol=2e-5)  # nothing else moved


@live_rows.CASES
def test_channelwise_step_moves_the_live_rows_states_and_no_other(
        slots, monkeypatch):
    """A row of the spare slot 0 costs the kernel no state: 16 heads a
    state, 8 a grid step, rows that hand in ``g = beta = 0`` as the
    engine's do."""
    heads, dk, dv = 16, 32, 16
    live_rows.two_groups_a_row(monkeypatch, heads * dk * dv * 4)
    q, k, v, g, beta, _ = inputs(live_rows.ROWS, live_rows.ROWS, 1, seed=8,
                                 heads=heads, dk=dk, dv=dv)
    live = (np.asarray(slots) != 0)[:, None]
    g, beta = g * live[..., None], beta * live
    pool = np.random.default_rng(8).normal(
        size=(2, live_rows.SLOTS, heads, dk, dv)).astype(np.float32)

    def step(pool, slots, kernel):
        return kda_step.__wrapped__(pool, 1, slots, q, k, v, g, beta,
                                    kernel=kernel, interpret=kernel)

    live_rows.check(step, pool, slots)


def test_the_kernels_names_are_what_a_trace_calls_them():
    """``kvbench/metrics/kda_*_roofline.py`` find the kernels by the names
    their jitted wrappers give the ops; the scopes inside ``attention``
    carry the same names."""
    import re

    from kvbench.harness import names as N
    from llmd_kv_cache_tpu.ops import gated_deltanet as gd

    assert (kda_scan.__name__, kda_step.__name__) == (
        gd.KERNEL_KDA_SCAN, gd.KERNEL_KDA_STEP) == ("kda_scan", "kda_step")
    for reader, own, other in (("kda_scan_roofline", "kda_scan", "gdn_scan"),
                               ("kda_step_roofline", "kda_step", "gdn_step")):
        pattern = N.metric(reader).KERNEL
        assert re.search(pattern, f"{own}.12")
        assert not re.search(pattern, f"{other}.12")


# -- the engine against the reference ----------------------------------------


@pytest.fixture(scope="module")
def model():
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=True)
    cfg, params = F.build_model(conf, 11)
    return SimpleNamespace(cfg=cfg, params=params,
                           reference=names.reference(conf))


def engine(model, **kw) -> MiniEngine:
    return MiniEngine(EngineConfig(**{**dict(
        model=model.cfg, num_pages=48, max_pages_per_seq=12, max_batch=4,
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
    (alts,) = model.reference.alternatives_at(model.params, model.cfg,
                                              tokens, [position])
    return min(float(np.abs(got - a).max() / np.abs(a).max()) for a in alts)


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def test_the_model_is_the_one_the_file_describes(model):
    cfg = model.cfg
    assert cfg.linear.decay == "channel" and cfg.linear.beta_scale == 2.0
    assert cfg.rope_theta == 0 and not cfg.is_mla and cfg.attn_output_gate
    assert cfg.page_layers == (0,) and cfg.linear_layers == (1, 2, 3)
    assert "w_og" in model.params["layers"][0]
    assert "w_f_up" in model.params["layers"][1]


@pytest.mark.parametrize("pallas", [None, True], ids=["xla", "pallas"])
def test_prefill_in_unequal_chunks_and_decode_through_the_pool(model,
                                                               pallas):
    """150 tokens in chunks of 64, 64 and 22 (padded to 32), then 2 decoded
    through the state pool and the key/value pages: the last prompt
    position's logits agree with the reference's, and every decoded token
    is within the tolerance of the reference's best at its position."""
    eng = engine(model, use_pallas_decode=pallas, use_pallas_prefill=pallas)
    prompt = prompt_of(150, 1)
    req, logits = serve(eng, "cold", prompt, new=3)
    assert req.cached_len == 0
    out = list(req.output)
    alts = model.reference.alternatives_at(
        model.params, model.cfg, prompt + out[:2], range(149, 152))
    assert min(float(np.abs(logits - a).max() / np.abs(a).max())
               for a in alts[0]) < TOLERANCE
    for token, answers in zip(out, alts):
        assert min(float((a.max() - a[token]) / np.abs(a).max())
                   for a in answers) < TOLERANCE


def test_a_hit_resumes_from_a_snapshot_beside_gqa_pages(model):
    """The same prompt again resumes at its last block boundary, from the
    snapshot there and the pages under it; one that shares 200 tokens
    resumes at 192, the first prefill's periodic checkpoint (the newest
    multiple of ``state_checkpoint_tokens`` it passed: 64 and 128 gave
    their slot to it); one that shares 100 finds 3 pages (96 tokens) and
    no state under them, is admitted at 0 and leaves a snapshot at the end
    of those pages, from which a third is served."""
    eng = engine(model)
    first = prompt_of(230, 2)
    _, cold = serve(eng, "first", first)
    depths = sorted(len(s.chain) * 32
                    for s in eng.state_pool.snapshots.values())
    assert depths == [192, 224]
    again, hit = serve(eng, "again", first)
    assert again.cached_len == 224
    assert np.abs(hit - cold).max() / np.abs(cold).max() < SAME
    assert nearest(model, first, 229, hit) < TOLERANCE
    near = first[:200] + prompt_of(40, 7)
    req, logits = serve(eng, "near", near)
    assert (req.page_hit_blocks, req.cached_len) == (6, 192)
    assert nearest(model, near, 239, logits) < TOLERANCE
    second = first[:100] + prompt_of(40, 3)
    req, logits = serve(eng, "second", second)
    assert (req.page_hit_blocks, req.cached_len) == (3, 0)
    assert nearest(model, second, 139, logits) < TOLERANCE
    third = first[:100] + prompt_of(25, 4)
    req, logits = serve(eng, "third", third)
    assert (req.page_hit_blocks, req.cached_len) == (3, 96)
    assert nearest(model, third, 124, logits) < TOLERANCE


def test_a_snapshot_whose_page_is_evicted_is_orphaned(model):
    """Two prompts share 64 tokens. Evicting the least recently used block
    (the first prompt's third: the second prompt touched the shared two
    after it) takes the snapshot at 128 that stands on it and counts one
    ``state_orphaned`` among the ``state_evictions``; the snapshot at 64
    stands, and the first prompt again is admitted at min(2 pages matched,
    the snapshot at 64)."""
    eng = engine(model)
    first = prompt_of(150, 4)
    _, cold = serve(eng, "first", first)
    serve(eng, "second", first[:64] + prompt_of(30, 5))
    before = eng.block_manager.pool_stats()
    assert before["state_orphaned"] == 0
    eng.block_manager._evict(1)
    after = eng.block_manager.pool_stats()
    assert after["state_orphaned"] == 1
    assert after["state_evictions"] == before["state_evictions"] + 1
    assert after["state_snapshots"] == before["state_snapshots"] - 1
    req, hit = serve(eng, "again", first)
    assert (req.page_hit_blocks, req.cached_len) == (2, 64)
    assert np.abs(hit - cold).max() / np.abs(cold).max() < SAME


def test_two_replicas_share_the_weights_and_not_the_states(model):
    one, two = engine(model), engine(model)
    prompt = prompt_of(90, 6)
    _, a = serve(one, "a", prompt)
    req, b = serve(two, "b", prompt)
    assert req.cached_len == 0               # the other replica's is cold
    np.testing.assert_array_equal(a, b)
    again, c = serve(two, "c", prompt)
    assert again.cached_len == 64
    assert np.abs(c - b).max() / np.abs(b).max() < SAME


def test_positions_reach_attention_through_the_mask_alone(model):
    """No positional encoding: ``_rope`` hands back what it was given."""
    x = jnp.arange(2 * 3 * 2 * 8, dtype=jnp.float32).reshape(2, 3, 2, 8)
    at = jnp.asarray([[5, 6, 7], [0, 1, 2]])
    assert llama._rope(x, at, model.cfg.rope_theta) is x
    assert not np.allclose(llama._rope(x, at, 10000.0), x)


# -- an expert layer's shares -------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Each of the 4 chips of the toy deployment computes its 8 experts'
    terms (weights normalised over all chosen) and the shared expert; the
    shares' routed parts and the shared expert counted once are the uncut
    reference's layer. float32 weights: what is left is the order of the
    sums."""
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=())
    assert cfg.num_experts == 32 and cfg.experts_held == (0, 8)
    layer = llama._init_layer_jit(jax.random.PRNGKey(5), whole, True)
    layer["router_bias"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(6), (32,))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))

    def shared_only():
        gate, up = x[0] @ layer["w_gate_sh"], x[0] @ layer["w_up_sh"]
        return (jax.nn.silu(gate) * up) @ layer["w_down_sh"]

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


def published(rehearse=True, **changes) -> SimpleNamespace:
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=rehearse)
    return SimpleNamespace(**{**{k: v for k, v in conf.items()
                                 if k != "kvbench"}, **changes})


def test_the_loader_reads_the_published_keys():
    """The configuration at its published widths (nothing is built)."""
    cfg = config_from_hf(published(rehearse=False), page_size=64)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (4096, 64, 8, 128)
    assert cfg.page_layers == (0, 4)
    assert cfg.linear_layers == (1, 2, 3, 5, 6, 7)
    la = cfg.linear
    assert (la.key_heads, la.value_heads, la.key_dim, la.value_dim,
            la.conv_kernel, la.conv_channels) == (64, 64, 128, 128, 4, 24576)
    assert (la.decay, la.beta_scale, la.gate_rank, la.gate_scale) == (
        "channel", 2.0, 128, 1.0)
    assert cfg.rope_theta == 0 and cfg.attn_output_gate
    assert cfg.moe_layers == tuple(range(8))
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_token,
            cfg.moe_intermediate_size) == (320, (0, 20), 8, 1280)
    assert cfg.moe_router == ("deepseek_v3", 1, 1, 1, 1.0)
    assert (cfg.state_slots, cfg.state_checkpoint_tokens) == (40, 4096)
    assert cfg.router_bias_init_scale == 0.002


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("kda_use_full_proj", True),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 32,
                            "num_heads": 4, "num_kv_heads": 2})])
def test_the_loader_refuses_what_is_not_built_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key.split("_config")[0]):
        config_from_hf(published(**{key: value}), page_size=32)


def test_a_sequences_state_is_float32_where_the_probe_cannot_tell(model):
    """The configuration states a float32 state. On the chip the probe's
    logits cannot tell a state kept in bfloat16 from a sound run (the
    file's ``assumed.state_dtype``: 0.023-0.025 beside 0.023-0.061), so the
    type is held here: the pool at the published widths (shapes only), the
    pool an engine serves from, and the scan's end state to a limit that
    the same state rounded to bfloat16 breaks."""
    big = config_from_hf(published(rehearse=False), page_size=64)
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(big))
    assert (recurrent.shape, recurrent.dtype) == (
        (6, 41, 64, 128, 128), jnp.float32)
    assert recurrent.shape[2] * 128 * 128 * 4 == 4_194_304
    assert engine(model).state[0].dtype == jnp.float32
    q, k, v, g, beta, state = inputs(64, 64, 32)
    _, states = recurrence(q, k, v, g, beta, state)
    _, end, _ = kda_scan(q, k, v, g, beta, state, 0, block=32, kernel=False)

    def off(got):
        return float(np.linalg.norm(got - states[-1])
                     / np.linalg.norm(states[-1]))

    rounded = np.asarray(jnp.asarray(states[-1], jnp.bfloat16), np.float64)
    assert end.dtype == jnp.float32 and off(np.asarray(end)) < 1e-4
    assert off(rounded) > 1e-3


def test_a_checkpoint_is_refused_as_for_every_linear_model(model):
    from llmd_kv_cache_tpu.models.hf_loader import params_from_hf

    with pytest.raises(NotImplementedError, match="linear layers"):
        params_from_hf({}, model.cfg)


def test_the_config_says_which_hybrids_are_built(model):
    with pytest.raises(ValueError, match="head.*channel"):
        dataclasses.replace(model.cfg, linear=dataclasses.replace(
            model.cfg.linear, decay="matrix"))
    with pytest.raises(ValueError, match="gate_rank"):
        dataclasses.replace(model.cfg, linear=dataclasses.replace(
            model.cfg.linear, gate_rank=0))
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(model.cfg, sliding_window=64,
                            swa_layers=(0,))
