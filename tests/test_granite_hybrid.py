"""Granite 4.0-H's block (``granite-4.0-h-small-ep2-l10``) at toy widths on
the CPU: Mamba-2 states beside one GQA layer's pages without positional
encoding, softmax-routed experts of which a chip holds a share, and the
four scalars. The engine against the benchmark's float32 reference (logits,
never tokens) through prefill, decode through the pool and a hit from a
snapshot; three planted faults, each far outside the tolerance; an expert
layer's shares against the uncut layer; the loader."""

import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "hack"))

from kvbench.harness import fleet as F, names  # noqa: E402
from llmd_kv_cache_tpu.models import llama  # noqa: E402
from llmd_kv_cache_tpu.models.engine import (  # noqa: E402
    EngineConfig, MiniEngine)
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf  # noqa: E402

CONFIG = "granite-4.0-h-small-ep2-l10"
# As ``tests/test_kimi_delta_attention.py``: tighter than the probe's own
# limit, which is set on the chip at the published widths.
TOLERANCE = 0.05
SAME = 0.02


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
    assert cfg.linear.decay == "mamba2" and cfg.linear.key_heads == 1
    assert cfg.rope_theta == 0 and not cfg.is_mla
    assert cfg.page_layers == (1,) and cfg.linear_layers == (0, 2, 3)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12.0, 0.22, 0.03125, 16.0)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_router,
            cfg.moe_dispatch) == (16, (0, 8), ("softmax_topk", 1), "grouped")
    mamba, attends = model.params["layers"][0], model.params["layers"][1]
    assert {"w_in", "conv_b", "D", "A_log", "dt_bias"} <= set(mamba)
    assert mamba["o_norm"].shape == (cfg.linear.inner,)
    assert "wq" in attends and "w_in" not in attends
    assert mamba["w_gate"].shape[0] == 8 and mamba["router"].shape[1] == 16
    assert mamba["w_gate_sh"].shape == (128, 128)
    assert cfg.step_counters == ("assignments_held", "experts_touched")


@pytest.mark.parametrize("pallas", [None, True], ids=["xla", "pallas"])
def test_prefill_in_unequal_chunks_and_decode_through_the_pool(model,
                                                               pallas):
    """150 tokens in chunks of 64, 64 and 22 (padded to 32), then 2 decoded
    through the state pool and the key/value pages: the last prompt
    position's logits agree with the reference's full forward, and every
    decoded token is within the tolerance of the reference's best at its
    position."""
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


def test_a_hit_two_tokens_past_a_block_boundary_is_no_hit_at_all(model):
    """66 tokens, two past the boundary at 64 (the benchmark's probe at the
    toy widths): the prompt again resumes from the snapshot at 64 and the
    pages under it, its two tokens inside the conv's window, and reads what
    the cold run read and what the reference reads."""
    eng = engine(model)
    prompt = prompt_of(66, 2)
    cold_req, cold = serve(eng, "cold", prompt)
    assert cold_req.cached_len == 0
    assert sorted(len(s.chain) * 32
                  for s in eng.state_pool.snapshots.values()) == [64]
    again, hit = serve(eng, "again", prompt)
    assert again.cached_len == 64
    assert np.abs(hit - cold).max() / np.abs(cold).max() < SAME
    assert nearest(model, prompt, 65, hit) < TOLERANCE


def test_a_burst_decodes_in_one_batch_each_row_on_its_own_state(model):
    """A burst into a batch whose rows all carry state: six prompts
    enqueued together into ``max_batch`` 8 decode side by side, and each
    reads what it reads alone."""
    prompts = [prompt_of(40 + 7 * i, 20 + i) for i in range(6)]
    alone = [serve(engine(model), f"alone-{i}", p, new=12)[0].output
             for i, p in enumerate(prompts[:2])]
    eng = engine(model, max_batch=8)
    reqs = [eng.enqueue(f"r{i}", p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    most = 0
    while not all(r.done for r in reqs):
        eng.step()
        most = max(most, sum(bool(r.output) and not r.done for r in reqs))
    assert most == 6
    assert [list(r.output) for r in reqs[:2]] == [list(o) for o in alone]
    for req, prompt in zip(reqs, prompts):
        alts = model.reference.alternatives_at(
            model.params, model.cfg, prompt + list(req.output)[:3],
            range(len(prompt) - 1, len(prompt) + 3))
        for token, answers in zip(req.output, alts):
            assert min(float((a.max() - a[token]) / np.abs(a).max())
                       for a in answers) < TOLERANCE


def planted(fault):
    """A fault of ``hack/kvbench_probe_readings.py`` planted in the program
    for the block's length (``planted`` there: what the step programs look
    up when they are traced is put back, and nothing traced before or after
    is shared)."""
    import kvbench_probe_readings as tool

    return tool.planted(fault)


@pytest.mark.parametrize("fault", ["conv-tail", "stale-state",
                                   "no-residual-scale"])
def test_a_planted_fault_reads_far_outside_the_tolerance(model, fault):
    """A prefill of three chunks whose last is two tokens (inside the
    conv's window of the boundary at 128), another sequence through the
    pool, then the first prompt again as a hit from the snapshot at 128: a
    chunk that starts its conv from zeros, a hit that keeps what its
    working slot held, and sub-layers that join the residual whole each
    read several times the tolerance at the cold run or at the hit, where
    the sound program reads inside it."""
    prompt = prompt_of(130, 3)

    def readings():
        eng = engine(model, max_batch=1)
        _, cold = serve(eng, "cold", prompt)
        serve(eng, "other", prompt_of(90, 4))
        again, hit = serve(eng, "again", prompt)
        assert again.cached_len == 128
        return max(nearest(model, prompt, 129, cold),
                   nearest(model, prompt, 129, hit))

    with planted(fault):
        assert readings() > 3 * TOLERANCE
    assert readings() < TOLERANCE


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


# -- the four scalars ---------------------------------------------------------


def test_a_model_without_the_scalars_is_handed_its_own_parameters(model):
    """``multiplied`` is the identity for every other model, so their
    programs hold nothing of it; for this one the embedding, the head and
    the attending layers' queries are scaled in float32 before they are
    rounded."""
    plain = dataclasses.replace(
        model.cfg, embedding_multiplier=1.0, attention_multiplier=0.0,
        logits_scaling=1.0)
    assert llama.multiplied(model.params, plain) is model.params
    view = llama.multiplied(model.params, model.cfg)
    ids = jnp.asarray([[3, 5]])
    np.testing.assert_array_equal(
        view["embed"][ids],
        (model.params["embed"][ids].astype(jnp.float32) * 12.0).astype(
            jnp.bfloat16))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128), jnp.bfloat16)
    np.testing.assert_array_equal(x @ view["lm_head"],
                                  (x @ model.params["lm_head"]) / 16)
    wq = model.params["layers"][1]["wq"]
    np.testing.assert_allclose(
        np.asarray(x @ view["layers"][1]["wq"], np.float32),
        np.asarray(x @ wq, np.float32) * 0.03125 * 32 ** 0.5, rtol=1e-2)
    assert view["layers"][0] is model.params["layers"][0]
    # The fused tree: the query columns alone.
    fused = llama.fuse_params(model.params, model.cfg)
    got = x @ llama.multiplied(fused, model.cfg)["layers"][1]["w_qkv"]
    want = np.asarray(x @ fused["layers"][1]["w_qkv"], np.float32)
    want[:, :128] *= 0.03125 * 32 ** 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2)


def test_the_scalars_and_the_third_form_are_refused_where_not_built():
    la = dataclasses.replace(
        config_from_hf(published(), page_size=32).linear)
    base = dict(linear_layers=(0,), linear=la, state_slots=4, rope_theta=0.0)
    for field, value in (("beta_scale", 2.0), ("gate_rank", 8),
                         ("gate_scale", 2.0)):
        with pytest.raises(ValueError, match=field):
            llama.LlamaConfig(**{**base, "linear": dataclasses.replace(
                la, **{field: value})})
    # Two heads of 64 a state tile: two groups of B and C are a tile each,
    # four would put heads of two groups into one tile.
    llama.LlamaConfig(**{**base, "linear": dataclasses.replace(
        la, key_heads=2)})
    with pytest.raises(NotImplementedError, match="key_heads"):
        llama.LlamaConfig(**{**base, "linear": dataclasses.replace(
            la, key_heads=4)})
    with pytest.raises(NotImplementedError, match="with_state"):
        llama.LlamaConfig(embedding_multiplier=12.0)
    with pytest.raises(NotImplementedError, match="attention_multiplier"):
        llama.LlamaConfig(**base, attention_multiplier=0.1, qk_norm=True)
    with pytest.raises(ValueError, match="softmax_topk"):
        llama.LlamaConfig(num_experts=4, moe_router=("softmax_topk", 0),
                          moe_dispatch="grouped")
    with pytest.raises(ValueError, match="experts_held"):
        llama.LlamaConfig(num_experts=4, moe_router=("softmax_topk", 1),
                          moe_dispatch="dense", experts_held=(0, 2))


# -- an expert layer's shares -------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Each of the 2 chips of the toy deployment computes its 8 experts'
    terms (weights the softmax over all 4 chosen logits) and the always-on
    MLP; the shares' routed parts and the MLP counted once are the uncut
    reference's layer. float32 weights: what is left is the order of the
    sums."""
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=())
    assert cfg.num_experts == 16 and cfg.experts_held == (0, 8)
    layer = llama._init_layer_jit(jax.random.PRNGKey(5), whole, True)
    assert layer["w_gate"].shape[0] == 16 and "router_bias" not in layer
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))

    def shared_only():
        gate, up = x[0] @ layer["w_gate_sh"], x[0] @ layer["w_up_sh"]
        return (jax.nn.silu(gate) * up) @ layer["w_down_sh"]

    total = shared_only()
    touched = 0
    for rank in range(2):
        held = dataclasses.replace(cfg, experts_held=(rank * 8, 8))
        part = {**layer, **{k: layer[k][rank * 8:rank * 8 + 8]
                            for k in ("w_gate", "w_up", "w_down")}}
        counters = {}
        total = total + (llama._mlp(x, part, held, counters=counters)[0]
                         - shared_only())
        touched += int(counters["assignments_held"])
    assert touched == 24 * 4            # every assignment fell to one chip
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
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (4096, 32, 8, 128, 50176)
    assert cfg.page_layers == (5,)
    assert cfg.linear_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9)
    la = cfg.linear
    assert (la.key_heads, la.value_heads, la.key_dim, la.value_dim,
            la.conv_kernel, la.conv_channels, la.inner) == (
                1, 128, 128, 64, 4, 8448, 8192)
    assert (la.decay, la.beta_scale, la.gate_rank, la.gate_scale) == (
        "mamba2", 1.0, 0, 1.0)
    assert cfg.rope_theta == 0 and not cfg.attn_output_gate
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_token,
            cfg.moe_intermediate_size, cfg.n_shared_experts) == (
                72, (0, 36), 10, 768, 2)
    assert (cfg.moe_router, cfg.moe_dispatch) == (("softmax_topk", 1),
                                                  "grouped")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12.0, 0.22, 0.0078125, 16.0)
    assert (cfg.state_slots, cfg.state_checkpoint_tokens) == (40, 4096)


@pytest.mark.parametrize("key,value", [
    ("position_embedding_type", "rope"), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mamba_conv_bias", False),
    ("mamba_n_groups", 8), ("num_local_experts", 0),
    ("normalization_function", "layernorm"),
    ("layer_types", ["mamba", "attention", "mamba", "window"])])
def test_the_loader_refuses_what_is_not_built_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        config_from_hf(published(**{key: value}), page_size=32)


def test_the_engine_refuses_by_name_what_cannot_carry_a_state(model):
    for kw, why in ((dict(ragged_attention=True), "ragged_attention"),
                    (dict(max_batch=12), "state_slots 12 for max_batch 12"),
                    (dict(kv_cache_dtype="float8_e4m3fn"), "fp8 cache")):
        with pytest.raises(ValueError, match=why):
            engine(model, **kw)


def test_a_sequences_state_is_float32_and_every_lane_of_the_pool_is_used(
        model):
    """The configuration states a float32 state of 4,194,304 B a layer and
    sequence: the pool at the published widths (shapes only) holds exactly
    that, as tiles of two heads side by side, and the pool an engine
    serves from is float32."""
    big = config_from_hf(published(rehearse=False), page_size=64)
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(big))
    assert (recurrent.shape, recurrent.dtype) == (
        (9, 41, 64, 128, 128), jnp.float32)
    assert recurrent.shape[-1] % 128 == 0
    assert int(np.prod(recurrent.shape[2:])) * 4 == 4_194_304
    assert (conv.shape, conv.dtype) == ((9, 41, 3, 8448), jnp.bfloat16)
    assert engine(model).state[0].dtype == jnp.float32


def test_a_checkpoint_is_refused_as_for_every_linear_model(model):
    from llmd_kv_cache_tpu.models.hf_loader import params_from_hf

    with pytest.raises(NotImplementedError, match="linear layers"):
        params_from_hf({}, model.cfg)
