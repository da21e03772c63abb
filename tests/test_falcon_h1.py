"""Falcon-H1's block (``falcon-h1-34b-l9``) at toy widths on the CPU: a
Mamba-2 state AND GQA pages in every layer, the two mixers side by side from
one normed input, two groups of B and C, rotary attention beside a state
pool, the family's multipliers. The engine against the benchmark's float32
reference (logits, never tokens) through prefill, decode through the pools
and a hit from a snapshot; seven planted faults, each far outside the
tolerance; the views of the parameters; the loader."""

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

CONFIG = "falcon-h1-34b-l9"
# Tighter than the probe's own limit, which is set on the chip at the
# published widths.
TOLERANCE = 0.025
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


def off(model, tokens, position, got) -> float:
    (want,) = model.reference.logits_at(model.params, model.cfg, tokens,
                                        [position])
    return float(np.abs(got - want).max() / np.abs(want).max())


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def test_the_model_is_the_one_the_file_describes(model):
    cfg = model.cfg
    assert cfg.linear.decay == "mamba2" and cfg.linear.key_heads == 2
    assert cfg.rope_theta == 1e11 and not cfg.is_mla
    assert cfg.page_layers == cfg.linear_layers == cfg.parallel_layers == (
        0, 1, 2)
    assert {cfg.layer_kind(i) for i in range(3)} == {"linear"}
    assert llama.init_kv_cache(cfg, 4)[0].shape[0] == 3
    assert llama.init_state_pool(cfg)[0].shape[:2] == (3, 13)
    assert cfg.attention_multiplier == pytest.approx(2 ** -6.5 * 32 ** -0.5)
    assert cfg.logits_scaling == 128.0
    assert (cfg.ssm_out_multiplier, cfg.attention_out_multiplier) == (
        pytest.approx(0.0883883), 0.0375)
    layer = model.params["layers"][0]
    assert {"w_in", "conv_b", "D", "A_log", "dt_bias", "w_ssm_out", "wq",
            "wk", "wv", "wo", "w_gate", "w_up", "w_down"} <= set(layer)
    assert layer["w_in"].shape == (128, 512 + 512 + 2 * 2 * 128 + 4)
    assert layer["w_ssm_out"].shape == (512, 128)
    assert layer["wo"].shape == (4 * 32, 128)
    assert layer["o_norm"].shape == (512,) and cfg.step_counters == ()


def test_each_branch_adds_a_tenth_to_once_the_residual(model):
    """What the initialisation's scales are for: neither mixer and no MLP
    vanishes under the residual, so a fault in one moves the logits."""
    for residual, *branches in model.reference.branch_sizes(
            model.params, model.cfg, prompt_of(150, 9)):
        for size in branches:
            assert 0.1 * residual < size < residual


@pytest.mark.parametrize("pallas", [None, True], ids=["xla", "pallas"])
def test_prefill_in_unequal_chunks_and_decode_through_the_pools(model,
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
    want = model.reference.logits_at(model.params, model.cfg,
                                     prompt + out[:2], range(149, 152))
    assert np.abs(logits - want[0]).max() / np.abs(want[0]).max() < TOLERANCE
    for token, row in zip(out, want):
        assert (row.max() - row[token]) / np.abs(row).max() < TOLERANCE


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
    assert off(model, prompt, 65, hit) < TOLERANCE


def test_a_burst_decodes_in_one_batch_each_row_on_its_own_state(model):
    """Six prompts enqueued together into ``max_batch`` 8 decode side by
    side, every row a state and pages in every layer, and each is within
    the tolerance of the reference's best at its positions."""
    prompts = [prompt_of(40 + 7 * i, 20 + i) for i in range(6)]
    eng = engine(model, max_batch=8)
    reqs = [eng.enqueue(f"r{i}", p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    most = 0
    while not all(r.done for r in reqs):
        eng.step()
        most = max(most, sum(bool(r.output) and not r.done for r in reqs))
    assert most == 6
    for req, prompt in zip(reqs, prompts):
        out = list(req.output)
        want = model.reference.logits_at(
            model.params, model.cfg, prompt + out[:3],
            range(len(prompt) - 1, len(prompt) + 3))
        for token, row in zip(out, want):
            assert (row.max() - row[token]) / np.abs(row).max() < TOLERANCE


FAULTS = ["stale-state", "conv-tail", "norm-all-channels", "wrong-group",
          "no-key-multiplier", "out-multipliers-swapped", "no-rope"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_far_outside_the_tolerance(model, fault):
    """A prefill of three chunks whose last is two tokens (inside the
    conv's window of the boundary at 128), another sequence through the
    pools, then the first prompt again as a hit from the snapshot at 128:
    each fault reads several times the tolerance at the cold run or at the
    hit, where the sound program reads inside it."""
    import kvbench_probe_readings as tool

    prompt = prompt_of(130, 3)

    def readings():
        eng = engine(model, max_batch=1)
        _, cold = serve(eng, "cold", prompt)
        serve(eng, "other", prompt_of(90, 4))
        again, hit = serve(eng, "again", prompt)
        assert again.cached_len == 128
        return max(off(model, prompt, 129, cold),
                   off(model, prompt, 129, hit))

    with tool.planted(fault):
        assert readings() > 3 * TOLERANCE
    if fault == FAULTS[0]:
        assert readings() < TOLERANCE


def test_two_replicas_share_the_weights_and_neither_pool(model):
    one, two = engine(model), engine(model)
    prompt = prompt_of(90, 6)
    _, a = serve(one, "a", prompt)
    req, b = serve(two, "b", prompt)
    assert req.cached_len == 0               # the other replica's is cold
    np.testing.assert_array_equal(a, b)
    again, c = serve(two, "c", prompt)
    assert again.cached_len == 64
    assert np.abs(c - b).max() / np.abs(b).max() < SAME


# -- the multipliers ----------------------------------------------------------


def test_the_view_scales_each_product_in_float32_where_the_model_says(model):
    cfg, params = model.cfg, model.params
    view = llama.multiplied(params, cfg)
    layer, seen = params["layers"][1], view["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128), jnp.bfloat16)
    f32 = jnp.float32

    def product(a, w):
        return np.asarray(jnp.matmul(a, w, preferred_element_type=f32))

    # W_in: ssm_in_multiplier and, a column, its part's ssm_multiplier.
    mup = np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                    (512, 512, 256, 256, 4)) * cfg.ssm_in_multiplier
    np.testing.assert_array_equal(
        x @ seen["w_in"],
        jnp.asarray(product(x, layer["w_in"]) * mup).astype(jnp.bfloat16))
    # The MLP's gate and output, unfused and fused (the gate's half alone).
    gate, down = cfg.mlp_multipliers
    np.testing.assert_array_equal(
        x @ seen["w_gate"],
        jnp.asarray(product(x, layer["w_gate"]) * gate).astype(jnp.bfloat16))
    assert seen["w_up"] is layer["w_up"]
    fused = llama.multiplied(llama.fuse_params(params, cfg), cfg)["layers"][1]
    got = np.asarray(x @ fused["w_gate_up"], np.float32)
    np.testing.assert_array_equal(
        got[:, :256], np.asarray(x @ seen["w_gate"], np.float32))
    np.testing.assert_array_equal(
        got[:, 256:], np.asarray(x @ layer["w_up"], np.float32))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 256), jnp.bfloat16)
    np.testing.assert_array_equal(
        y @ seen["w_down"],
        jnp.asarray(product(y, layer["w_down"]) * down).astype(jnp.bfloat16))
    # The two mixers' outputs through their own projections, each under its
    # own multiplier, summed in float32 and rounded once.
    both = jax.random.normal(jax.random.PRNGKey(2), (2, 512 + 128),
                             jnp.bfloat16)
    want = (cfg.ssm_out_multiplier * product(both[:, :512],
                                             layer["w_ssm_out"])
            + cfg.attention_out_multiplier * product(both[:, 512:],
                                                     layer["wo"]))
    np.testing.assert_allclose(
        np.asarray(both @ seen["wo"], np.float32), want, rtol=1e-2,
        atol=1e-6)
    # The queries carry the scores' scale: key_multiplier x head_dim^-0.5.
    np.testing.assert_allclose(
        np.asarray(x @ seen["wq"], np.float32),
        product(x, layer["wq"]) * cfg.attention_multiplier * 32 ** 0.5,
        rtol=1e-2)
    np.testing.assert_array_equal(
        x @ view["lm_head"], (x @ params["lm_head"]) / 128)


def test_the_mixers_are_told_apart_by_scope(model):
    """A profile by scope tells the two branches of a layer: the state's
    kernel under ``attention/mixer.ssm``, the paged kernel under
    ``attention/mixer.attention``."""
    cfg = model.cfg
    state = llama.init_state_pool(cfg)
    pools = llama.init_kv_cache(cfg, 8)
    packed, shapes = llama.pack_inputs((
        np.zeros((2, 1)), np.zeros((2, 4)), np.full((2,), 3),
        np.ones((2,)), np.asarray([1, 2]), np.zeros((3,))))
    text = llama.step_forward_paged_state.lower(
        model.params, cfg, packed, (*pools, *state),
        shapes=shapes).as_text(debug_info=True)
    assert f"{llama.SCOPE_ATTENTION}/{llama.SCOPE_MIXER_SSM}" in text
    assert f"{llama.SCOPE_ATTENTION}/{llama.SCOPE_MIXER_ATTENTION}" in text


def test_the_forms_that_are_not_built_are_refused(model):
    cfg = model.cfg
    with pytest.raises(NotImplementedError, match="every layer"):
        dataclasses.replace(cfg, parallel_layers=(0, 1))
    with pytest.raises(NotImplementedError, match="two mixers"):
        dataclasses.replace(cfg, num_experts=4)
    with pytest.raises(ValueError, match="no pages"):
        dataclasses.replace(cfg, parallel_layers=())
    with pytest.raises(ValueError, match="parallel layer"):
        llama.LlamaConfig(ssm_out_multiplier=0.5)
    with pytest.raises(ValueError, match="five"):
        dataclasses.replace(cfg, ssm_multipliers=(1.0, 1.0))
    with pytest.raises(NotImplementedError, match="key_heads"):
        dataclasses.replace(cfg, linear=dataclasses.replace(
            cfg.linear, value_dim=64, key_heads=4))


# -- the loader ---------------------------------------------------------------


def published(rehearse=True, **changes) -> SimpleNamespace:
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=rehearse)
    return SimpleNamespace(**{**{k: v for k, v in conf.items()
                                 if k != "kvbench"}, **changes})


def test_the_loader_reads_the_published_keys():
    """The configuration at its published widths (nothing is built)."""
    cfg = config_from_hf(published(rehearse=False), page_size=64)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.num_layers) == (
                5120, 20, 4, 128, 21504, 32640, 9)
    assert cfg.page_layers == cfg.linear_layers == tuple(range(9))
    la = cfg.linear
    assert (la.key_heads, la.value_heads, la.key_dim, la.value_dim,
            la.conv_kernel, la.conv_channels, la.inner) == (
                2, 32, 256, 128, 4, 5120, 4096)
    assert la.inner != 2 * cfg.hidden_size          # mamba_expand: not read
    assert cfg.rope_theta == 1e11 and cfg.rope_scaling == ()
    assert cfg.attention_multiplier == 2.0 ** -10   # key_multiplier x 128^-.5
    assert (cfg.embedding_multiplier, cfg.logits_scaling,
            cfg.ssm_in_multiplier) == (5.656854249492381, 128.0, 0.25)
    assert cfg.ssm_multipliers == (0.3535533905932738, 0.25,
                                   0.1767766952966369, 0.5,
                                   0.3535533905932738)
    assert cfg.mlp_multipliers == (0.1767766952966369, 0.011160714285714284)
    assert (cfg.state_slots, cfg.state_checkpoint_tokens) == (37, 4096)
    assert (cfg.embed_init_scale, cfg.mixer_init_scale,
            cfg.mlp_init_scale) == (0.05, 0.2, 0.1)
    assert llama.fuse_profitable(cfg)


@pytest.mark.parametrize("key,value", [
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("mamba_proj_bias", True), ("attention_bias", True), ("mlp_bias", True),
    ("projectors_bias", True), ("mamba_conv_bias", False),
    ("attn_layer_indices", [0, 2]), ("attention_in_multiplier", 0.5),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("time_step_limit", [0.0, 1.0])])
def test_the_loader_refuses_what_is_not_built_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        config_from_hf(published(**{key: value}), page_size=32)


def test_the_inner_width_is_the_heads_not_the_expansion():
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        config_from_hf(published(mamba_d_ssm=256), page_size=32)


def test_the_engine_refuses_by_name_what_cannot_carry_a_state(model):
    for kw, why in ((dict(ragged_attention=True), "ragged_attention"),
                    (dict(max_batch=12), "state_slots 12 for max_batch 12"),
                    (dict(kv_cache_dtype="float8_e4m3fn"), "fp8 cache")):
        with pytest.raises(ValueError, match=why):
            engine(model, **kw)


def test_a_sequences_cache_is_the_bytes_the_file_states(model):
    """38,025,216 B of state a sequence and 18,432 B of pages a token: the
    pools at the published widths (shapes only), both nine layers long; a
    state is float32, 32 tiles [256, 128] of one head each."""
    big = config_from_hf(published(rehearse=False), page_size=64)
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(big))
    assert (recurrent.shape, recurrent.dtype) == (
        (9, 38, 32, 256, 128), jnp.float32)
    assert int(np.prod(recurrent.shape[2:])) * 4 == 4_194_304
    assert (conv.shape, conv.dtype) == ((9, 38, 3, 5120), jnp.bfloat16)
    a_sequence = 9 * (4_194_304 + 3 * 5120 * 2)
    assert a_sequence == 38_025_216
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(big, 736))
    assert k.shape == v.shape == (9, 736, 4, 64, 128)
    a_token = 2 * int(np.prod(k.shape)) * 2 // (736 * 64)
    assert a_token == 18_432 and a_sequence // a_token == 2063
    assert engine(model).state[0].dtype == jnp.float32


def test_a_checkpoint_is_refused_as_for_every_linear_model(model):
    from llmd_kv_cache_tpu.models.hf_loader import params_from_hf

    with pytest.raises(NotImplementedError, match="linear layers"):
        params_from_hf({}, model.cfg)


def test_five_query_heads_a_key_head_keep_a_chunks_tile_of_128():
    """20 query heads over 4 key/value heads: ``gcd(512, 1024 // 5)`` would
    hand the prefill kernel 4 query rows a program; rounded down to a power
    of two the rule gives 128, and for 1, 2, 4, ... heads a group what it
    gave."""
    import math

    big = config_from_hf(published(rehearse=False), page_size=64)
    assert [llama._prefill_q_tile(big, s) for s in (64, 128, 512)] == [
        64, 128, 128]
    for heads, kv in ((16, 8), (32, 8), (8, 1), (128, 1)):
        cfg = llama.LlamaConfig(num_heads=heads, num_kv_heads=kv)
        for seq in (32, 512, 2048):
            q_tile = math.gcd(seq, max(128, 1024 // (heads // kv)))
            if heads // kv * q_tile > 4096:
                q_tile = math.gcd(seq, max(16, 2048 // (heads // kv)))
            assert llama._prefill_q_tile(cfg, seq) == q_tile
