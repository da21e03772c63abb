"""Mellum 2's block (``mellum2-12b-ep4``) at toy widths on the CPU: window
layers beside full layers in two page pools, a rotary rule a layer kind,
softmax-routed experts of which a chip holds a share. The engine against
the benchmark's float32 reference (logits, never tokens) through chunked
prefill past the window, decode across a reclaim and a hit that resumes on
a trailing window; the two-pool kernel forms (interpreted) against the XLA
form; three controls, each far outside the tolerance; an expert layer's four
shares against the uncut layer; the loader; the window pool's counters."""

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

from kvbench.harness import fleet as F, names  # noqa: E402
from llmd_kv_cache_tpu.models import llama  # noqa: E402
from llmd_kv_cache_tpu.models.engine import (  # noqa: E402
    EngineConfig, MiniEngine)
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf  # noqa: E402
from llmd_kv_cache_tpu.telemetry.engine_telemetry import (  # noqa: E402
    EngineTelemetryConfig)

CONFIG = "mellum2-12b-ep4"
# Tighter than the probe's own limit, which is set on the chip at the
# published widths.
TOLERANCE = 0.05
PAGE, WINDOW = 32, 64


@pytest.fixture(scope="module")
def model():
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=True)
    cfg, params = F.build_model(conf, 11)
    return SimpleNamespace(cfg=cfg, params=params, conf=conf,
                           reference=names.reference(conf))


def engine(model, cfg=None, **kw) -> MiniEngine:
    return MiniEngine(EngineConfig(**{**dict(
        model=cfg or model.cfg, num_pages=64, max_pages_per_seq=12,
        max_batch=4, max_prefill_tokens=64), **kw}), params=model.params)


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
    assert cfg.is_hybrid and cfg.swa_layers == (0, 1, 2)
    assert (cfg.sliding_window, cfg.window_pages, cfg.page_size) == (
        WINDOW, 40, PAGE)
    assert cfg.group_layers(0) == (3,) and cfg.group_layers(1) == (0, 1, 2)
    assert cfg.rope_scaling[0] == "yarn"
    assert cfg.swa_rope_scaling == ("default",)
    assert [cfg.layer_rope(i) for i in range(4)] == [(), (), (),
                                                     cfg.rope_scaling]
    assert cfg.qk_norm and cfg.rope_theta == 500000.0
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_router,
            cfg.moe_dispatch, cfg.n_shared_experts) == (
                16, (0, 4), ("softmax_topk", 1), "grouped", 0)
    layer = model.params["layers"][0]
    assert layer["w_gate"].shape == (4, 128, 64)
    assert layer["router"].shape == (128, 16) and "w_gate_sh" not in layer


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "kernels"])
def test_chunks_past_the_window_a_reclaim_and_a_hit_on_a_trailing_window(
        model, pallas):
    """250 tokens in chunks of 64, nearly four windows deep. The cold
    prefill's logits, a token decoded across a page edge and the reclaim of
    a window page, and the repeated prompt's logits, which resume at 224 on
    window blocks 5-6 alone, against the reference's nearest answer."""
    eng = engine(model, use_pallas_decode=pallas, use_pallas_prefill=pallas,
                 telemetry=EngineTelemetryConfig())
    assert eng.attention_backends["decode"]["backend"] == (
        "pallas" if pallas else "xla")
    assert eng.swa_manager.num_pages == 40     # the configuration's key
    prompt = prompt_of(250, 3)
    req, logits = serve(eng, "cold", prompt, new=10)
    assert req.cached_len == 0
    assert nearest(model, prompt, 249, logits) < TOLERANCE
    stats = eng.block_manager.pool_stats()
    # Prefill reclaimed behind each chunk, and decode crossed a page edge.
    assert stats["window_reclaimed"] >= 6 and stats["window_evictions"] == 0
    assert stats["window_free"] == len(eng.swa_manager.free_pages)
    out = list(req.output)
    (ref,) = model.reference.alternatives_at(
        model.params, model.cfg, prompt + out, [249 + 9])
    # The 10th token, chosen 9 positions on, through both pools.
    short = min(float((a.max() - a[out[9]]) / np.abs(a).max()) for a in ref)
    assert short < TOLERANCE
    hit, logits = serve(eng, "hit", prompt, new=1)
    assert hit.cached_len == 224 and hit.swa_acquired_from == 5
    assert nearest(model, prompt, 249, logits) < TOLERANCE


def test_the_kernel_forms_give_what_the_xla_form_gives(model):
    """``step_decode_pallas_pools`` / ``step_prefill_pallas_pools``
    (interpreted) against ``step_forward_hybrid``, float32 weights: the
    same logits to rounding, the same tokens."""
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        model.params)
    got = {}
    for pallas in (False, True):
        eng = MiniEngine(EngineConfig(
            model=cfg, num_pages=64, max_pages_per_seq=12, max_batch=4,
            max_prefill_tokens=64, use_pallas_decode=pallas,
            use_pallas_prefill=pallas), params=params)
        forms = {getattr(f, "func", f) for f in (eng._decode_forward,
                                                 eng._prefill_forward)}
        assert forms == ({llama.step_decode_pallas_pools,
                          llama.step_prefill_pallas_pools} if pallas
                         else {llama.step_forward_hybrid})
        a = eng.enqueue("a", prompt_of(100, 5), max_new_tokens=5)
        b = eng.enqueue("b", prompt_of(40, 6), max_new_tokens=5)
        rows = {}
        while eng.requests:
            eng.step()
            for r in (a, b):
                if r.last_logits is not None and r.request_id not in rows:
                    rows[r.request_id] = np.asarray(r.last_logits)
        got[pallas] = (rows, list(a.output), list(b.output))
    for rid in "ab":
        np.testing.assert_allclose(got[True][0][rid], got[False][0][rid],
                                   rtol=2e-3, atol=2e-3)
    assert got[True][1:] == got[False][1:]


YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}
CONTROLS = {
    "yarn-everywhere": {"rope_parameters": {"full_attention": YARN,
                                            "sliding_attention": YARN}},
    "yarn-nowhere": {"rope_parameters": {"full_attention": PLAIN,
                                         "sliding_attention": PLAIN}},
    "window-ignored": {"sliding_window": 4096},
}


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_far_outside_the_tolerance(model, control):
    """The three ways to serve the wrong model that the chip's probe has to
    refuse: the same weights under a rule that is not the configuration's."""
    served = F.model_config({**model.conf, **CONTROLS[control]})
    eng = engine(model, served)
    prompt = prompt_of(258, 3)
    _, logits = serve(eng, "p", prompt)
    assert nearest(model, prompt, 257, logits) > 3 * TOLERANCE


def test_a_decode_program_launched_ahead_finds_its_window_page(model):
    """A row whose last token is unread writes one position further: the
    program launched ahead is handed a live window page under THAT block
    (a page edge at 96), and the pools end as a synchronous engine's."""
    out = {}
    for other in (False, True):
        eng = engine(model, telemetry=EngineTelemetryConfig())
        assert eng._defers
        req = eng.enqueue("r", prompt_of(90, 8), max_new_tokens=30)
        ahead = 0
        while eng.requests:
            if other:
                eng._lone_decodes = 0   # never alone: never ahead
            eng.step()
            ahead += eng._unread is not None
        assert (ahead > 10) is not other
        out[other] = (list(req.output), eng.block_manager.pool_stats())
    assert out[False] == out[True]


def test_two_replicas_share_the_weights_and_not_the_pools(model):
    a, b = engine(model), engine(model)
    assert a.params is b.params is model.params
    prompt = prompt_of(130, 9)
    serve(a, "x", prompt)
    hit, _ = serve(a, "y", prompt)
    cold, _ = serve(b, "z", prompt)
    assert hit.cached_len == 128 and cold.cached_len == 0


def test_a_hit_needs_the_trailing_window(model):
    """A prefill commits the window blocks that still stand at its end, the
    trailing window (blocks 6-7 of 8: the earlier ones were reclaimed
    behind the chunks). With block 7 evicted from the window pool the
    global chain still matches 8 blocks and no depth has its window: the
    prompt is computed again, which puts the tail back."""
    eng = engine(model, telemetry=EngineTelemetryConfig())
    from test_telemetry import _recorded

    seen = _recorded(eng._phases)
    prompt = prompt_of(258, 4)
    first, _ = serve(eng, "a", prompt, new=1)
    hashes = list(first.block_hashes)
    swa = eng.swa_manager
    assert [h in swa.blocks for h in hashes[:8]] == 6 * [False] + 2 * [True]
    victim = swa.blocks.pop(hashes[7])
    swa.page_to_hash.pop(victim.page)
    again, _ = serve(eng, "b", prompt, new=1)
    assert again.cached_len == 0 and again.page_hit_blocks == 8
    third, _ = serve(eng, "c", prompt, new=1)
    assert third.cached_len == 256 and third.swa_acquired_from == 6
    lookups = [a for name, a, _ in seen if name == "enqueue.lookup"]
    assert [(a["page_hit_tokens"], a["window_hit_tokens"])
            for a in lookups] == [(0, 0), (256, 0), (256, 256)]
    windows = [a for name, a, _ in seen if name == "step.window"]
    assert sum(a.get("ensured", 0) for a in windows) >= 9
    assert sum(a.get("reclaimed", 0) for a in windows) == (
        eng.block_manager.pool_stats()["window_reclaimed"])
    chunks = [a for name, a, _ in seen
              if name == "step.dispatch" and "prefill_pos" in a]
    assert (chunks[4]["full_keys"], chunks[4]["window_keys"]) == (
        258, 2 + WINDOW - 1)
    assert (chunks[1]["full_keys"], chunks[1]["window_keys"]) == (128, 127)


def test_a_decode_dispatch_tells_both_pools_keys(model):
    eng = engine(model, telemetry=EngineTelemetryConfig())
    from test_telemetry import _recorded

    seen = _recorded(eng._phases)
    serve(eng, "a", prompt_of(100, 1), new=3)
    serve(eng, "b", prompt_of(20, 2), new=3)
    decodes = [a for name, a, _ in seen
               if name == "step.dispatch" and "prefill_pos" not in a]
    assert [(a["full_keys"], a["window_keys"]) for a in decodes] == [
        (101, WINDOW), (102, WINDOW), (21, 21), (22, 22)]


# -- a chip's share -----------------------------------------------------------


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(model):
    """Each of the 4 chips of the toy deployment computes its 4 experts'
    terms (weights the softmax over all 4 chosen logits); together they
    are the uncut reference's layer. float32 weights: what is left is the
    order of the sums."""
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=())
    assert cfg.num_experts == 16 and cfg.experts_held == (0, 4)
    layer = llama._init_layer_jit(jax.random.PRNGKey(5), whole, True)
    assert layer["w_gate"].shape[0] == 16 and "w_gate_sh" not in layer
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))
    total, touched = 0.0, 0
    for rank in range(4):
        held = dataclasses.replace(cfg, experts_held=(rank * 4, 4))
        part = {**layer, **{k: layer[k][rank * 4:rank * 4 + 4]
                            for k in ("w_gate", "w_up", "w_down")}}
        counters = {}
        total = total + llama._mlp(x, part, held, counters=counters)[0]
        touched += int(counters["assignments_held"])
    assert touched == 24 * 4            # every assignment fell to one chip
    ties, gaps = {}, {}
    with jax.default_matmul_precision("highest"):
        want = model.reference._routed(x[0], layer, whole, 0, [], {}, ties,
                                       gaps, 0.0)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# -- the rotary rule ----------------------------------------------------------


def test_the_references_yarn_is_the_programs(model):
    """Two independent writings of the rule agree, and differ from plain
    RoPE where the factor bites."""
    cfg = model.cfg
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 200, 2, cfg.head_dim))
    positions = jnp.arange(200)[None, :]
    for rule in (cfg.rope_scaling, ()):
        freqs, att = model.reference.rope_frequencies(
            cfg.head_dim, cfg.rope_theta, rule)
        np.testing.assert_allclose(
            llama._rope(x, positions, cfg.rope_theta, rule)[0],
            model.reference._rope(x[0], freqs, att), rtol=1e-4, atol=1e-4)
    plain = llama._rope(x, positions, cfg.rope_theta, ())
    yarn = llama._rope(x, positions, cfg.rope_theta, cfg.rope_scaling)
    assert float(jnp.abs(plain - yarn).max()) > 0.5


def test_a_config_says_which_rule_a_layer_takes():
    base = dict(num_layers=4, sliding_window=8, swa_layers=(0, 2))
    yarn = ("yarn", 16.0, 32.0, 1.0, 64.0, 1.2)
    same = llama.LlamaConfig(**base, rope_scaling=yarn)
    assert [same.layer_rope(i) for i in range(4)] == [yarn] * 4
    split = llama.LlamaConfig(**base, rope_scaling=yarn,
                              swa_rope_scaling=("default",))
    assert [split.layer_rope(i) for i in range(4)] == [(), yarn, (), yarn]
    other = llama.LlamaConfig(**base, swa_rope_scaling=yarn)
    assert [other.layer_rope(i) for i in range(4)] == [yarn, (), yarn, ()]
    with pytest.raises(ValueError, match="swa_rope_scaling"):
        llama.LlamaConfig(num_layers=4, swa_rope_scaling=yarn)
    with pytest.raises(ValueError, match="rope_scaling must be"):
        llama.LlamaConfig(**base, rope_scaling=("default",))
    with pytest.raises(ValueError, match="rope_scaling must be"):
        llama.LlamaConfig(**base, swa_rope_scaling=("linear", 2.0))


# -- the loader ---------------------------------------------------------------


def published(rehearse=True, **changes) -> SimpleNamespace:
    conf = names.config_for_run(names.benchmark(), CONFIG, rehearse=rehearse)
    return SimpleNamespace(**{**{k: v for k, v in conf.items()
                                 if k != "kvbench"}, **changes})


def test_the_loader_reads_the_published_keys():
    """The configuration at its published widths (nothing is built)."""
    cfg = config_from_hf(published(rehearse=False), page_size=64)
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2304, 28, 32, 4, 128, 24576)
    assert cfg.is_hybrid and cfg.sliding_window == 1024
    assert cfg.group_layers(0) == (3, 7, 11, 15, 19, 23, 27)
    assert len(cfg.group_layers(1)) == 21 and cfg.window_pages == 576
    assert cfg.rope_theta == 500000.0
    assert cfg.rope_scaling == ("yarn", 16.0, 32.0, 1.0, 8192.0,
                                1.2772588722239782)
    assert cfg.swa_rope_scaling == ("default",)
    assert cfg.qk_norm and cfg.norm_eps == 1e-6
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_token,
            cfg.moe_intermediate_size, cfg.n_shared_experts) == (
                64, (0, 16), 8, 896, 0)
    assert (cfg.moe_router, cfg.moe_dispatch) == (("softmax_topk", 1),
                                                  "grouped")
    assert cfg.step_counters == ("assignments_held", "experts_touched")


def test_the_loader_reads_a_share_a_rule_a_kind_and_the_pools_key():
    whole = config_from_hf(published(layer_share=None, num_experts=16),
                           page_size=32)
    assert whole.num_experts == 16 and whole.experts_held == ()
    third = config_from_hf(published(layer_share={
        "chips": 4, "rank": 3, "n_routed_experts": 16}), page_size=32)
    assert third.experts_held == (12, 4)
    with pytest.raises(ValueError, match="layer_share"):
        config_from_hf(published(layer_share={
            "chips": 2, "rank": 0, "n_routed_experts": 16}), page_size=32)
    assert config_from_hf(published(window_pages=7),
                          page_size=32).window_pages == 7
    both = config_from_hf(published(rope_parameters={
        "full_attention": YARN, "sliding_attention": YARN}), page_size=32)
    assert both.swa_rope_scaling == () and both.rope_scaling[0] == "yarn"
    swapped = config_from_hf(published(rope_parameters={
        "full_attention": PLAIN, "sliding_attention": YARN}), page_size=32)
    assert swapped.rope_scaling == () and swapped.swa_rope_scaling[0] == "yarn"
    assert not config_from_hf(published(qk_norm=False), page_size=32).qk_norm
    full = config_from_hf(published(layer_types=["full_attention"] * 4),
                          page_size=32)
    assert not full.is_hybrid and full.sliding_window is None


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("use_sliding_window", False),
    ("norm_topk_prob", False),
    ("layer_types", ["sliding_attention", "mamba", "full_attention",
                     "full_attention"]),
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse"]),
    ("rope_parameters", {"full_attention": {**YARN, "rope_theta": 10000},
                         "sliding_attention": PLAIN}),
    ("rope_parameters", {"full_attention": YARN}),
])
def test_the_loader_refuses_what_is_not_built_by_the_keys_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        config_from_hf(published(**{key: value}), page_size=32)


def test_an_engine_takes_its_own_window_pool_size_first(model):
    eng = engine(model, num_swa_pages=24)
    assert eng.swa_manager.num_pages == 24
    bare = engine(model, dataclasses.replace(model.cfg, window_pages=0))
    assert bare.swa_manager.num_pages == 64      # as many as the global pool
    plain = MiniEngine(EngineConfig(model=llama.LlamaConfig.tiny(),
                                    num_pages=16, max_pages_per_seq=4))
    assert "window_free" not in plain.block_manager.pool_stats()
