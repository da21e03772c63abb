"""Tensor-parallel serving: a mesh-sharded MiniEngine matches the
single-device engine.

Runs on the virtual 8-device CPU mesh (conftest). The reference only
fingerprints TP topology for its offload store (``file_mapper.py:63-74``);
here the serving engine itself shards — params in the Megatron layout, KV
pools on the kv-heads axis — and the unchanged jitted forwards run SPMD.
"""

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the 8-device virtual CPU mesh (tests/conftest.py)",
)

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
from llmd_kv_cache_tpu.parallel.mesh import make_mesh
from llmd_kv_cache_tpu.parallel.serve import (
    mesh_tp_size, validate_tp_config)


def _engine(cfg, params, mesh=None, **kw):
    return MiniEngine(
        EngineConfig(**{**dict(model=cfg, num_pages=64, max_pages_per_seq=16,
                               model_name="tp-test", pod_identifier="p"),
                        **kw}),
        params=params, mesh=mesh,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    return cfg, params


def test_tp_engine_matches_single_device(setup):
    cfg, params = setup
    prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=8)

    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=8)
    assert out == ref


def test_tp_with_dp_axis(setup):
    """A dp axis alongside tp (the fleet shape) places and runs fine;
    batch stays replicated — dp is across engines, not within one."""
    cfg, params = setup
    prompt = np.random.default_rng(1).integers(1, 250, 16).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=6)
    mesh = make_mesh({"dp": 4, "tp": 2})
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=6)
    assert out == ref


def test_tp_hybrid_engine(setup):
    """Hybrid (full+SWA) models shard both page pools."""
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
        sliding_window=8, swa_layers=(1,),
    )
    params = init_params(jax.random.PRNGKey(5), cfg)
    prompt = np.random.default_rng(3).integers(1, 250, 20).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=6)
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=6)
    assert out == ref


def test_tp_pallas_attention(setup):
    """Pallas flash prefill+decode under tp: shard_map runs the kernel on
    each shard's local kv heads; tokens match the single-device XLA
    engine (interpret mode on the CPU mesh)."""
    cfg, params = setup
    prompt = np.random.default_rng(6).integers(1, 250, 24).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=8)
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh,
                  use_pallas_decode=True).generate("r", prompt,
                                                   max_new_tokens=8)
    assert out == ref


def test_tp_less_mesh_replicates(setup):
    """A mesh with no tp axis (dp-only fleet mesh) must not crash engine
    init: the KV pools place replicated and serving still matches."""
    cfg, params = setup
    prompt = np.random.default_rng(4).integers(1, 250, 12).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=4)
    mesh = make_mesh({"dp": 8})
    eng = _engine(cfg, params, mesh=mesh)
    assert eng.generate("r", prompt, max_new_tokens=4) == ref
    assert len({s.data.shape for s in eng.k_cache.addressable_shards}) == 1
    assert next(iter(eng.k_cache.addressable_shards)).data.shape == \
        eng.k_cache.shape


@pytest.fixture(scope="module")
def mla_setup():
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=16, intermediate_size=128, page_size=4,
        kv_lora_rank=16, qk_rope_head_dim=8,
    )
    params = init_params(jax.random.PRNGKey(11), cfg)
    return cfg, params


def test_tp_mla_matches_single_device(mla_setup):
    """Absorbed MLA under tp: heads shard (wq/w_uk/w_uv/wo), the latent
    cache replicates, tokens match the single-device engine.

    MLA as a first-class family: reference events.go:34 mla_attention."""
    cfg, params = mla_setup
    prompt = np.random.default_rng(8).integers(1, 250, 24).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=8)
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=8)
    assert out == ref


def test_tp_mla_pallas_decode(mla_setup):
    """Absorbed MLA through the flash-decode kernel under tp: each shard
    runs its local query heads as one multi-query group against the
    replicated latent pool (interpret mode on the CPU mesh)."""
    cfg, params = mla_setup
    prompt = np.random.default_rng(10).integers(1, 250, 24).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=8)
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh,
                  use_pallas_decode=True).generate("r", prompt,
                                                   max_new_tokens=8)
    assert out == ref


def test_tp_mla_prefill_keeps_the_absorbed_kernel(mla_setup):
    """A chunk of 128 queries (these widths go per head from 84 on a lone
    device): the sharded engine's chunk program keeps the absorbed kernel,
    per shard over its heads, and says so (``expanded_keys`` 0); same
    tokens as the lone engine, whose chunk attends per head."""
    from llmd_kv_cache_tpu.models.llama import prefill_per_head
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetryConfig)
    from tests.test_telemetry import _recorded

    cfg, params = mla_setup
    prompt = np.random.default_rng(12).integers(1, 250, 100).tolist()
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    assert prefill_per_head(cfg, 128) and not prefill_per_head(cfg, 128, mesh)
    expanded = {}
    out = {}
    for name, m in (("lone", None), ("tp", mesh)):
        eng = _engine(cfg, params, mesh=m, use_pallas_decode=True,
                      use_pallas_prefill=True, max_prefill_tokens=128,
                      max_pages_per_seq=32, telemetry=EngineTelemetryConfig())
        seen = _recorded(eng._phases)
        out[name] = eng.generate("r", prompt, max_new_tokens=4)
        expanded[name] = [a["expanded_keys"] for n, a, _ in seen
                          if n == "step.dispatch" and "prefill_pos" in a]
    assert out["tp"] == out["lone"]
    assert expanded == {"lone": [128], "tp": [0]}


def test_tp_mla_latent_cache_replicates(mla_setup):
    """The latent pool must place replicated under tp — every shard reads
    the full latent for its local heads' multi-query attention."""
    cfg, params = mla_setup
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    eng = _engine(cfg, params, mesh=mesh)
    assert next(iter(eng.k_cache.addressable_shards)).data.shape == \
        eng.k_cache.shape


def test_tp_mla_validation():
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=6,
        num_kv_heads=6, head_dim=16, intermediate_size=128, page_size=4,
        kv_lora_rank=16, qk_rope_head_dim=8,
    )
    mesh = make_mesh({"tp": 4}, jax.devices()[:4])
    with pytest.raises(ValueError, match="num_heads"):
        validate_tp_config(cfg, mesh)


def test_tp_validation():
    cfg = LlamaConfig.tiny()  # num_kv_heads=2
    mesh = make_mesh({"tp": 4}, jax.devices()[:4])
    with pytest.raises(ValueError, match="num_kv_heads"):
        validate_tp_config(cfg, mesh)
    assert mesh_tp_size(None) == 1
    assert mesh_tp_size(make_mesh({"dp": 8})) == 1


def test_tp_cache_sharding_layout(setup):
    """The KV pools physically shard over tp: each shard holds
    kv_heads/tp heads (axis 2 of [layers, pages, kvh, ps, hd])."""
    cfg, params = setup
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    eng = _engine(cfg, params, mesh=mesh)
    shard_shapes = {s.data.shape for s in eng.k_cache.addressable_shards}
    assert shard_shapes == {
        (cfg.num_layers, 64, cfg.num_kv_heads // 2, cfg.page_size,
         cfg.head_dim)
    }


def test_ep_serve_moe_matches_single_device():
    """Expert-parallel SERVING: a MoE engine on an ``ep`` mesh (expert
    axis of the 3-D expert stacks sharded, GSPMD partitioning the
    capacity-dispatch einsums) generates the same tokens as the
    single-device engine."""
    cfg = LlamaConfig.mixtral_tiny()
    params = init_params(jax.random.PRNGKey(9), cfg)
    prompt = np.random.default_rng(9).integers(
        1, cfg.vocab_size - 6, 20).tolist()

    def run(mesh):
        eng = _engine(cfg, params, mesh=mesh, use_pallas_decode=False,
                      fuse_projections=False)
        return eng.generate("r", prompt, max_new_tokens=5)

    ref = run(None)
    got_ep = run(make_mesh({"ep": 2}, jax.devices()[:2]))
    assert got_ep == ref
    got_ep_tp = run(make_mesh({"ep": 2, "tp": 2}, jax.devices()[:4]))
    assert got_ep_tp == ref
