"""The configuration route without ``transformers`` equals the route with
it, and the float32 reference agrees with the engine at toy width."""

import numpy as np
import pytest

from kvbench.harness import fleet, names


@pytest.mark.parametrize("config", ["qwen3-1.7b", "mistral-7b-l16"])
def test_namespace_route_equals_transformers_route(config):
    transformers = pytest.importorskip("transformers")
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.models.hf_loader import config_from_hf

    bench = names.benchmark()
    conf = names.config_for_run(bench, config, False)
    published = {k: v for k, v in conf.items()
                 if k not in ("kvbench", "architectures", "model_type",
                              "torch_dtype")}
    cls = {"qwen3": transformers.Qwen3Config,
           "mistral": transformers.MistralConfig}[conf["model_type"]]
    page = conf["kvbench"]["engine"]["page_size"]
    want = config_from_hf(cls(**published), page_size=page,
                          dtype=jnp.bfloat16)
    assert fleet.model_config(conf) == want


def test_published_widths():
    bench = names.benchmark()
    q = fleet.model_config(names.config_for_run(bench, "qwen3-1.7b", False))
    assert (q.num_layers, q.hidden_size, q.num_heads, q.num_kv_heads,
            q.head_dim, q.intermediate_size, q.vocab_size, q.qk_norm,
            q.sliding_window) == (28, 2048, 16, 8, 128, 6144, 151936, True,
                                  None)
    m = fleet.model_config(names.config_for_run(bench, "mistral-7b-l16",
                                                False))
    assert (m.num_layers, m.hidden_size, m.num_heads, m.num_kv_heads,
            m.head_dim, m.intermediate_size, m.vocab_size, m.qk_norm,
            m.sliding_window) == (16, 4096, 32, 8, 128, 14336, 32000, False,
                                  4096)
    assert m.swa_layers == tuple(range(16)) and not m.is_hybrid


def test_big_seed_makes_a_key():
    import jax

    a = fleet.key_for(2 ** 31 + 5)
    b = fleet.key_for(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))


@pytest.mark.parametrize("config", ["qwen3-1.7b", "mistral-7b-l16"])
def test_reference_agrees_with_the_engine_at_toy_width(config, tmp_path):
    """The probe of ``correct`` (prefill logits, tokens decoded through the
    paged cache, prefix hit, the other replica) against the plain float32
    forward, with the Pallas kernels interpreted; the fused layout too."""
    from kvbench.harness import correct

    bench = names.benchmark()
    conf = names.config_for_run(bench, config, True)
    reference = names.reference(conf)
    cfg, params = fleet.build_model(conf, 2 ** 31 + 11)
    store = tmp_path if conf["kvbench"].get("storage") else None
    fl = fleet.build_fleet(conf, cfg, params, [None, None], store,
                           force_pallas=True)
    try:
        assert fleet.what_serves(fl, interpret=True) == []
        report = correct.probe(fl, params, reference, 2 ** 31 + 11, 40, 3)
        assert report["ok"], report
        assert report["prefill_rel_err"] < reference.TOLERANCE / 2
        assert report["hit_cached_len"] >= 32
    finally:
        fl.shutdown()
    # The reference reads the fused layout the same way.
    from llmd_kv_cache_tpu.models.llama import fuse_params

    tokens = list(range(1, 30))
    plain = reference.logits_at(params, cfg, tokens, [28])
    fused = reference.logits_at(fuse_params(params, cfg), cfg, tokens, [28])
    np.testing.assert_allclose(plain, fused, rtol=1e-5, atol=1e-5)


LATENT = names.KVBENCH / "tests" / "fixtures" / "latent-toy.json"


def toy_conf(config: str) -> dict:
    """A configuration of the benchmark by name, or the tests' fixture with
    a latent cache (its own reference and counts), at toy widths."""
    if config == "latent-toy":
        return names.as_run(names.load_json(LATENT, "the fixture"), True)
    return names.config_for_run(names.benchmark(), config, True)


@pytest.mark.parametrize("config", ["qwen3-1.7b", "latent-toy"])
def test_reference_sees_a_wrong_cache(config):
    """Logits of another context are far outside the tolerance: the bound
    is tight enough to tell a wrong page from bf16 rounding."""
    conf = toy_conf(config)
    reference = names.reference(conf)
    cfg, params = fleet.build_model(conf, 3)
    a = reference.logits_at(params, cfg, list(range(1, 41)), [39])[0]
    b = reference.logits_at(params, cfg, list(range(2, 42)), [39])[0]
    assert np.abs(a - b).max() / np.abs(a).max() > 10 * reference.TOLERANCE


def test_opcount_and_peaks():
    from kvbench.trace import opcount

    bench = names.benchmark()
    q = fleet.model_config(names.config_for_run(bench, "qwen3-1.7b", False))
    # 28 layers x (2048*(16+16)*128 + 16*128*2048 + 3*2048*6144) MACs x 2.
    assert opcount.dense_flops_per_token(q) == pytest.approx(
        2 * 28 * (2048 * 32 * 128 + 2048 * 2048 + 3 * 2048 * 6144))
    assert opcount.keys_attended(0, 4) == 1 + 2 + 3 + 4
    assert opcount.keys_attended(10, 2) == 11 + 12
    assert opcount.keys_attended(0, 6, window=4) == 1 + 2 + 3 + 4 + 4 + 4
    assert opcount.keys_attended(100, 3, window=4) == 12
    assert opcount.decode_attention_bytes(q, 1000) == 2 * 28 * 8 * 128 * 2 * 1000
    assert opcount.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        opcount.peaks("TPU v9 imaginary")


def test_fused_tree_builds_and_matches_the_reference():
    """At hidden 4096 the program's own gate fuses the projections; the
    harness shares that one fused tree and the reference reads it."""
    from kvbench.harness import correct

    bench = names.benchmark()
    conf = names.config_for_run(bench, "mistral-7b-l16", True)
    reference = names.reference(conf)
    conf.update(hidden_size=4096, num_hidden_layers=1)
    cfg, params = fleet.build_model(conf, 5)
    assert "w_qkv" in params["layers"][0] and "w_gate_up" in params[
        "layers"][0]
    conf["kvbench"]["storage"] = None
    fl = fleet.build_fleet(conf, cfg, params, [None], None,
                           force_pallas=True)
    report = correct.probe(fl, params, reference, 5, 40, 2)
    fl.shutdown()
    assert report["ok"], report


@pytest.mark.parametrize("config", ["mistral-7b-l16-store", "latent-toy"])
def test_storage_tier_deployment_at_toy_width(config, tmp_path):
    """The proposed deployment with the shared-storage tier: the probe's
    second replica is served from what the first wrote through, and the
    warm-up walks every gather and scatter size of the copier. Behind the
    fixture's latent cache too: the offload spec takes the pool's payload
    (one stream of one head 96 wide) from the model's config; sized as
    dense GQA the engine refuses it."""
    from types import SimpleNamespace

    from kvbench.harness import correct, prepare

    if config == "latent-toy":
        conf = toy_conf(config)
        conf["kvbench"]["storage"] = {"io_threads": 2}
    else:
        conf = names.as_run(names.load_json(
            names.KVBENCH / "proposed" / f"{config}.json", "config"), True)
    reference = names.reference(conf)
    assert conf["kvbench"]["storage"]
    cfg, params = fleet.build_model(conf, 9)
    fl = fleet.build_fleet(conf, cfg, params, [None, None], tmp_path,
                           force_pallas=True)
    try:
        report = correct.probe(fl, params, reference, 9, 40, 2)
        assert report["ok"], report
        assert report["other_replicas_cached_len"] == [32]
        for eng in fl.engines.values():  # instance attribute: a short test
            eng.offload_handlers.copier.MAX_BATCH_PAGES = 4
        prepare.warm_up(SimpleNamespace(fleet=fl))
    finally:
        fl.shutdown()
    assert any(tmp_path.rglob("*"))
