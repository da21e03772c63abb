"""fuse_params parity: fused wider matmuls must reproduce the unfused
forward exactly-enough (same dtype math over the same reductions — the
per-column dot products are identical; only tiling may differ).

Families covered: GQA (tiny), QKV biases + qk_norm (qwen-lineage),
absorbed MLA incl. q-LoRA + shared-expert MoE (deepseek), dense SwiGLU.
The serving engine turns fusion on by default for single-shard engines
whose shape profits (llama.fuse_profitable — the v5e measured fusion
slower below hidden 4096); this file pins the equivalence, the layout
contract, and the shape-aware auto rule directly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.models.llama import (
    LlamaConfig,
    forward,
    fuse_params,
    init_kv_cache,
    init_params,
)


def run_forward(cfg, params, seed=5):
    rng = np.random.default_rng(seed)
    batch, seq = 2, 8
    k, v = init_kv_cache(cfg, num_pages=16)
    tokens = jnp.asarray(
        rng.integers(1, cfg.vocab_size - 1, (batch, seq)), jnp.int32)
    table = jnp.asarray(
        rng.permutation(16)[: batch * 4].reshape(batch, 4), jnp.int32)
    ctx = jnp.zeros((batch,), jnp.int32)
    new = jnp.full((batch,), seq, jnp.int32)
    logits, k, v = forward(params, cfg, tokens, k, v, table, ctx, new)
    return np.asarray(logits), np.asarray(k), np.asarray(v)


FAMILIES = {
    "gqa": lambda: LlamaConfig.tiny(),
    "qwen3_qknorm": lambda: LlamaConfig.qwen3_tiny(),
    "deepseek_mla_moe": lambda: LlamaConfig.deepseek_tiny(),
    "mixtral_moe": lambda: LlamaConfig.mixtral_tiny(),
    "sinks": lambda: LlamaConfig.sink_tiny(),
}


class TestFusedParity:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_logits_and_cache_parity(self, family):
        cfg = FAMILIES[family]()
        params = init_params(jax.random.PRNGKey(1), cfg)
        fused = fuse_params(params, cfg)
        base_logits, base_k, base_v = run_forward(cfg, params)
        f_logits, f_k, f_v = run_forward(cfg, fused)
        np.testing.assert_allclose(f_logits, base_logits,
                                   rtol=2e-5, atol=2e-5)
        assert np.argmax(f_logits[..., -1, :], -1).tolist() == \
            np.argmax(base_logits[..., -1, :], -1).tolist()
        np.testing.assert_allclose(f_k, base_k, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(f_v, base_v, rtol=2e-5, atol=2e-5)

    def test_qkv_biases_fuse(self):
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(2), cfg)
        rng = np.random.default_rng(3)
        for layer in params["layers"]:
            for name, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
                layer[name] = jnp.asarray(
                    rng.standard_normal(layer[w].shape[1]) * 0.02,
                    layer[w].dtype)
        fused = fuse_params(params, cfg)
        assert "b_qkv" in fused["layers"][0]
        base_logits, *_ = run_forward(cfg, params)
        f_logits, *_ = run_forward(cfg, fused)
        np.testing.assert_allclose(f_logits, base_logits,
                                   rtol=2e-5, atol=2e-5)

    def test_layout_contract(self):
        cfg = LlamaConfig.tiny()
        fused = fuse_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
        lyr = fused["layers"][0]
        assert "w_qkv" in lyr and "w_gate_up" in lyr
        for gone in ("wq", "wk", "wv", "w_gate", "w_up"):
            assert gone not in lyr
        h = cfg.hidden_size
        assert lyr["w_qkv"].shape == (
            h, (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim)
        assert lyr["w_gate_up"].shape == (h, 2 * cfg.intermediate_size)

    def test_moe_expert_weights_untouched(self):
        cfg = LlamaConfig.mixtral_tiny()
        fused = fuse_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
        lyr = fused["layers"][0]
        # 3-D expert stacks stay; only the attention projections fuse.
        assert "w_gate" in lyr and lyr["w_gate"].ndim == 3
        assert "w_qkv" in lyr


class TestEngineFusion:
    def test_engine_auto_fusion_is_shape_aware(self):
        # Auto (fuse_projections=None) consults fuse_profitable (fuse
        # from a per-shard hidden width of 4096 up), so narrow test
        # models stay unfused and wide single-shard engines fuse.
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import fuse_profitable

        eng = MiniEngine(EngineConfig(num_pages=32, max_pages_per_seq=8))
        assert not fuse_profitable(eng.cfg.model)
        assert "wq" in eng.params["layers"][0]
        assert "w_qkv" not in eng.params["layers"][0]
        req = eng.add_request("r0", list(range(1, 20)), max_new_tokens=4)
        while not req.done:
            eng.step()
        assert len(req.output) == 4

    def test_engine_fuses_when_asked(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        eng = MiniEngine(EngineConfig(num_pages=32, max_pages_per_seq=8,
                                      fuse_projections=True))
        assert "w_qkv" in eng.params["layers"][0]
        req = eng.add_request("r0", list(range(1, 20)), max_new_tokens=4)
        while not req.done:
            eng.step()
        assert len(req.output) == 4

    def test_fuse_profitable_crossover(self):
        import dataclasses

        from llmd_kv_cache_tpu.models.llama import fuse_profitable

        narrow = LlamaConfig.tiny()
        assert not fuse_profitable(narrow)
        wide = dataclasses.replace(narrow, hidden_size=4096)
        assert fuse_profitable(wide)

    def test_fused_engine_matches_unfused_tokens(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        prompt = list(range(1, 40))
        outs = []
        for fuse in (False, True):
            eng = MiniEngine(EngineConfig(
                num_pages=64, max_pages_per_seq=16, fuse_projections=fuse),
                seed=0)
            req = eng.add_request("r0", prompt, max_new_tokens=8)
            while not req.done:
                eng.step()
            outs.append(list(req.output))
        assert outs[0] == outs[1]


class TestUnfuse:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_round_trip_is_identity(self, family):
        from llmd_kv_cache_tpu.models.llama import unfuse_params

        cfg = FAMILIES[family]()
        params = init_params(jax.random.PRNGKey(4), cfg)
        back = unfuse_params(fuse_params(params, cfg), cfg)
        flat_a = jax.tree_util.tree_leaves_with_path(params)
        flat_b = dict(jax.tree_util.tree_leaves_with_path(back))
        assert len(flat_a) == len(flat_b)
        for path, leaf in flat_a:
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat_b[path]))

    def test_unfuse_is_noop_on_canonical(self):
        from llmd_kv_cache_tpu.models.llama import unfuse_params

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(4), cfg)
        back = unfuse_params(params, cfg)
        assert set(back["layers"][0]) == set(params["layers"][0])


class TestFusionInterplay:
    def test_mla_engine_ignores_decode_batch_rows(self):
        """kv_cache_heads == 1 (absorbed MLA) runs the per-head kernel;
        the rows knob must clamp, not crash (review r5 finding)."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        eng = MiniEngine(EngineConfig(
            model=LlamaConfig.deepseek_tiny(), num_pages=64,
            max_pages_per_seq=16, use_pallas_decode=True,
            decode_batch_rows=4))
        req = eng.add_request("r0", list(range(1, 20)), max_new_tokens=3)
        while not req.done:
            eng.step()
        assert len(req.output) == 3

    def test_checkpoint_saves_canonical_layout(self, tmp_path):
        from llmd_kv_cache_tpu.models.checkpoint import (
            load_engine_checkpoint, save_engine_checkpoint)
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        # Explicit fuse: the shape-aware auto would leave the tiny model
        # unfused and this test pins the fused→canonical save path.
        eng = MiniEngine(EngineConfig(model=cfg, num_pages=32,
                                      max_pages_per_seq=8,
                                      fuse_projections=True), seed=1)
        assert "w_qkv" in eng.params["layers"][0]  # fused serving tree
        save_engine_checkpoint(str(tmp_path / "ck"), eng.params, cfg,
                               "tiny", "s")
        params, cfg2, _, _ = load_engine_checkpoint(str(tmp_path / "ck"))
        assert "wq" in params["layers"][0]
        assert "w_qkv" not in params["layers"][0]


class TestInterleavedTP:
    """Fused projections under tensor-parallel serving: the per-rank
    interleaved column layout (``fused_interleave`` = tp) keeps the
    fused leaves Megatron-column-shardable — token identity, sharding,
    and collective-count parity vs the unfused layout."""

    pytestmark = pytest.mark.skipif(
        len(jax.devices()) < 8,
        reason="needs the 8-device virtual CPU mesh (tests/conftest.py)",
    )

    def _mesh(self, axes):
        from llmd_kv_cache_tpu.parallel.mesh import make_mesh

        n = 1
        for v in axes.values():
            n *= v
        return make_mesh(axes, jax.devices()[:n])

    @pytest.mark.parametrize("family", ["gqa", "qwen3_qknorm",
                                        "mixtral_moe", "sinks"])
    def test_interleaved_forward_parity(self, family):
        """fuse(t=2) + interleave-aware split == canonical forward
        (single device: the layout permutation alone must be exact)."""
        import dataclasses

        cfg = FAMILIES[family]()
        params = init_params(jax.random.PRNGKey(1), cfg)
        tcfg = dataclasses.replace(cfg, fused_interleave=2)
        fused = fuse_params(params, tcfg)
        base_logits, base_k, base_v = run_forward(cfg, params)
        f_logits, f_k, f_v = run_forward(tcfg, fused)
        np.testing.assert_allclose(f_logits, base_logits,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(f_k, base_k, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("family", ["gqa", "qwen3_qknorm",
                                        "mixtral_moe", "sinks"])
    def test_interleave_round_trip(self, family):
        import dataclasses

        from llmd_kv_cache_tpu.models.llama import unfuse_params

        cfg = dataclasses.replace(FAMILIES[family](), fused_interleave=2)
        params = init_params(jax.random.PRNGKey(4), cfg)
        back = unfuse_params(fuse_params(params, cfg), cfg)
        flat_a = jax.tree_util.tree_leaves_with_path(params)
        flat_b = dict(jax.tree_util.tree_leaves_with_path(back))
        assert len(flat_a) == len(flat_b)
        for path, leaf in flat_a:
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat_b[path]))

    def test_interleave_refused_for_mla(self):
        import dataclasses

        with pytest.raises(ValueError, match="fused_interleave"):
            dataclasses.replace(LlamaConfig.deepseek_tiny(),
                                fused_interleave=2)

    def test_engine_fused_tp_matches_unfused(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(mesh=None, fuse=None, **kw):
            e = MiniEngine(EngineConfig(model=cfg, num_pages=64,
                                        max_pages_per_seq=16,
                                        fuse_projections=fuse,
                                        model_name="fuse-tp",
                                        pod_identifier="p", **kw),
                           params=params, mesh=mesh, seed=0)
            return e, e.generate("r", prompt, max_new_tokens=8)

        _, ref = gen()
        mesh = self._mesh({"tp": 2})
        e, out = gen(mesh=mesh, fuse=True)
        assert out == ref
        w = e.params["layers"][0]["w_qkv"]
        assert e.cfg.model.fused_interleave == 2
        # really column-sharded, not silently replicated
        assert w.sharding.shard_shape(w.shape)[1] == w.shape[1] // 2
        _, dptp = gen(mesh=self._mesh({"dp": 4, "tp": 2}), fuse=True)
        assert dptp == ref

    def test_hlo_collective_parity(self):
        """The interleaved split must compile to LOCAL reshapes: same
        collective counts as the unfused tp forward (an all-gather would
        mean the layout broke GSPMD propagation)."""
        import dataclasses

        from llmd_kv_cache_tpu.parallel.mesh import shard_params
        from llmd_kv_cache_tpu.parallel.serve import shard_kv_pool

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        mesh = self._mesh({"tp": 2})

        def counts(cfg_used, tree):
            with_mesh = shard_params(mesh, tree)
            k, v = init_kv_cache(cfg, 64)
            k, v = shard_kv_pool(mesh, k, v)
            tokens = jnp.zeros((1, 8), jnp.int32)
            table = jnp.zeros((1, 16), jnp.int32)
            ctx = jnp.zeros((1,), jnp.int32)
            new = jnp.full((1,), 8, jnp.int32)
            txt = jax.jit(forward, static_argnames=("cfg",)).lower(
                with_mesh, cfg_used, tokens, k, v, table, ctx, new
            ).compile().as_text()
            return {op: txt.count(op) for op in
                    ("all-reduce", "all-gather", "collective-permute",
                     "all-to-all")}

        tcfg = dataclasses.replace(cfg, fused_interleave=2)
        assert counts(tcfg, fuse_params(params, tcfg)) == \
            counts(cfg, params)

    def test_mesh_refusals(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        with pytest.raises(ValueError, match="MLA under a mesh"):
            MiniEngine(EngineConfig(model=LlamaConfig.deepseek_tiny(),
                                    num_pages=32, max_pages_per_seq=8,
                                    fuse_projections=True),
                       mesh=self._mesh({"tp": 2}))
        with pytest.raises(ValueError, match="pp serving"):
            MiniEngine(EngineConfig(num_pages=32, max_pages_per_seq=8,
                                    max_batch=2, fuse_projections=True),
                       mesh=self._mesh({"pp": 2}))
        # Auto under the same meshes: silently unfused, no raise.
        e = MiniEngine(EngineConfig(model=LlamaConfig.deepseek_tiny(),
                                    num_pages=32, max_pages_per_seq=8),
                       mesh=self._mesh({"tp": 2}))
        assert "w_mla_in" not in e.params["layers"][0]

    def test_checkpoint_canonical_from_fused_tp(self, tmp_path):
        from llmd_kv_cache_tpu.models.checkpoint import (
            load_engine_checkpoint, save_engine_checkpoint)
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        eng = MiniEngine(EngineConfig(model=cfg, num_pages=32,
                                      max_pages_per_seq=8,
                                      fuse_projections=True),
                         mesh=self._mesh({"tp": 2}), seed=1)
        assert eng.cfg.model.fused_interleave == 2
        save_engine_checkpoint(str(tmp_path / "ck"), eng.params,
                               eng.cfg.model, "tiny", "s")
        params, cfg2, _, _ = load_engine_checkpoint(str(tmp_path / "ck"))
        assert "wq" in params["layers"][0]
        assert cfg2.fused_interleave == 1
        # Canonical bytes: identical to an unfused single-device init.
        ref = init_params(jax.random.PRNGKey(1), cfg)
        for key in ("wq", "wk", "wv", "w_gate", "w_up"):
            np.testing.assert_array_equal(
                np.asarray(params["layers"][0][key]),
                np.asarray(ref["layers"][0][key]))

    def test_prefused_shared_tree_relayouts_under_tp(self):
        """The documented sharing path (maybe_fuse_params → one
        canonical-order fused tree across pods) handed to a tp engine:
        the engine must re-layout into its interleaved order, not
        silently permute q/k/v through the t>1 split (review r5)."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        prefused = fuse_params(params, cfg)  # canonical column order
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(p, mesh=None):
            e = MiniEngine(EngineConfig(model=cfg, num_pages=64,
                                        max_pages_per_seq=16,
                                        fuse_projections=True,
                                        model_name="fuse-tp",
                                        pod_identifier="p"),
                           params=p, mesh=mesh, seed=0)
            return e.generate("r", prompt, max_new_tokens=8)

        ref = gen(params)
        out = gen(prefused, mesh=self._mesh({"tp": 2}))
        assert out == ref

    def test_non_dividing_widths_refused_loudly(self):
        """Projection widths that do not divide tp cannot shard at all
        (jax.device_put refuses uneven NamedShardings, fused or not) —
        validate_tp_config must surface that at engine construction
        with the width named, instead of the late cryptic device_put
        error (review r5)."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig(vocab_size=64, hidden_size=64, num_layers=1,
                          num_heads=8, num_kv_heads=8, head_dim=16,
                          intermediate_size=100, page_size=4)
        with pytest.raises(ValueError, match="intermediate_size"):
            MiniEngine(EngineConfig(model=cfg, num_pages=32,
                                    max_pages_per_seq=8,
                                    model_name="nondiv",
                                    pod_identifier="p"),
                       mesh=self._mesh({"tp": 8}), seed=0)

    def test_fused_tp_composes_with_fp8_cache(self):
        """Weights-side fusion and cache-side fp8 are orthogonal; the
        triple (fused interleave + fp8 pool + tp mesh) is the realistic
        wide-model deployment and must match the unfused single-device
        fp8 engine token-for-token."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(mesh=None, fuse=None):
            e = MiniEngine(EngineConfig(model=cfg, num_pages=64,
                                        max_pages_per_seq=16,
                                        fuse_projections=fuse,
                                        kv_cache_dtype="f8_e4m3",
                                        model_name="fuse-fp8",
                                        pod_identifier="p"),
                           params=params, mesh=mesh, seed=0)
            return e, e.generate("r", prompt, max_new_tokens=8)

        _, ref = gen()
        e, out = gen(mesh=self._mesh({"tp": 2}), fuse=True)
        assert out == ref
        assert e.k_cache.dtype == jnp.float8_e4m3fn
        assert "w_qkv" in e.params["layers"][0]

    def test_fused_tp_composes_with_sp_prefill(self):
        """Sequence-parallel prefill shards the chunk tokens; the fused
        interleaved matmul consumes the sharded activations like the
        unfused ones (same contraction dim) — tp x sp fused must match
        single-device."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(mesh=None, fuse=None):
            e = MiniEngine(EngineConfig(model=cfg, num_pages=64,
                                        max_pages_per_seq=16,
                                        fuse_projections=fuse,
                                        model_name="fuse-sp",
                                        pod_identifier="p"),
                           params=params, mesh=mesh, seed=0)
            return e.generate("r", prompt, max_new_tokens=8)

        ref = gen()
        out = gen(mesh=self._mesh({"tp": 2, "sp": 2}), fuse=True)
        assert out == ref

    def test_fused_and_fp8_compose_with_ep_moe(self):
        """Expert-parallel MoE serving with fused attention (experts
        stay 3-D unfused; only w_qkv/w_gate_up_sh fuse) and an fp8 pool:
        both must match the single-device engine."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

        cfg = LlamaConfig.mixtral_tiny()
        params = init_params(jax.random.PRNGKey(3), cfg)
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(mesh=None, fuse=None, dtype=None):
            e = MiniEngine(EngineConfig(model=cfg, num_pages=64,
                                        max_pages_per_seq=16,
                                        fuse_projections=fuse,
                                        kv_cache_dtype=dtype,
                                        model_name="ep-moe",
                                        pod_identifier="p"),
                           params=params, mesh=mesh, seed=0)
            return e, e.generate("r", prompt, max_new_tokens=8)

        _, ref = gen()
        ep = self._mesh({"ep": 2})
        e, out = gen(mesh=ep, fuse=True)
        assert out == ref
        assert "w_qkv" in e.params["layers"][0]
        assert e.params["layers"][0]["w_gate"].ndim == 3  # experts unfused
        _, ref8 = gen(dtype="f8_e4m3")
        _, out8 = gen(mesh=ep, dtype="f8_e4m3")
        assert out8 == ref8
        _, eptp = gen(mesh=self._mesh({"ep": 2, "tp": 2}), fuse=True)
        assert eptp == ref
