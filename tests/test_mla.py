"""MLA (multi-head latent attention) model family: absorbed paged serving.

The engine serves MLA in absorbed form — multi-query paged attention over
the latent itself (models/llama.py MLA branch). These tests pin that to
the textbook non-absorbed formulation (materialize per-head K/V from the
latent, plain causal attention), and cover the family end-to-end:
latent-paged engine serving, prefix reuse, mla_attention
event tagging (reference ``events.go:34``), and single-stream offload
round-trips.
"""

import jax
import jax.numpy as jnp
import numpy as np

from llmd_kv_cache_tpu.core.hma import SPEC_MLA
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_kv_cache,
    init_params,
)
from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

CFG = LlamaConfig.deepseek_tiny()


def _rope_ref(x, positions, theta):
    """Same rotary formula as models/llama._rope, for the oracle."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b,s,d/2]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def naive_mla_logits(params, cfg, tokens):
    """Non-absorbed dense MLA forward (no paging, no absorption):
    materialize k_nope/v per head from the latent, standard causal MHA
    with the decoupled-RoPE key appended — the DeepSeek-V2 §2.1 equations
    as written."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :].repeat(b, axis=0)
    hd, dr = cfg.head_dim, cfg.qk_rope_head_dim
    x = params["embed"][tokens]

    def rms(v, w, eps=None):
        eps = cfg.norm_eps if eps is None else eps
        var = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (v.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
                ).astype(v.dtype) * w.astype(v.dtype)

    for layer in params["layers"]:
        attn_in = rms(x, layer["attn_norm"])
        q = (attn_in @ layer["wq"]).reshape(b, s, cfg.num_heads, hd + dr)
        q_nope, q_rope = q[..., :hd], _rope_ref(q[..., hd:], positions,
                                                cfg.rope_theta)
        c_kv = attn_in @ layer["w_dkv"]                      # [b,s,r]
        k_rope = _rope_ref((attn_in @ layer["w_kr"])[:, :, None, :],
                           positions, cfg.rope_theta)        # [b,s,1,dr]
        k_nope = jnp.einsum("bsr,hrd->bshd", c_kv, layer["w_uk"])
        v = jnp.einsum("bsr,hrv->bshv", c_kv, layer["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope,
                                      k_nope.shape[:-1] + (dr,))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)

        scale = (hd + dr) ** -0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk",
                            qf.astype(jnp.float32) * scale,
                            k.astype(jnp.float32))
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
        ctx = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(logits, -1),
                         v.astype(jnp.float32)).astype(x.dtype)
        x = x + ctx.reshape(b, s, -1) @ layer["wo"]

        mlp_in = rms(x, layer["mlp_norm"])
        gated = jax.nn.silu(mlp_in @ layer["w_gate"]) * (mlp_in @ layer["w_up"])
        x = x + gated @ layer["w_down"]

    x = rms(x, params["final_norm"])
    return (x @ params["lm_head"]).astype(jnp.float32)


class TestAbsorbedEqualsNaive:
    def test_paged_absorbed_matches_dense_non_absorbed(self):
        """The serving path (paged + absorbed up-projections) reproduces
        the textbook MLA forward to bf16 tolerance."""
        params = init_params(jax.random.PRNGKey(0), CFG)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(1, 250, (1, 12)), jnp.int32)
        k_cache, v_cache = init_kv_cache(CFG, num_pages=16)
        table = jnp.arange(1, 5, dtype=jnp.int32)[None, :].repeat(1, 0)
        table = jnp.pad(table, ((0, 0), (0, 4)))
        logits, _, _ = forward(
            params, CFG, tokens, k_cache, v_cache, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([12], jnp.int32))
        ref = naive_mla_logits(params, CFG, tokens)
        np.testing.assert_allclose(
            np.asarray(logits[:, :12]), np.asarray(ref),
            rtol=0.05, atol=0.05)
        assert np.mean(np.argmax(np.asarray(logits[:, :12]), -1)
                       == np.argmax(np.asarray(ref), -1)) == 1.0


class TestMLACacheLayout:
    def test_latent_pages_and_zero_width_v(self):
        k_cache, v_cache = init_kv_cache(CFG, num_pages=8)
        r_total = CFG.kv_lora_rank + CFG.qk_rope_head_dim
        assert k_cache.shape == (CFG.num_layers, 8, 1, CFG.page_size, r_total)
        assert v_cache.shape == (CFG.num_layers, 8, 1, CFG.page_size, 0)

    def test_memory_ratio_vs_gqa(self):
        """The family's point: latent bytes/token far below GQA K+V."""
        k, v = init_kv_cache(CFG, num_pages=8)
        gqa = LlamaConfig.tiny()
        kg, vg = init_kv_cache(gqa, num_pages=8)
        assert (k.nbytes + v.nbytes) * 2 < (kg.nbytes + vg.nbytes)

    def test_config_validation(self):
        import pytest

        with pytest.raises(ValueError, match="qk_rope_head_dim"):
            LlamaConfig(kv_lora_rank=16)
        with pytest.raises(ValueError, match="sliding_window_mla"):
            LlamaConfig(kv_lora_rank=16, qk_rope_head_dim=8,
                        sliding_window=8, swa_layers=(0,))


class TestMLAEngine:
    def _engine(self, **kw):
        return MiniEngine(
            EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                         max_batch=4, model_name="ds", pod_identifier="p",
                         **kw),
            seed=0,
        )

    def test_serve_and_prefix_reuse(self):
        eng = self._engine()
        prompt = list(range(10, 29))
        toks = eng.generate("r", prompt, max_new_tokens=8)
        req = eng.add_request("r2", prompt, max_new_tokens=1)
        assert req.cached_len > 0  # latent blocks served from cache
        eng2 = self._engine()
        assert eng2.generate("r", prompt, max_new_tokens=8) == toks

    def test_events_tagged_mla(self):
        events = []
        eng = MiniEngine(
            EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                         max_batch=4, model_name="ds", pod_identifier="p"),
            event_sink=events.extend, seed=0)
        eng.generate("r", list(range(10, 22)), max_new_tokens=2)
        stored = [e for e in events if hasattr(e, "kv_cache_spec_kind")]
        assert stored and all(
            e.kv_cache_spec_kind == SPEC_MLA for e in stored)

    def test_tp_mesh_accepted(self):
        """TP MLA serving is implemented (head-axis sharding, replicated
        latent pool) — engine init must accept a tp mesh. Token identity
        vs single-device is covered in test_tp_serve.py."""
        import pytest

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >= 2 devices")
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devs[:2]), ("tp",))
        eng = MiniEngine(
            EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                         model_name="ds", pod_identifier="p"),
            seed=0, mesh=mesh)
        # The latent pool replicates: every shard holds the full pool.
        assert next(iter(eng.k_cache.addressable_shards)).data.shape == \
            eng.k_cache.shape

    def test_dp_and_sp_meshes_token_identical(self):
        """MLA under dp (replicated) and sp (sequence-sharded prefill)
        meshes: the absorbed forward is token-parallel, so both must
        match single-device token-for-token (the architecture doc's
        composition matrix cites this test)."""
        import pytest

        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs >= 4 devices")
        from jax.sharding import Mesh

        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()

        def gen(mesh=None):
            return MiniEngine(
                EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                             model_name="ds", pod_identifier="p"),
                seed=0, mesh=mesh).generate("r", prompt, max_new_tokens=8)

        ref = gen()
        assert gen(Mesh(np.array(devs[:2]), ("dp",))) == ref
        assert gen(Mesh(np.array(devs[:2]), ("sp",))) == ref
        assert gen(Mesh(np.array(devs[:4]).reshape(2, 2),
                        ("dp", "sp"))) == ref


class TestMLAOffload:
    def test_misdeclared_spec_rejected(self, tmp_path):
        """An MLA engine with a default two-stream spec must fail loudly,
        not write latent files under K+V metadata."""
        import pytest

        spec = SharedStorageOffloadSpec(
            root=str(tmp_path), model_name="ds", page_size=CFG.page_size,
            num_layers=CFG.num_layers, kv_heads=CFG.num_kv_heads,
            head_dim=CFG.head_dim, io_threads=2, parallel_agnostic=True,
        )
        with pytest.raises(ValueError, match="kv_streams=1"):
            MiniEngine(
                EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                             model_name="ds", pod_identifier="p"),
                seed=0, offload_spec=spec)

    def test_single_stream_storage_roundtrip(self, tmp_path):
        """Latent blocks offload as one-stream files and restore bit-exactly
        on a fresh pod (same machinery, half the bytes of a K+V store)."""
        def spec():
            return SharedStorageOffloadSpec(
                root=str(tmp_path), model_name="ds",
                page_size=CFG.page_size, num_layers=CFG.num_layers,
                kv_heads=CFG.kv_cache_heads, head_dim=CFG.kv_cache_head_dim,
                kv_streams=1, io_threads=2, parallel_agnostic=True,
            )

        def engine(pod):
            return MiniEngine(
                EngineConfig(model=CFG, num_pages=64, max_pages_per_seq=16,
                             max_batch=4, model_name="ds",
                             pod_identifier=pod),
                seed=0, offload_spec=spec())

        prompt = list(range(70, 86))
        a = engine("pod-a")
        out = a.generate("r1", prompt, max_new_tokens=4)
        a.flush_offload()

        b = engine("pod-b")
        req = b.add_request("r2", prompt, max_new_tokens=4)
        assert req.cached_len == len(prompt)  # restored, not recomputed
        while not req.done:
            b.step()
        assert req.output == out  # latent restored bit-exactly
