"""fp8 (e4m3) KV cache: quantized paged pools behind the unchanged
engine/offload seams.

The serving-time ``EngineConfig.kv_cache_dtype="f8_e4m3"`` halves KV HBM
traffic and pool capacity — the decode-bandwidth lever identified by the
round-5 on-chip sweeps (b32/ctx2048 decode is attention-bandwidth bound,
ROADMAP S1). e4m3's per-element exponent means no scale arrays:
``scatter_kv_pages`` casts on write, the attention backends upcast on
read, and the offload plane moves 1-byte elements under a
dtype-fingerprinted store directory (reference analog: the fingerprint
discipline of ``llmd_fs_backend/file_mapper.py`` — any field that changes
the bytes changes the directory).

Quantization error is bounded (2^-3 relative per element), so these tests
pin closeness and internal consistency, not bit-parity with bf16: the
fp8 engine must agree with ITSELF across serve paths (kernel vs XLA
attention, restore vs recompute) bit-exactly, while the bf16 comparison is a
bounded-error check.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_kv_cache,
    init_params,
)


def fp8_engine(tmp_path=None, offload_spec=None, seed=0, **kw):
    cfg = EngineConfig(num_pages=64, max_pages_per_seq=16,
                       kv_cache_dtype="f8_e4m3", model_name="tiny-fp8",
                       pod_identifier="pod-q", **kw)
    return MiniEngine(cfg, offload_spec=offload_spec, seed=seed)


class TestForwardQuality:
    def test_logits_close_to_bf16_cache(self):
        """One prefill step over an fp8 pool vs a bf16 pool: same params,
        same tokens — logits must stay within the quantization budget
        (attention output error ~ fp8 relative step times value scale)."""
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(5)
        batch, seq = 2, 16
        tokens = jnp.asarray(
            rng.integers(1, cfg.vocab_size - 1, (batch, seq)), jnp.int32)
        table = jnp.asarray(
            rng.permutation(16)[: batch * 4].reshape(batch, 4), jnp.int32)
        ctx = jnp.zeros((batch,), jnp.int32)
        new = jnp.full((batch,), seq, jnp.int32)

        outs = {}
        for name, dtype in (("bf16", None), ("fp8", jnp.float8_e4m3fn)):
            k, v = init_kv_cache(cfg, 16, dtype=dtype)
            logits, _, _ = forward(params, cfg, tokens, k, v, table, ctx, new)
            outs[name] = np.asarray(logits, np.float32)
        err = np.max(np.abs(outs["fp8"] - outs["bf16"]))
        spread = np.max(np.abs(outs["bf16"]))
        # Quantization error must be small relative to the logit scale —
        # loose enough to be seed-robust, tight enough that a broken
        # upcast (garbage bytes) cannot pass.
        assert err < 0.25 * spread, (err, spread)
        # And the distributions must actually correlate head-on.
        a, b = outs["fp8"].ravel(), outs["bf16"].ravel()
        cos = float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b)))
        assert cos > 0.999, cos

    def test_cache_dtype_is_fp8(self):
        eng = fp8_engine()
        assert eng.k_cache.dtype == jnp.float8_e4m3fn
        assert eng.v_cache.dtype == jnp.float8_e4m3fn


class TestServeConsistency:
    def test_prefix_cache_hit_reuses_fp8_pages(self):
        eng = fp8_engine()
        prompt = list(range(30, 62))  # 2 pages worth
        first = eng.generate("r1", prompt, max_new_tokens=4)
        req = eng.add_request("r2", prompt, max_new_tokens=4)
        assert req.cached_len > 0  # prefix served from the fp8 pool
        while not req.done:
            eng.step()
        assert list(req.output) == first

    def test_qwen_bias_family_fp8_serves(self):
        """QKV-bias + qk-norm family (Qwen lineage) over an fp8 pool:
        the kernel's quantized arm and XLA attention read the same
        bytes, so their tokens stay bit-equal — the family's extra
        projection terms change nothing about where quantization
        happens (the scatter's cast on write)."""
        cfg = LlamaConfig.qwen3_tiny()
        prompt = np.random.default_rng(11).integers(
            1, cfg.vocab_size - 1, 48).tolist()
        outs = []
        for pallas in (False, True):
            eng = MiniEngine(EngineConfig(
                model=cfg, num_pages=64, max_pages_per_seq=16,
                kv_cache_dtype="f8_e4m3", model_name="qwen-fp8",
                pod_identifier="p", use_pallas_decode=pallas), seed=0)
            outs.append(eng.generate("r0", prompt, max_new_tokens=10))
        assert len(outs[0]) == 10 and outs[0] == outs[1], outs

    def test_hybrid_fp8_serves(self):
        cfg = LlamaConfig.sink_tiny()
        eng = MiniEngine(EngineConfig(
            model=cfg, num_pages=64, num_swa_pages=64, max_pages_per_seq=24,
            kv_cache_dtype="f8_e4m3", model_name="hyb-fp8",
            pod_identifier="pod-q"), seed=0)
        prompt = np.random.default_rng(0).integers(1, 250, 64).tolist()
        out = eng.generate("r0", prompt, max_new_tokens=8)
        assert len(out) == 8
        assert eng.k_swa is None or eng.k_swa.dtype == jnp.float8_e4m3fn


class TestQuantKernelArm:
    def test_pallas_decode_matches_xla_on_fp8_cache(self):
        """The merged kernel's quant arm (flat whole-page 1-byte DMAs +
        in-VMEM upcast) must reproduce the XLA reference over the SAME
        fp8 cache — the quantization already happened at write, so the
        two backends read identical bytes and must agree to float
        tolerance."""
        from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
        from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
            pallas_paged_decode_attention)

        rng = np.random.default_rng(0)
        b, qh, kvh, hd, ps, npg, pps = 4, 8, 4, 128, 16, 64, 8
        q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(npg, kvh, ps, hd)),
                        jnp.float8_e4m3fn)
        v = jnp.asarray(rng.normal(size=(npg, kvh, ps, hd)),
                        jnp.float8_e4m3fn)
        table = jnp.asarray(1 + np.arange(b * pps).reshape(b, pps) % (npg - 1),
                            jnp.int32)
        lens = jnp.asarray([120, 64, 37, 16], jnp.int32)
        out = pallas_paged_decode_attention(q, k, v, table, lens,
                                            interpret=True)
        ref = paged_attention(q[:, None], k, v, table, (lens - 1)[:, None],
                              lens)[:, 0]
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 0.1, err

    def test_quant_arm_multi_row(self):
        from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
        from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
            pallas_paged_decode_attention)

        rng = np.random.default_rng(1)
        b, qh, kvh, hd, ps, npg, pps = 4, 8, 4, 128, 16, 64, 8
        q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(npg, kvh, ps, hd)),
                        jnp.float8_e4m3fn)
        v = jnp.asarray(rng.normal(size=(npg, kvh, ps, hd)),
                        jnp.float8_e4m3fn)
        table = jnp.asarray(1 + np.arange(b * pps).reshape(b, pps) % (npg - 1),
                            jnp.int32)
        lens = jnp.asarray([128, 99, 64, 3], jnp.int32)
        out = pallas_paged_decode_attention(q, k, v, table, lens,
                                            batch_rows=2, interpret=True)
        ref = paged_attention(q[:, None], k, v, table, (lens - 1)[:, None],
                              lens)[:, 0]
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 0.1, err

    def test_mla_fp8_kernel_refused(self):
        from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
            pallas_paged_decode_attention)

        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.bfloat16)
        lat = jnp.asarray(rng.normal(size=(16, 1, 16, 128)),
                          jnp.float8_e4m3fn)
        table = jnp.asarray(np.ones((2, 4)), jnp.int32)
        lens = jnp.asarray([16, 16], jnp.int32)
        with pytest.raises(ValueError, match="shared-kv"):
            pallas_paged_decode_attention(q, lat, lat, table, lens,
                                          shared_kv=True, interpret=True)

    def test_windowed_engine_pallas_fp8_matches_xla_fp8(self):
        """A window + sinks model over fp8 pages: the kernel's quant arm
        (window page skipping, sink pages, in-VMEM upcast) and the XLA
        backend over the same bytes must emit identical tokens. Pages of
        16 tokens: ``kv_heads * page_size`` has to be a multiple of 32
        for 1-byte pages to ride the kernel at all."""
        cfg = dataclasses.replace(LlamaConfig.sink_tiny(), page_size=16)
        prompt = np.random.default_rng(5).integers(1, 250, 64).tolist()
        outs = {}
        for pallas in (False, True):
            eng = MiniEngine(EngineConfig(
                model=cfg, num_pages=64, num_swa_pages=64,
                max_pages_per_seq=24, kv_cache_dtype="f8_e4m3",
                model_name="hyb-fp8", pod_identifier="p",
                use_pallas_decode=pallas), seed=0)
            assert eng.attention_backends["decode"]["backend"] == (
                "pallas" if pallas else "xla")
            outs[pallas] = eng.generate("r0", prompt, max_new_tokens=8)
        assert outs[False] == outs[True], outs

    def test_engine_pallas_fp8_matches_xla_fp8(self):
        """End-to-end: fp8 engine on the interpret-mode Pallas decode
        backend vs the fp8 XLA backend — identical cache bytes, token
        output must match (same invariant the bf16 engines pin)."""
        prompt = np.random.default_rng(9).integers(1, 250, 48).tolist()
        outs = {}
        for pallas in (False, True):
            eng = MiniEngine(EngineConfig(
                num_pages=64, max_pages_per_seq=16,
                kv_cache_dtype="f8_e4m3", model_name="t",
                pod_identifier="p", use_pallas_decode=pallas), seed=0)
            outs[pallas] = eng.generate("r0", prompt, max_new_tokens=8)
        assert outs[False] == outs[True], outs


class TestGates:
    def test_bad_dtype_string_refused(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            MiniEngine(EngineConfig(num_pages=16, max_pages_per_seq=4,
                                    kv_cache_dtype="int8"))

    def test_mla_refused(self):
        cfg = LlamaConfig.deepseek_tiny()
        with pytest.raises(ValueError, match="MLA"):
            MiniEngine(EngineConfig(model=cfg, num_pages=16,
                                    max_pages_per_seq=4,
                                    kv_cache_dtype="f8_e4m3"))

    def test_spec_dtype_mismatch_refused(self, tmp_path):
        from llmd_kv_cache_tpu.offload import SharedStorageOffloadSpec

        tiny = LlamaConfig.tiny()
        spec = SharedStorageOffloadSpec(
            root=str(tmp_path), model_name="tiny", page_size=tiny.page_size,
            num_layers=tiny.num_layers, kv_heads=tiny.num_kv_heads,
            head_dim=tiny.head_dim, io_threads=2, parallel_agnostic=True,
        )  # dtype left at the bf16 default
        with pytest.raises(ValueError, match="dtype"):
            fp8_engine(offload_spec=spec)


class TestMeshComposition:
    """fp8 pools under mesh-sharded serving: the cast is elementwise and
    the pools shard exactly like bf16 (kv-heads under tp, layers under
    pp), so every mesh mode must serve token-identically to the
    single-device fp8 engine."""

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

    def _gen(self, mesh=None, cfg=None, seed_params=None, **kw):
        if cfg is not None:
            kw["model"] = cfg
        e = MiniEngine(EngineConfig(num_pages=64,
                                    max_pages_per_seq=16,
                                    kv_cache_dtype="f8_e4m3",
                                    model_name="fp8-mesh",
                                    pod_identifier="p", **kw),
                       params=seed_params, mesh=mesh, seed=0)
        prompt = np.random.default_rng(0).integers(1, 250, 24).tolist()
        return e, e.generate("r", prompt, max_new_tokens=8)

    _ref_tokens = None

    def _ref(self):
        # One single-device fp8 reference run shared by the mesh tests
        # (deterministic: fixed seeds, same default config).
        if TestMeshComposition._ref_tokens is None:
            TestMeshComposition._ref_tokens = self._gen()[1]
        return TestMeshComposition._ref_tokens

    def test_tp_matches_single_device(self):
        ref = self._ref()
        tp_eng, out = self._gen(mesh=self._mesh({"tp": 2}))
        assert out == ref
        # The pool really is fp8 AND really sharded (a silently
        # replicated pool would still match tokens).
        assert tp_eng.k_cache.dtype == jnp.float8_e4m3fn
        kvh = tp_eng.k_cache.shape[2]
        assert tp_eng.k_cache.sharding.shard_shape(
            tp_eng.k_cache.shape)[2] == kvh // 2

    def test_tp_with_dp_axis(self):
        ref = self._ref()
        _, dptp = self._gen(mesh=self._mesh({"dp": 4, "tp": 2}))
        assert dptp == ref

    def test_pp_and_sp_meshes(self):
        ref = self._ref()
        _, pp = self._gen(mesh=self._mesh({"pp": 2}))
        assert pp == ref
        _, sp = self._gen(mesh=self._mesh({"sp": 2}))
        assert sp == ref

    def test_hybrid_tp(self):
        from llmd_kv_cache_tpu.models.llama import init_params

        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2, head_dim=16,
                          intermediate_size=128, page_size=4,
                          sliding_window=8, swa_layers=(1,))
        params = init_params(jax.random.PRNGKey(3), cfg)
        _, ref = self._gen(cfg=cfg, seed_params=params)
        _, out = self._gen(mesh=self._mesh({"tp": 2}), cfg=cfg,
                           seed_params=params)
        assert out == ref

    def test_tp_quant_kernel_arm(self):
        """The quantized flash-decode arm under tp shard_map: shapes
        chosen so the PER-SHARD cache qualifies (kv_heads=4/tp=2 → local
        2, 2*16=32 % 32 == 0, head_dim 128) — the engine gate must judge
        the local shape (a global-shape gate would admit configs whose
        shards then raise inside the kernel), and the interpret-mode
        kernel must reproduce the XLA tokens over the same fp8 bytes."""
        from llmd_kv_cache_tpu.models.llama import (init_params,
                                                    step_decode_pallas)

        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=4, head_dim=128,
                          intermediate_size=128, page_size=16)
        params = init_params(jax.random.PRNGKey(3), cfg)
        mesh = self._mesh({"tp": 2})
        outs = {}
        for pallas in (False, True):
            e, outs[pallas] = self._gen(mesh=mesh, cfg=cfg,
                                        seed_params=params,
                                        use_pallas_decode=pallas)
            if pallas:
                fwd = getattr(e._decode_forward, "func", e._decode_forward)
                assert fwd is step_decode_pallas, \
                    "quant kernel arm did not engage under tp"
        assert outs[True] == outs[False]

        # kv_heads=4 / tp=4 → local kv_heads=1: the merged-heads quant
        # arm is unavailable per shard, so the engine must FALL BACK to
        # XLA (not crash in the kernel's per-shard validation).
        e, out = self._gen(mesh=self._mesh({"tp": 4}), cfg=cfg,
                           seed_params=params, use_pallas_decode=True)
        fwd = getattr(e._decode_forward, "func", e._decode_forward)
        assert fwd is not step_decode_pallas
        assert out == outs[False]


class TestOffload:
    def _spec(self, tmp_path):
        from llmd_kv_cache_tpu.offload import SharedStorageOffloadSpec

        tiny = LlamaConfig.tiny()
        return SharedStorageOffloadSpec(
            root=str(tmp_path), model_name="tiny", page_size=tiny.page_size,
            num_layers=tiny.num_layers, kv_heads=tiny.num_kv_heads,
            head_dim=tiny.head_dim, dtype="float8_e4m3fn", io_threads=2,
            parallel_agnostic=True,
        )

    def test_fp8_store_restore_bit_exact(self, tmp_path):
        prompt = list(range(70, 102))  # 2 pages
        a = fp8_engine(offload_spec=self._spec(tmp_path))
        out_a = a.generate("r1", prompt, max_new_tokens=4)
        a.flush_offload()

        b = MiniEngine(EngineConfig(
            num_pages=64, max_pages_per_seq=16, kv_cache_dtype="f8_e4m3",
            model_name="tiny-fp8", pod_identifier="pod-b"),
            offload_spec=self._spec(tmp_path), seed=0)
        req = b.add_request("r2", prompt, max_new_tokens=4)
        assert req.cached_len == len(prompt)
        while not req.done:
            b.step()
        # fp8 bytes restored into an fp8 pool are the SAME bytes → the
        # resumed decode is bit-exact vs the engine that wrote them.
        assert list(req.output) == out_a

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs the 8-device virtual CPU mesh "
                               "(tests/conftest.py)")
    def test_fp8_store_restore_through_tp_engine(self, tmp_path):
        """Write-through from a tp-sharded fp8 engine, restore into a
        FRESH tp-sharded fp8 engine: the copier's gather reads the
        kv-head-sharded 1-byte pool and the restore scatter must land the
        same bytes back under the same sharding — resumed decode
        bit-exact, pool still fp8 and still sharded."""
        from llmd_kv_cache_tpu.parallel.mesh import make_mesh

        prompt = list(range(70, 102))  # 2 pages

        def build(pod):
            return MiniEngine(EngineConfig(
                num_pages=64, max_pages_per_seq=16,
                kv_cache_dtype="f8_e4m3", model_name="tiny-fp8",
                pod_identifier=pod),
                offload_spec=self._spec(tmp_path), seed=0,
                mesh=make_mesh({"tp": 2}, jax.devices()[:2]))

        a = build("pod-a")
        out_a = a.generate("r1", prompt, max_new_tokens=4)
        a.flush_offload()

        b = build("pod-b")
        req = b.add_request("r2", prompt, max_new_tokens=4)
        assert req.cached_len == len(prompt)  # restored, not recomputed
        while not req.done:
            b.step()
        assert list(req.output) == out_a
        assert b.k_cache.dtype == jnp.float8_e4m3fn
        kvh = b.k_cache.shape[2]
        assert b.k_cache.sharding.shard_shape(
            b.k_cache.shape)[2] == kvh // 2

    def test_fingerprint_separates_fp8_from_bf16(self):
        from llmd_kv_cache_tpu.offload.file_mapper import (
            FileMapper, FileMapperConfig)

        base = dict(root="/tmp/x", model_name="m")
        bf = FileMapper(FileMapperConfig(**base, dtype="bfloat16"))
        f8 = FileMapper(FileMapperConfig(**base, dtype="float8_e4m3fn"))
        assert bf.fingerprint != f8.fingerprint
