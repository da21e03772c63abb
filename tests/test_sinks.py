"""Attention sinks (StreamingLLM): the sink_full_attention family.

Uniform-SWA models whose first ``attention_sinks`` positions stay
attendable past the window (reference spec kind ``events.go:40``). The
mask lives in ``ops.paged_attention``; the engine advertises
``sink_full_attention`` blocks and serves the family end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.core.hma import SPEC_SINK_FULL
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig
from llmd_kv_cache_tpu.ops.kv_pages import scatter_kv_pages
from llmd_kv_cache_tpu.ops.paged_attention import paged_attention


class TestSinkMask:
    def _setup(self, s=16):
        rng = np.random.default_rng(0)
        b, h, d, page = 1, 2, 4, 4
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        k_cache = jnp.zeros((8, h, page, d), jnp.float32)
        v_cache = jnp.zeros((8, h, page, d), jnp.float32)
        table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        positions = jnp.arange(s)[None, :]
        valid = jnp.ones((1, s), bool)
        k_cache = scatter_kv_pages(k_cache, k, table, positions, valid)
        v_cache = scatter_kv_pages(v_cache, v, table, positions, valid)
        return q, k, v, k_cache, v_cache, table, positions

    def test_matches_dense_sink_mask(self):
        """Paged window+sink attention == dense attention under the
        explicit StreamingLLM mask (causal & (in-window | sink))."""
        s, window, sinks = 16, 6, 3
        q, k, v, k_cache, v_cache, table, positions = self._setup(s)
        out = paged_attention(
            q, k_cache, v_cache, table, positions,
            jnp.asarray([s], jnp.int32), sliding_window=window,
            attention_sinks=sinks)

        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q * d ** -0.5, k)
        qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = (kp <= qp) & ((qp - kp < window) | (kp < sinks))
        logits = jnp.where(mask[None, None], logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_sinks_change_output_beyond_window(self):
        """Past the window the sink mask must matter (vs plain SWA) and
        within it, it must not."""
        s, window, sinks = 16, 6, 3
        q, k, v, k_cache, v_cache, table, positions = self._setup(s)

        def run(sk):
            return np.asarray(paged_attention(
                q, k_cache, v_cache, table, positions,
                jnp.asarray([s], jnp.int32), sliding_window=window,
                attention_sinks=sk))

        plain, sunk = run(None), run(sinks)
        # queries < window see identical context either way
        np.testing.assert_allclose(sunk[:, :window], plain[:, :window],
                                   rtol=1e-6, atol=1e-6)
        assert np.abs(sunk[:, window + sinks:]
                      - plain[:, window + sinks:]).max() > 1e-4


class TestSinkConfig:
    def test_requires_window(self):
        with pytest.raises(ValueError, match="requires sliding_window"):
            LlamaConfig(attention_sinks=4)

    def test_hybrid_rejected(self):
        with pytest.raises(ValueError, match="uniform-SWA"):
            LlamaConfig(num_layers=2, sliding_window=8, swa_layers=(0,),
                        attention_sinks=4)


class TestSinkEngine:
    def _engine(self, **kw):
        return MiniEngine(
            EngineConfig(model=LlamaConfig.sink_tiny(), num_pages=64,
                         max_pages_per_seq=16, max_batch=4,
                         model_name="sink", pod_identifier="p", **kw),
            seed=0)

    def test_serves_beyond_window_deterministically(self):
        prompt = list(range(10, 30))  # 20 tokens >> window 8
        toks = self._engine().generate("r", prompt, max_new_tokens=16)
        assert self._engine().generate("r", prompt, max_new_tokens=16) == toks

    def test_differs_from_plain_swa(self):
        """The sink mask is live in the engine: a same-weights plain-SWA
        model diverges on long generations."""
        cfg = LlamaConfig.sink_tiny()
        plain_cfg = LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            intermediate_size=cfg.intermediate_size, page_size=cfg.page_size,
            sliding_window=cfg.sliding_window, swa_layers=cfg.swa_layers)
        prompt = list(range(10, 34))
        sunk = self._engine().generate("r", prompt, max_new_tokens=16)
        plain = MiniEngine(
            EngineConfig(model=plain_cfg, num_pages=64, max_pages_per_seq=16,
                         max_batch=4, model_name="sink", pod_identifier="p"),
            seed=0).generate("r", prompt, max_new_tokens=16)
        assert sunk != plain

    def test_offload_spec_must_declare_sinks(self, tmp_path):
        from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

        cfg = LlamaConfig.sink_tiny()
        spec = SharedStorageOffloadSpec(
            root=str(tmp_path), model_name="sink", page_size=cfg.page_size,
            num_layers=cfg.num_layers, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, sliding_window=cfg.sliding_window,
            swa_layers=tuple(cfg.swa_layers), io_threads=2,
            parallel_agnostic=True)  # attention_sinks left at 0
        with pytest.raises(ValueError, match="attention_sinks"):
            MiniEngine(
                EngineConfig(model=cfg, num_pages=64, max_pages_per_seq=16,
                             model_name="sink", pod_identifier="p"),
                seed=0, offload_spec=spec)

    def test_sink_store_fingerprint_differs(self, tmp_path):
        """Sink and sink-free stores of the same model must not share a
        directory (byte-incompatible KV past the window)."""
        from llmd_kv_cache_tpu.offload.file_mapper import (
            FileMapper, FileMapperConfig,
        )

        base = dict(root=str(tmp_path), model_name="m", sliding_window=8,
                    swa_layers=(0, 1))
        plain = FileMapper(FileMapperConfig(**base))
        sunk = FileMapper(FileMapperConfig(**base, attention_sinks=4))
        assert plain.fingerprint != sunk.fingerprint

    def test_scorer_treats_sink_pools_as_longest_prefix(self):
        """A sink pod missing block 0 must not be valued for its trailing
        window: the engine's resume is longest-prefix and the sink KV is
        gone (HybridAwareScorer sink-kind handling)."""
        from llmd_kv_cache_tpu.core import (
            GroupCatalog, GroupMetadata, PodEntry,
        )
        from llmd_kv_cache_tpu.scoring.scorer import HybridAwareScorer

        catalog = GroupCatalog()
        block = 4
        catalog.learn("sink-pod", 0,
                      GroupMetadata(SPEC_SINK_FULL, block, 8))
        catalog.learn("swa-pod", 0,
                      GroupMetadata("sliding_window", block, 8))
        scorer = HybridAwareScorer({"tpu-hbm": 1.0}, catalog,
                                   block_size_tokens=block)

        def entry(pod):
            return PodEntry(pod, "tpu-hbm", has_group=True, group_idx=0)

        keys = [11, 22, 33, 44]
        # Both pods hold only the TRAILING window (blocks 0,1 evicted).
        key_to_pods = {k: [entry("sink-pod"), entry("swa-pod")]
                       for k in keys[2:]}
        scores = scorer.score(keys, key_to_pods)
        # The plain-SWA pod's trailing window has resume value; the sink
        # pod (longest-prefix semantics, block 0 missing) scores zero.
        assert scores.get("swa-pod", 0) > 0
        assert scores.get("sink-pod", 0) == 0

    def test_events_tagged_sink_full(self):
        events = []
        eng = MiniEngine(
            EngineConfig(model=LlamaConfig.sink_tiny(), num_pages=64,
                         max_pages_per_seq=16, model_name="sink",
                         pod_identifier="p"),
            event_sink=events.extend, seed=0)
        eng.generate("r", list(range(10, 22)), max_new_tokens=2)
        stored = [e for e in events if hasattr(e, "kv_cache_spec_kind")]
        assert stored
        assert all(e.kv_cache_spec_kind == SPEC_SINK_FULL for e in stored)
        assert all(e.kv_cache_spec_sliding_window == 8 for e in stored)
