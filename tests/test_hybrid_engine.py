"""Hybrid (mixed full/SWA) engine: two cache groups with separate page
pools, group-tagged events, out-of-window reclamation, and the HybridAware
scoring loop fed by a real producer — through ZMQ, with engine block size
different from the indexer's canonical size (many:1 realignment,
reference ``pool.go:227-260`` + ``hma.go:32-66``).
"""

import time

import numpy as np
import pytest

from llmd_kv_cache_tpu.core import GroupCatalog
from llmd_kv_cache_tpu.core.hma import SPEC_FULL_ATTENTION, SPEC_SLIDING_WINDOW
from llmd_kv_cache_tpu.events.model import BlockRemovedEvent, BlockStoredEvent
from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig
from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig
from llmd_kv_cache_tpu.scoring.scorer import KVBlockScorerConfig

PAGE = 4
WINDOW = 8  # 2 pages


def hybrid_cfg(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=PAGE,
        sliding_window=WINDOW, swa_layers=(1,),
    )
    base.update(kw)
    return LlamaConfig(**base)


def make_engine(events=None, num_pages=64, num_swa_pages=None, cfg=None):
    def sink_batch(evs):
        events.extend(evs)

    return MiniEngine(
        EngineConfig(
            model=cfg or hybrid_cfg(),
            num_pages=num_pages,
            num_swa_pages=num_swa_pages,
            max_pages_per_seq=16,
            model_name="tiny-hybrid",
            pod_identifier="pod-h",
            # The shape-aware auto leaves tiny models unfused, which
            # most suites now exercise; this suite pins the FUSED
            # serving layout through the hybrid paging paths so the
            # production hidden>=4096 default keeps integration
            # coverage (r5 review).
            fuse_projections=True,
        ),
        event_sink=sink_batch if events is not None else None,
    )


class TestHybridConfig:
    def test_is_hybrid_detection(self):
        assert hybrid_cfg().is_hybrid
        assert not LlamaConfig.tiny().is_hybrid
        # all-SWA is single-group, not hybrid
        assert not hybrid_cfg(swa_layers=(0, 1)).is_hybrid

    def test_group_layers(self):
        cfg = hybrid_cfg()
        assert cfg.group_layers(0) == (0,)
        assert cfg.group_layers(1) == (1,)
        assert cfg.layer_group(0) == 0
        assert cfg.layer_group(1) == 1


class TestHybridEquivalence:
    def test_hybrid_matches_unified_pool_outputs(self):
        """The two-pool hybrid path must produce the same tokens as the
        same model run through the unified single-pool path (which handles
        per-layer windows in attention but shares one page pool)."""
        cfg = hybrid_cfg()
        prompt = list(np.random.default_rng(0).integers(1, 250, 21))
        hybrid = make_engine(cfg=cfg)
        assert hybrid.hybrid
        out_h = hybrid.generate("r", prompt, max_new_tokens=8)

        # Unified-pool baseline: same weights (same seed), same per-layer
        # windows, one pool — forced by building a non-hybrid engine on a
        # model whose layer_window matches but is_hybrid is False. We get
        # that by running the hybrid config through the single-pool path:
        # construct engine with swa_layers=() then manually compare is not
        # equivalent; instead run forward directly via the unified engine
        # over all layers with windows — covered by the model-level check
        # below. Here: determinism of the hybrid path itself.
        hybrid2 = make_engine(cfg=cfg)
        assert hybrid2.generate("r", prompt, max_new_tokens=8) == out_h

    def test_hybrid_forward_matches_unified_forward(self):
        """Model-level: forward_hybrid over split pools == forward over a
        unified pool, same weights and windows."""
        import jax
        import jax.numpy as jnp

        from llmd_kv_cache_tpu.models.llama import (
            forward, forward_hybrid, init_kv_cache, init_kv_cache_hybrid,
            init_params,
        )

        cfg = hybrid_cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(1, 250, (1, 12)), jnp.int32)
        ctx = jnp.zeros((1,), jnp.int32)
        new = jnp.full((1,), 12, jnp.int32)

        k, v = init_kv_cache(cfg, 16)
        table = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)
        logits_u, _, _ = forward(params, cfg, tokens, k, v, table, ctx, new)

        k0, v0, k1, v1 = init_kv_cache_hybrid(cfg, 16, 16)
        t0 = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)
        t1 = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)
        logits_h, *_ = forward_hybrid(
            params, cfg, tokens, k0, v0, k1, v1, t0, t1, ctx, new)
        np.testing.assert_allclose(
            np.asarray(logits_u), np.asarray(logits_h), rtol=2e-2, atol=2e-2)

    def test_prefix_reuse_across_requests(self):
        eng = make_engine()
        prompt = list(range(1, 17))  # 4 full pages
        eng.generate("a", prompt, max_new_tokens=2)
        req = eng.add_request("b", prompt + [99, 98], max_new_tokens=2)
        # After a's finish, group 1 dropped its out-of-window blocks but
        # kept the trailing window; group 0 kept everything. Trailing-
        # window acquisition therefore still yields the FULL prefix hit:
        # resume at 16 needs group 0's chain [0,4) plus group 1's last 2
        # blocks only.
        assert req.cached_len == 16
        # pre-window SWA slots are garbage-mapped, in-window ones real
        assert req.swa_acquired_from == 2
        assert req.swa_pages[:2] == [0, 0] and all(req.swa_pages[2:4])


class TestGroupEvents:
    def test_stored_events_carry_group_specs(self):
        events = []
        eng = make_engine(events)
        eng.generate("a", list(range(1, 17)), max_new_tokens=2)
        stored = [e for e in events if isinstance(e, BlockStoredEvent)]
        by_group = {}
        for e in stored:
            by_group.setdefault(e.group_idx, []).append(e)
        assert set(by_group) == {0, 1}
        assert all(e.kv_cache_spec_kind == SPEC_FULL_ATTENTION
                   for e in by_group[0])
        assert all(e.kv_cache_spec_kind == SPEC_SLIDING_WINDOW
                   and e.kv_cache_spec_sliding_window == WINDOW
                   for e in by_group[1])
        # Group 1 stores only the in-window trailing suffix of the chain:
        # out-of-window blocks are reclaimed pre-commit and never
        # advertised (prompt 16 tokens, window 8 → last 2 of 4 blocks).
        g0 = [h for e in by_group[0] for h in e.block_hashes]
        g1 = [h for e in by_group[1] for h in e.block_hashes]
        assert len(g0) == 4 and g1 == g0[2:]

    def test_prompt_tail_swa_window_survives_decode(self):
        """Decode sliding the live window past the prompt tail must NOT
        revoke committed SWA blocks: block i always serves a resume at
        boundary i+1 (whose trailing window covers it), so committed SWA
        blocks stay cached like full-attention blocks and only pressure
        eviction (or clear) revokes them. An earlier policy dropped them
        eagerly against the FINAL context's window, which destroyed
        exactly the blocks a prompt replay resumes from."""
        events = []
        eng = make_engine(events)
        prompt = list(range(1, 17))  # 4 blocks; window = 2 blocks
        eng.generate("a", prompt, max_new_tokens=10)  # context grows to 26
        stored1 = [h for e in events
                   if isinstance(e, BlockStoredEvent) and e.group_idx == 1
                   for h in e.block_hashes]
        assert stored1  # blocks 2,3 were in-window at commit
        assert not any(isinstance(e, BlockRemovedEvent) and e.group_idx == 1
                       for e in events)
        # And they really do serve a replay: full prompt-prefix hit,
        # token-identical continuation.
        req2 = eng.add_request("replay", prompt, max_new_tokens=1)
        assert req2.cached_len == len(prompt)
        # Deeper prompts resume straight through them too.
        req3 = eng.add_request("deeper", prompt + list(range(101, 109)),
                               max_new_tokens=1)
        assert req3.cached_len >= len(prompt)
        assert not any(isinstance(e, BlockRemovedEvent) and e.group_idx == 0
                       for e in events)

    def test_swa_pool_reuse_after_drop(self):
        """Dropped SWA pages return to the pool: a small SWA pool survives
        many sequential requests."""
        eng = make_engine(num_swa_pages=20)
        for i in range(4):
            prompt = list(np.random.default_rng(i).integers(1, 250, 17))
            eng.generate(f"r{i}", prompt, max_new_tokens=2)
        assert eng.swa_manager.num_free() > 0

    def test_window_bounded_swa_pool_fits_long_prompt(self):
        """The documented memory win: with just-in-time allocation and
        mid-prefill reclamation, a prompt much longer than the SWA pool
        fits — demand is window + chunk, not prompt length."""
        eng = MiniEngine(EngineConfig(
            model=hybrid_cfg(),
            num_pages=64,
            num_swa_pages=10,        # 40-token prompt needs 10 blocks alone
            max_pages_per_seq=16,
            max_prefill_tokens=8,    # 2-page chunks
            model_name="tiny-hybrid",
            pod_identifier="pod-h",
        ))
        prompt = list(np.random.default_rng(7).integers(1, 250, 40))
        out = eng.generate("long", prompt, max_new_tokens=4)
        assert len(out) == 4
        # steady state: only in-window slots hold pages
        assert eng.swa_manager.num_free() >= 10 - 1 - (WINDOW // PAGE + 2)

    def test_window_bounded_pool_matches_unbounded_outputs(self):
        """Reclaiming out-of-window SWA pages must not change results."""
        prompt = list(np.random.default_rng(9).integers(1, 250, 33))

        def run(num_swa_pages, max_prefill):
            eng = MiniEngine(EngineConfig(
                model=hybrid_cfg(), num_pages=64,
                num_swa_pages=num_swa_pages, max_pages_per_seq=16,
                max_prefill_tokens=max_prefill,
                model_name="tiny-hybrid", pod_identifier="pod-h",
            ))
            return eng.generate("r", prompt, max_new_tokens=6)

        assert run(10, 8) == run(64, 512)


class TestHybridScoringE2E:
    def test_zmq_realigned_hybrid_scoring(self, tmp_path):
        """The full loop, from a REAL producer: hybrid engine (block size 4)
        → ZMQ publisher → subscriber → pool (canonical block size 8, many:1
        realignment) → GroupCatalog → HybridAwareScorer."""
        from llmd_kv_cache_tpu.events.publisher import KVEventPublisher
        from llmd_kv_cache_tpu.events.zmq_subscriber import ZMQSubscriber

        endpoint = "ipc://" + str(tmp_path / "events.ipc")

        indexer = Indexer(IndexerConfig.from_dict({
            "tokenProcessorConfig": {"blockSize": 8},  # canonical ≠ engine 4
            "kvBlockScorerConfig": {"scoringStrategy": "HybridAware"},
        }))
        pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                    indexer.token_processor)
        indexer.attach_group_catalog(pool.group_catalog)
        pool.start()
        sub = ZMQSubscriber(endpoint, "kv@", pool.add_task, bind=True)
        sub.start()
        time.sleep(0.2)

        publisher = KVEventPublisher(endpoint, "pod-h", "tiny-hybrid",
                                     bind=False)
        eng = make_engine()
        eng_events = []
        eng.block_manager.event_sink = lambda evs: (
            eng_events.extend(evs), publisher.publish(evs))
        eng.swa_manager.event_sink = eng.block_manager.event_sink

        try:
            prompt = list(range(1, 33))  # 8 engine blocks = 4 canonical
            eng.generate("warm", prompt, max_new_tokens=2)

            # republish-until-observed: PUB/SUB joins are slow, and the
            # two groups' events are separate messages (a score can show
            # before the SWA group's batch was ingested).
            deadline = time.monotonic() + 10
            scores = {}
            while time.monotonic() < deadline:
                scores = indexer.score_tokens(prompt, "tiny-hybrid")
                if scores and pool.group_catalog.get("pod-h", 1) is not None:
                    break
                publisher.publish(
                    [e for e in eng_events if isinstance(e, BlockStoredEvent)])
                time.sleep(0.1)
            assert "pod-h" in scores, "hybrid pod never scored"

            # The catalog learned both groups from the wire.
            cat = pool.group_catalog
            g0 = cat.get("pod-h", 0)
            g1 = cat.get("pod-h", 1)
            assert g0 is not None and g0.kind == SPEC_FULL_ATTENTION
            assert g1 is not None and g1.kind == SPEC_SLIDING_WINDOW
            assert g1.sliding_window_size == WINDOW

            # SWA cap: score is min(full-group value, window value); the
            # window (8 tokens = 1 canonical block) caps the pod's score
            # at the weight of the trailing canonical block.
            assert scores["pod-h"] <= 2.0
        finally:
            publisher.close()
            sub.stop()
            pool.shutdown()
