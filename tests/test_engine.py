"""Mini serving engine tests: prefix caching, events, e2e indexer loop."""

import dataclasses
import types

import numpy as np
import pytest

from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
from llmd_kv_cache_tpu.events.model import (
    AllBlocksClearedEvent,
    BlockRemovedEvent,
    BlockStoredEvent,
    EventBatch,
)
from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig
from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig


def make_engine(events=None, pod="pod-0", seed=0, num_pages=64):
    sink = events.append if events is not None else None

    def sink_batch(evs):
        events.extend(evs)

    return MiniEngine(
        EngineConfig(
            model=LlamaConfig.tiny(),
            num_pages=num_pages,
            max_pages_per_seq=16,
            model_name="tiny",
            pod_identifier=pod,
        ),
        event_sink=sink_batch if events is not None else None,
        seed=seed,
    )


PAGE = LlamaConfig.tiny().page_size  # 4


class TestPrefixCache:
    def test_second_request_hits_prefix(self):
        engine = make_engine()
        prompt = list(range(50, 66))  # 4 full blocks
        r1 = engine.add_request("r1", prompt, max_new_tokens=1)
        assert r1.cached_len == 0
        r2 = engine.add_request("r2", prompt, max_new_tokens=1)
        assert r2.cached_len == len(prompt)  # full-prefix hit
        # shares the same physical pages
        assert r2.pages[:4] == r1.pages[:4]

    def test_partial_prefix_hit(self):
        engine = make_engine()
        engine.add_request("r1", list(range(50, 62)), max_new_tokens=1)  # 3 blocks
        r2 = engine.add_request("r2", list(range(50, 58)) + [99, 98, 97, 96],
                                max_new_tokens=1)
        assert r2.cached_len == 8  # first 2 blocks shared

    def test_cache_hit_same_output(self):
        """Prefix-cached generation must produce identical tokens."""
        cold = make_engine()
        prompt = list(range(30, 46))
        out_cold = cold.generate("c", prompt, max_new_tokens=4)

        warm = make_engine()
        warm.add_request("w0", prompt, max_new_tokens=1)
        warm.step()
        req = warm.add_request("w1", prompt, max_new_tokens=4)
        assert req.cached_len > 0
        while not req.done:
            warm.step()
        assert req.output == out_cold

    def test_generation_is_deterministic(self):
        a = make_engine().generate("a", list(range(20, 36)), max_new_tokens=4)
        b = make_engine().generate("b", list(range(20, 36)), max_new_tokens=4)
        assert a == b


class TestEvents:
    def test_block_stored_emitted_with_tokens_and_parent(self):
        events = []
        engine = make_engine(events)
        prompt = list(range(50, 62))  # 3 full blocks
        req = engine.add_request("r1", prompt, max_new_tokens=1)
        stored = [e for e in events if isinstance(e, BlockStoredEvent)]
        assert len(stored) == 1
        ev = stored[0]
        assert ev.block_hashes == req.block_hashes
        assert ev.tokens == prompt
        assert ev.parent_hash == 0
        assert ev.block_size == PAGE

    def test_engine_hashes_are_canonical(self):
        """Engine block hashes == indexer request keys (1:1 dual keys)."""
        events = []
        engine = make_engine(events)
        prompt = list(range(70, 82))
        engine.add_request("r1", prompt, max_new_tokens=1)
        processor = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=PAGE))
        expected = processor.tokens_to_kv_block_keys(0, prompt, "tiny")
        stored = [e for e in events if isinstance(e, BlockStoredEvent)][0]
        assert stored.block_hashes == expected

    def test_eviction_emits_block_removed(self):
        events = []
        # page pool too small for three distinct 3-block prompts + decode room
        engine = make_engine(events, num_pages=10)
        engine.generate("r1", list(range(100, 112)), max_new_tokens=1)
        engine.generate("r2", list(range(200, 212)), max_new_tokens=1)
        engine.generate("r3", list(range(300, 312)), max_new_tokens=1)
        removed = [e for e in events if isinstance(e, BlockRemovedEvent)]
        assert removed, "LRU eviction under page pressure must emit BlockRemoved"

    def test_reset_emits_all_blocks_cleared(self):
        events = []
        engine = make_engine(events)
        engine.generate("r1", list(range(30, 42)), max_new_tokens=1)
        engine.reset_cache()
        assert any(isinstance(e, AllBlocksClearedEvent) for e in events)
        assert engine.block_manager.num_cached_blocks() == 0


class TestChunkedPrefill:
    def test_chunked_equals_single_shot(self):
        """Chunked prefill must produce identical generations."""
        prompt = list(range(100, 124))  # 24 tokens
        outs = {}
        for cap in (1024, 8):  # single-shot vs 2-page chunks
            engine = MiniEngine(
                EngineConfig(model=LlamaConfig.tiny(), num_pages=64,
                             max_pages_per_seq=16, model_name="tiny",
                             pod_identifier="p", max_prefill_tokens=cap),
                seed=0,
            )
            outs[cap] = engine.generate("r", prompt, max_new_tokens=4)
        assert outs[1024] == outs[8]

    def test_chunked_prefill_commits_blocks(self):
        events = []
        engine = MiniEngine(
            EngineConfig(model=LlamaConfig.tiny(), num_pages=64,
                         max_pages_per_seq=16, model_name="tiny",
                         pod_identifier="p", max_prefill_tokens=8),
            event_sink=events.extend,
        )
        prompt = list(range(200, 216))
        req = engine.add_request("r", prompt, max_new_tokens=1)
        stored = [e for e in events if isinstance(e, BlockStoredEvent)]
        assert stored and stored[0].tokens == prompt
        # prefix cache warm for the next identical request
        req2 = engine.add_request("r2", prompt, max_new_tokens=1)
        assert req2.cached_len == len(prompt)


class TestPageAccounting:
    def test_oversized_request_rejected(self):
        engine = make_engine()
        with pytest.raises(ValueError, match="max_pages_per_seq"):
            engine.add_request("big", list(range(1000)), max_new_tokens=1)

    def test_out_of_pages_rolls_back(self):
        engine = make_engine(num_pages=8)  # 7 usable pages
        free_before = engine.block_manager.num_free()
        # needs (12+8+3)//4+1 = 6 pages < 7 → first fits
        engine.add_request("r1", list(range(100, 112)), max_new_tokens=8)
        with pytest.raises(RuntimeError, match="out of KV pages"):
            engine.add_request("r2", list(range(200, 212)), max_new_tokens=8)
        # finish r1; its pages and prefix refs must all come back
        while engine._running:
            engine.step()
        # all blocks unreferenced → evictable; free + cached pages == pool
        cached_pages = engine.block_manager.num_cached_blocks()
        assert engine.block_manager.num_free() + cached_pages == free_before
        assert all(
            info.ref_count == 0 for info in engine.block_manager.blocks.values()
        )

    def test_page_pressure_defers_admission(self):
        # 23 usable pages hold two of these requests at once ((28+4+3)//4+1
        # = 9 pages each): an enqueue the pool cannot hold raises, leaves
        # the engine serving, and succeeds once steps have freed pages.
        engine = make_engine(num_pages=24)
        prompts = [list(range(100 * i, 100 * i + 28)) for i in range(1, 5)]
        waiting = list(enumerate(prompts))
        requests, deferred = [], 0
        while waiting or engine._running:
            while waiting:
                i, prompt = waiting[0]
                try:
                    requests.append(
                        engine.enqueue(f"r{i}", prompt, max_new_tokens=4))
                except RuntimeError:
                    assert engine._running  # or nothing would free pages
                    deferred += 1
                    break
                waiting.pop(0)
            engine.step()
        assert deferred > 0
        assert len(requests) == 4
        assert all(r.done and len(r.output) == 4 for r in requests)

    def test_reset_with_inflight_requests_frees_all_pages(self):
        engine = make_engine()
        free_before = engine.block_manager.num_free()
        engine.add_request("r1", list(range(100, 112)), max_new_tokens=8)
        engine.reset_cache()  # abort mid-flight
        assert engine.block_manager.num_free() == free_before
        assert not engine._running

    def test_abort_request_releases_pages(self):
        engine = make_engine()
        free0 = engine.block_manager.num_free()
        engine.add_request("r1", list(range(100, 112)), max_new_tokens=8)
        assert engine.abort_request("r1")
        assert not engine.abort_request("r1")  # already gone
        assert not engine._running
        # committed blocks stay cached (unreferenced); page accounting holds
        cached = engine.block_manager.num_cached_blocks()
        assert engine.block_manager.num_free() + cached == free0
        # decode after abort is a no-op, not a crash
        assert engine.step() == {}

    def test_finished_requests_are_dropped(self):
        engine = make_engine()
        engine.generate("r1", list(range(30, 42)), max_new_tokens=2)
        assert "r1" not in engine.requests

    def test_duplicate_block_commit_returns_canonical_page(self):
        """Two engines' worth of the same content on one engine: committing
        an already-resident block must adopt the resident page and free the
        duplicate, with no net page loss."""
        engine = make_engine()
        free0 = engine.block_manager.num_free()
        prompt = list(range(80, 92))
        r1 = engine.add_request("a", prompt, max_new_tokens=1)
        # capture resident pages, then force recompute by evicting nothing:
        # a second identical request takes the cached path; instead commit
        # manually with fresh pages to exercise the duplicate branch.
        bm = engine.block_manager
        dup_pages = [bm.allocate_page() for _ in range(len(r1.block_hashes))]
        tokens_per_block = [prompt[i * PAGE:(i + 1) * PAGE]
                            for i in range(len(r1.block_hashes))]
        canonical = bm.commit_blocks(r1.block_hashes, dup_pages,
                                     tokens_per_block, 0)
        assert canonical == [bm.blocks[h].page for h in r1.block_hashes]
        for p in dup_pages:
            assert p in bm.free_pages  # redundant copies freed
        bm.release(r1.block_hashes, [])  # drop the extra refs we created
        # net: no leak (free + one page per cached block == initial free)
        assert bm.num_free() + bm.num_cached_blocks() == free0


class TestEngineIndexerLoop:
    def test_events_flow_to_scores(self):
        """The full loop: engine emits events → pool ingests → indexer
        scores the pod for a prompt it has cached."""
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size_tokens=PAGE)
            ),
            index=InMemoryIndex(InMemoryIndexConfig(size=10_000)),
        )
        pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                    indexer.token_processor)

        engines = {}
        for pod in ("pod-a", "pod-b"):
            events = []
            engine = make_engine(events, pod=pod)
            engines[pod] = (engine, events)

        shared_prefix = list(range(10, 26))  # 4 blocks
        engines["pod-a"][0].generate("r1", shared_prefix + [77, 78, 79, 80],
                                     max_new_tokens=1)
        engines["pod-b"][0].generate("r2", shared_prefix, max_new_tokens=1)

        for pod, (engine, events) in engines.items():
            pool.process_event_batch(EventBatch(timestamp=0.0, events=events), pod, "tiny")

        scores = indexer.score_tokens(shared_prefix + [77, 78, 79, 80], "tiny")
        assert scores["pod-a"] == 5.0  # all 5 blocks
        assert scores["pod-b"] == 4.0  # shared prefix only

        # eviction/reset propagates
        engines["pod-b"][1].clear()
        engines["pod-b"][0].reset_cache()
        pool.process_event_batch(
            EventBatch(timestamp=1.0, events=engines["pod-b"][1]), "pod-b", "tiny"
        )
        scores = indexer.score_tokens(shared_prefix, "tiny")
        assert "pod-b" not in scores


class TestContinuousBatching:
    """enqueue(): admission now, prefill chunk-at-a-time inside step()
    interleaved with decode (vLLM chunked-prefill scheduling)."""

    def _cfg(self, **kw):
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.models.engine import EngineConfig

        return EngineConfig(
            model=LlamaConfig.tiny(), num_pages=128, max_pages_per_seq=32,
            model_name="cb", pod_identifier="p", **kw)

    def test_enqueue_matches_add_request(self):
        from llmd_kv_cache_tpu.models.engine import MiniEngine

        prompt = list(range(1, 40))
        ref_eng = MiniEngine(self._cfg(), seed=3)
        ref = ref_eng.generate("r", prompt, max_new_tokens=6)

        eng = MiniEngine(self._cfg(max_prefill_tokens=16), seed=3)
        req = eng.enqueue("r", prompt, max_new_tokens=6)
        assert req.prefill_pos is not None and not req.output
        while not req.done:
            eng.step()
        assert req.output == ref

    def test_admission_delay_metric_observed(self):
        """enqueue()-to-first-schedule wait feeds the admission-delay
        histogram, once a request: what the CoDel shedder and an
        operator read the queue's wait from."""
        from llmd_kv_cache_tpu.metrics.collector import ENGINE_ADMISSION_DELAY
        from llmd_kv_cache_tpu.models.engine import MiniEngine

        def hist_count():
            return next(
                s.value for s in ENGINE_ADMISSION_DELAY.collect()[0].samples
                if s.name.endswith("_count"))

        before = hist_count()
        eng = MiniEngine(self._cfg(), seed=0)
        req = eng.enqueue("r", list(range(1, 9)), max_new_tokens=4)
        assert hist_count() == before  # not yet scheduled
        eng.step()  # first schedule observes the delay
        assert hist_count() == before + 1
        while not req.done:
            eng.step()
        assert hist_count() == before + 1  # observed exactly once

    def test_prefill_interleaves_with_decode(self):
        from llmd_kv_cache_tpu.models.engine import MiniEngine

        # Small chunks force the long prompt through several steps.
        eng = MiniEngine(self._cfg(max_prefill_tokens=8), seed=1)
        short = eng.add_request("short", list(range(1, 9)),
                                max_new_tokens=12)
        long_req = eng.enqueue("long", list(range(1, 81)), max_new_tokens=2)

        decoded_while_prefilling = 0
        while long_req.prefill_pos is not None:
            before = len(short.output)
            eng.step()
            decoded_while_prefilling += len(short.output) - before
        # The short request kept decoding during the long prefill.
        assert decoded_while_prefilling >= 3
        while not (short.done and long_req.done):
            eng.step()
        assert len(short.output) == 12 and len(long_req.output) == 2

    def test_enqueue_prefix_hit_and_events(self):
        """Deferred prefill still registers blocks + emits BlockStored, so
        a second enqueue of the same prompt gets the prefix hit."""
        from llmd_kv_cache_tpu.models.engine import MiniEngine

        events = []
        eng = MiniEngine(self._cfg(), event_sink=events.extend, seed=0)
        prompt = list(range(1, 33))
        r1 = eng.enqueue("a", prompt, max_new_tokens=2)
        while not r1.done:
            eng.step()
        assert any(type(e).__name__ == "BlockStoredEvent" for e in events)
        r2 = eng.enqueue("b", prompt, max_new_tokens=2)
        assert r2.cached_len >= 32 - eng.cfg.model.page_size
        while not r2.done:
            eng.step()
        assert r2.output == r1.output

    def test_enqueue_hybrid(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig

        cfg = EngineConfig(
            model=LlamaConfig(
                vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, intermediate_size=128,
                page_size=4, sliding_window=8, swa_layers=(1,),
            ),
            num_pages=64, max_pages_per_seq=32, model_name="cb",
            pod_identifier="p", max_prefill_tokens=8,
        )
        prompt = list(range(1, 41))
        ref = MiniEngine(cfg, seed=2).generate("r", prompt, max_new_tokens=4)
        eng = MiniEngine(cfg, seed=2)
        req = eng.enqueue("r", prompt, max_new_tokens=4)
        while not req.done:
            eng.step()
        assert req.output == ref

    def test_abort_mid_prefill_frees_pages(self):
        """Aborting an enqueue()d request before its prefill completes must
        return every page to the pool (its blocks were never committed, so
        release-by-hash would silently leak them)."""
        from llmd_kv_cache_tpu.models.engine import MiniEngine

        eng = MiniEngine(self._cfg(max_prefill_tokens=8), seed=0)
        free0 = eng.block_manager.num_free()
        for i in range(3):
            req = eng.enqueue(f"r{i}", list(range(1, 41)), max_new_tokens=4)
            eng.step()  # one chunk only
            assert req.prefill_pos is not None
            assert eng.abort_request(f"r{i}")
            assert eng.block_manager.num_free() == free0, f"leak on abort {i}"

    def test_abort_mid_prefill_hybrid_frees_pages(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig

        cfg = EngineConfig(
            model=LlamaConfig(
                vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, intermediate_size=128,
                page_size=4, sliding_window=8, swa_layers=(1,),
            ),
            num_pages=64, max_pages_per_seq=32, model_name="cb",
            pod_identifier="p", max_prefill_tokens=8,
        )
        eng = MiniEngine(cfg, seed=0)
        free0 = eng.block_manager.num_free()
        swa_free0 = eng.swa_manager.num_free()
        req = eng.enqueue("r", list(range(1, 41)), max_new_tokens=4)
        eng.step()
        assert req.prefill_pos is not None
        assert eng.abort_request("r")
        assert eng.block_manager.num_free() == free0
        assert eng.swa_manager.num_free() == swa_free0


class TestLongContext:
    """Long-context serving: chunked prefill + paged attention handle
    prompts far beyond one chunk; SWA keeps the live working set
    window-bounded (the serving-side long-context story; training-side
    ring attention is tests/test_ring_attention.py)."""

    def test_4k_prompt_chunked_prefill(self):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()  # page_size 4
        eng = MiniEngine(EngineConfig(
            model=cfg, num_pages=1100, max_pages_per_seq=1040,
            model_name="long", pod_identifier="p", max_prefill_tokens=512,
        ), seed=0)
        prompt = np.random.default_rng(0).integers(1, 250, 4096).tolist()
        req = eng.add_request("r", prompt, max_new_tokens=2)
        assert req.computed_len == 4096
        while not req.done:
            eng.step()
        assert len(req.output) == 2
        # The whole prompt is now prefix cache: replay is a full hit.
        req2 = eng.add_request("r2", prompt, max_new_tokens=1)
        assert req2.cached_len == 4096
        assert req2.output == req.output[:1]

    def test_4k_prompt_hybrid_swa_bounded_pool(self):
        """A hybrid model's SWA group prefills a 4k prompt through an SWA
        pool that could never hold it (window + chunk demand, not prompt
        length); the full-attention group keeps the whole context."""
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128, page_size=4,
            sliding_window=32, swa_layers=(0,),  # hybrid: layer 1 full
        )
        eng = MiniEngine(EngineConfig(
            model=cfg, num_pages=1100, num_swa_pages=80,  # << 1024 blocks
            max_pages_per_seq=1040, model_name="swa-long",
            pod_identifier="p", max_prefill_tokens=64,
        ), seed=0)
        prompt = np.random.default_rng(1).integers(1, 250, 4096).tolist()
        out = eng.generate("r", prompt, max_new_tokens=2)
        assert len(out) == 2


class TestUnpipelinedDecodePadding:
    """max_batch % pp != 0 runs decode unpipelined (M=1) — that schedule
    accepts any batch size, so dead-row padding to max_batch only burns
    per-stage FLOPs. Decode must pad to the power-of-two bucket instead."""

    def _pp_engine(self, max_batch):
        import jax
        from jax.sharding import Mesh

        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetryConfig,
        )

        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=4,
                          num_heads=4, num_kv_heads=2, head_dim=16,
                          intermediate_size=128, page_size=4)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
        return MiniEngine(EngineConfig(
            model=cfg, num_pages=128, max_pages_per_seq=16,
            max_batch=max_batch, model_name="t", pod_identifier="pp-pad",
            telemetry=EngineTelemetryConfig()), seed=0, mesh=mesh)

    def test_unpipelined_decode_pads_to_bucket_not_max_batch(self):
        eng = self._pp_engine(max_batch=3)
        assert eng._pp == 2 and eng._pp_decode_mb == 1
        prompts = [list(range(10, 22)), list(range(30, 38))]
        reqs = [eng.add_request(f"r{i}", p, max_new_tokens=2 + 2 * i)
                for i, p in enumerate(prompts)]
        dispatches = []
        orig = eng.telemetry.on_dispatch_tokens
        eng.telemetry.on_dispatch_tokens = (
            lambda real, padded: (dispatches.append((real, padded)),
                                  orig(real, padded)))
        eng.step()  # both requests decode: one chunk of 2 rows
        assert dispatches == [(2, 2)], (
            f"2 active rows must dispatch a 2-row bucket, got {dispatches}")
        # One request finishes; the lone survivor must ride a 1-row
        # dispatch, not a max_batch=3 pad.
        while not reqs[0].done:
            eng.step()
        dispatches.clear()
        eng.step()
        assert dispatches == [(1, 1)], dispatches

    def test_pipelined_decode_keeps_fixed_shape(self):
        """max_batch % pp == 0: the microbatch split requires the fixed
        max_batch shape — padding stays at max_batch by design."""
        eng = self._pp_engine(max_batch=4)
        assert eng._pp_decode_mb == 2
        eng.add_request("r0", list(range(10, 22)), max_new_tokens=2)
        dispatches = []
        orig = eng.telemetry.on_dispatch_tokens
        eng.telemetry.on_dispatch_tokens = (
            lambda real, padded: (dispatches.append((real, padded)),
                                  orig(real, padded)))
        eng.step()
        assert dispatches == [(1, 4)], dispatches


# -- a lone engine launches its next decode step ahead ------------------------


def _served_model(kind):
    """``(cfg, params, engine options)`` of a toy model of each kind that
    steps through the padded path: dense (XLA and the Pallas kernels), a
    routed one whose programs count on the device behind their tokens, one
    that keeps a sequence state in a pool beside its pages, and one whose
    prediction module drafts a token that every decode step verifies; and
    one that keeps a window pool beside a global pool (``pools``: the XLA
    form; the kernel forms are held to it in ``tests/test_mellum2.py``)."""
    if kind in ("dense", "dense-pallas"):
        pallas = True if kind == "dense-pallas" else None
        return LlamaConfig.tiny(), None, dict(
            use_pallas_decode=pallas, use_pallas_prefill=pallas)
    from kvbench.harness import fleet, names

    conf = names.config_for_run(names.benchmark(), {
        "counters": "deepseek-v3.2-exp-ep16-l5",
        "state": "gigachat3.5-ep16-l5",
        "drafting": "openpangu-ultra-ep32-l5",
        "pools": "mellum2-12b-ep4"}[kind], rehearse=True)
    cfg, params = fleet.build_model(conf, 11)
    return cfg, params, {}


def _decodes(seen):
    """The attributes of the decode ``step.dispatch`` phases recorded."""
    return [attrs for name, attrs, _ in seen
            if name == "step.dispatch" and "prefill_pos" not in attrs]


def _queue_depths(seen):
    """Walk a recorded launch log (one engine's phases, in order) and hold
    it to the queue-depth invariant: of either kind of program the engine
    is never more than one ahead of the device. A fetch of launch L says
    every program the engine launched up to L has run (a device runs them
    in order), so what is in flight is what was launched since the last
    fetch's L. At the moment any chunk is launched at most one decode
    program is in flight (the one launched ahead before the request was
    admitted) and at most one chunk (the one before it, where nothing was
    read behind that); at a decode program's launch at most one other
    decode program; and a ``step()`` that launches a chunk launches no
    decode program ahead. Returns ``(chunks, the most decode programs and
    the most chunks in flight at a chunk's launch)``."""
    flying, chunk_steps, ahead_steps = [], set(), set()
    chunks = decodes_deep = chunks_deep = 0
    for name, attrs, _ in seen:
        if name == "step.fetch":
            flying = [(n, kind) for n, kind in flying if n > attrs["launch"]]
        if name != "step.dispatch":
            continue
        kind = "chunk" if "prefill_pos" in attrs else "decode"
        same = sum(1 for _, k in flying if k == kind)
        assert same <= 1, (attrs, flying)
        if kind == "chunk":
            chunks += 1
            chunk_steps.add(attrs["step"])
            chunks_deep = max(chunks_deep, same)
            decodes_deep = max(decodes_deep, len(flying) - same)
            assert len(flying) - same <= 1, (attrs, flying)
        elif attrs.get("ahead"):
            ahead_steps.add(attrs["step"])
        flying.append((attrs["launch"], kind))
    assert not chunk_steps & ahead_steps
    return chunks, decodes_deep, chunks_deep


class TestLookAhead:
    """``MiniEngine.step`` launches decode program N+1 before it reads N's
    tokens (N+1 takes them on the device) while the engine only decodes and
    has the chip to itself. Both are read from what the engine sees: no
    request of its own in prefill, and the device's launch numbers, so a
    second engine that launches in between holds it off: the synchronous
    order."""

    # (step, request, prompt tokens, new tokens): rows join and finish at
    # different steps, beside rows that decode and in the stretches between;
    # one finishes with its prefill, one a token later.
    PLAN = ((0, "a", 37, 40), (0, "b", 7, 25), (14, "c", 70, 12),
            (30, "d", 5, 1), (32, "e", 9, 2), (36, "f", 21, 6))

    def _pair(self, kind, monkeypatch, **over):
        from llmd_kv_cache_tpu.models import engine as engine_module
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetryConfig)

        monkeypatch.setattr(engine_module, "_launch_counts", {})
        cfg, params, options = _served_model(kind)
        conf = dict(model=cfg, num_pages=128, max_pages_per_seq=32,
                    max_batch=4, max_prefill_tokens=2 * cfg.page_size,
                    telemetry=EngineTelemetryConfig(), **options)
        conf.update(over)
        eng = MiniEngine(EngineConfig(pod_identifier="pod-0", **conf),
                         params=params)
        other = MiniEngine(EngineConfig(pod_identifier="pod-1", **conf),
                           params=eng.params)
        from test_telemetry import _recorded

        return eng, other, _recorded(eng._phases), cfg

    def _serve(self, eng, cfg, other=None, after=None, plan=PLAN):
        """Step ``eng`` through ``plan``; ``other`` launches a program
        before each of its steps. Every call returns a request's newest
        token, and ``req.output`` grew by one token (or, where the model
        drafts and the draft was right, two) exactly where it did."""
        rng = np.random.default_rng(5)
        prompts = {rid: rng.integers(1, cfg.vocab_size, n).tolist()
                   for _, rid, n, _ in plan}
        reqs, streams, step = {}, {}, 0
        while step < 200 and (eng.requests or step <= plan[-1][0]):
            for at, rid, _, new in plan:
                if at == step:
                    reqs[rid] = eng.enqueue(rid, prompts[rid],
                                            max_new_tokens=new)
            if other is not None:
                other.enqueue(f"o{step}", prompts[plan[0][1]][:3],
                              max_new_tokens=2)
                while other.requests:
                    other.step()
            grew = {rid: len(req.output) for rid, req in reqs.items()}
            emitted = eng.step()
            for rid, req in reqs.items():
                grown = len(req.output) - grew[rid]
                assert (grown > 0) == (rid in emitted)
                assert grown <= eng._step_tokens
            for rid, token in emitted.items():
                assert token == reqs[rid].output[-1]
                streams.setdefault(rid, []).extend(
                    reqs[rid].output[grew[rid]:])
            if after is not None:
                after(step, reqs)
            step += 1
        assert not eng.requests
        assert streams == {rid: req.output for rid, req in reqs.items()
                           if req.output}
        return {rid: list(req.output) for rid, req in reqs.items()}

    @pytest.mark.parametrize(
        "kind", ["dense", "dense-pallas", "counters", "state", "drafting",
                 "pools"])
    def test_ahead_gives_the_synchronous_order_token_for_token(
            self, kind, monkeypatch):
        eng, other, seen, cfg = self._pair(kind, monkeypatch)
        ahead = self._serve(eng, cfg)
        decodes = _decodes(seen)
        assert sum(d["ahead"] for d in decodes) > len(decodes) // 3
        assert [len(ahead[rid]) for _, rid, _, _ in self.PLAN] == [
            new for _, _, _, new in self.PLAN]
        tel = eng.telemetry.debug_vars()["lookahead"]
        assert tel == {"launched_ahead": sum(d["ahead"] for d in decodes),
                       "drained": {}}
        chunks, decodes_deep, chunks_deep = _queue_depths(seen)
        # (Pages of 32 tokens: the plan's prompts make 7 chunks of 64.)
        assert chunks >= (7 if kind == "pools" else 9)
        assert decodes_deep == chunks_deep == 1
        assert eng._unread is None

        held, other, seen, _ = self._pair(kind, monkeypatch)
        assert held._launches is other._launches is not eng._launches
        assert self._serve(held, cfg, other) == ahead
        assert _decodes(seen) and not any(d["ahead"] for d in _decodes(seen))
        assert held.telemetry.debug_vars()["lookahead"] == {
            "launched_ahead": 0, "drained": {}}
        assert (held.block_manager.pool_stats()
                == eng.block_manager.pool_stats())

    @pytest.mark.parametrize("kind", ["dense", "counters", "state", "pools"])
    def test_a_rows_last_token_is_not_launched_past(self, kind, monkeypatch):
        """A row stops by count: a program launched ahead leaves out the
        row whose unread token is its last, so no row runs for nothing and
        the last ``step()`` of a run launches nothing."""
        plan = ((0, "a", 9, 12), (0, "b", 5, 7), (0, "c", 6, 3))
        eng, _, seen, cfg = self._pair(kind, monkeypatch)
        out = self._serve(eng, cfg, plan=plan)
        assert [len(out[rid]) for rid in "abc"] == [12, 7, 3]
        decodes = _decodes(seen)
        # (Three admissions: three steps with a chunk.)
        assert [d["ahead"] for d in decodes] == 3 * [0] + 8 * [1]
        assert sum(d["rows"] for d in decodes) == sum(
            new - 1 for _, _, _, new in plan)
        last = max(attrs["step"] for _, attrs, _ in seen)
        assert [name for name, attrs, _ in seen if attrs["step"] == last
                and name.startswith("step.") and name not in (
                    "step.offload_poll", "step.schedule")] == [
                        "step.fetch", "step.finish"]
        assert eng._unread is None

    def test_an_engine_in_prefill_never_launches_ahead(self, monkeypatch):
        """The case PR 42 let through: a prompt of many chunks admitted
        beside a decoding row, the other engine idle. No step that launches
        a chunk launches a decode program ahead; when the first chunk goes
        out, the one program launched ahead before the admission is the
        only one unread, and it is read in that step as any other (no
        drain); from then on every chunk finds the queue empty."""
        plan = ((0, "a", 6, 60), (8, "long", 90, 4))
        eng, _, seen, cfg = self._pair("dense", monkeypatch)
        unread_at_chunk = []
        chunk = eng._prefill_chunk

        def watched(req):
            rec = eng._unread  # (taken by ``step()`` only after the chunk)
            unread_at_chunk.append(
                0 if rec is None or rec.host is not None else 1)
            return chunk(req)

        monkeypatch.setattr(eng, "_prefill_chunk", watched)
        out = self._serve(eng, cfg, plan=plan)
        assert [len(out["a"]), len(out["long"])] == [60, 4]
        chunks, decodes_deep, chunks_deep = _queue_depths(seen)
        assert chunks == len(unread_at_chunk) == 13
        # (The chunk of the admission's own step, behind the program in
        # flight, is the one chunk the next step's is launched behind.)
        assert decodes_deep == chunks_deep == 1
        assert unread_at_chunk == [0, 1] + 11 * [0]        # a's; long's 12
        by_step = {}
        for name, attrs, _ in seen:
            if name == "step.dispatch":
                by_step.setdefault(attrs["step"], []).append(
                    "chunk" if "prefill_pos" in attrs else attrs["ahead"])
        # Step 9 (the plan's 8) reads the program step 8 launched ahead and
        # launches the chunk alone; steps 10-20 are synchronous: a chunk,
        # then the decode program, read before the step returns.
        assert by_step[8] == [1] and by_step[9] == ["chunk"]
        assert all(by_step[step] == ["chunk", 0] for step in range(10, 21))
        # The first step without a prefill is synchronous and launches
        # ahead again at once: the engine was alone all along.
        assert by_step[21] == [0, 1] and by_step[22] == [1]
        assert eng.telemetry.debug_vars()["lookahead"]["drained"] == {}

        held, other, _, _ = self._pair("dense", monkeypatch)
        assert self._serve(held, cfg, other, plan=plan) == out

    @pytest.mark.parametrize("kind", ["dense", "state"])
    def test_an_engine_with_nothing_to_decode_is_one_chunk_ahead(
            self, kind, monkeypatch):
        """A prompt of many chunks and no row to decode: a step reads
        nothing of its own, so it waits for the chunk before the one it
        launched. One chunk runs, one is queued, and the engine is no
        further ahead of the device (left alone it sent a whole document's
        chunks ahead, and the other replica's next program stood behind
        them all)."""
        eng, _, seen, cfg = self._pair(kind, monkeypatch)
        chunk = 2 * cfg.page_size
        out = self._serve(eng, cfg, plan=((0, "long", 12 * chunk - 3, 3),))
        assert len(out["long"]) == 3
        assert _queue_depths(seen) == (12, 0, 1)
        log = [("chunk" if "prefill_pos" in a else "decode", a["launch"])
               if name == "step.dispatch" else ("fetch", a["launch"])
               for name, a, _ in seen
               if name in ("step.dispatch", "step.fetch")]
        # Chunk k+1 goes out, then chunk k is waited for; the last one's
        # token is the request's first, read in its own step.
        assert log[:5] == [("chunk", 1), ("chunk", 2), ("fetch", 1),
                           ("chunk", 3), ("fetch", 2)]
        assert log[21:] == [("chunk", 12), ("fetch", 12), ("decode", 13),
                            ("fetch", 13), ("decode", 14), ("fetch", 14)]
        assert eng._chunk_ahead is None

    def test_two_engines_in_turn_never_launch_ahead(self, monkeypatch):
        a, b, seen, cfg = self._pair("dense", monkeypatch)
        b._phases._annotation = a._phases._annotation
        rng = np.random.default_rng(9)
        for eng in (a, b):
            eng.enqueue("r", rng.integers(1, 256, 11).tolist(),
                        max_new_tokens=8)
        while a.requests or b.requests:
            for eng in (a, b):
                if eng.requests:
                    eng.step()
                    assert eng._unread is None
        assert {d["pod"] for d in _decodes(seen)} == {"pod-0", "pod-1"}
        assert [d["ahead"] for d in _decodes(seen)] == 14 * [0]
        # Left alone, the one that still decodes goes ahead.
        a.enqueue("s", rng.integers(1, 256, 11).tolist(), max_new_tokens=8)
        while a.requests:
            a.step()
        assert [d["ahead"] for d in _decodes(seen)[14:]] == [
            0, 0, 1, 1, 1, 1, 1]

    @pytest.mark.parametrize("how", ["abort", "reset", "offload"])
    def test_ending_a_request_under_a_program_in_flight(self, how,
                                                        monkeypatch):
        """The program is waited for first (``lookahead.drained`` names
        what asked), an ended row's unread token is dropped, and the pools
        read as a synchronous engine's."""
        plan = ((0, "a", 7, 9), (0, "b", 5, 9))

        class _Copiers:  # what ``_sync_caches_to_copier`` hands the pools
            copier = types.SimpleNamespace(k_cache=None, v_cache=None)

        def end_one(eng, in_flight):
            def after(step, reqs):
                if step != 4:
                    return
                assert (eng._unread is not None) == in_flight
                assert len(reqs["a"].output) == 5
                if how == "abort":
                    assert eng.abort_request("a")
                    assert (eng._unread is not None) == in_flight
                elif how == "reset":
                    eng.reset_cache()
                    assert eng._unread is None and eng.step() == {}
                else:
                    monkeypatch.setattr(eng, "offload_handlers", _Copiers)
                    eng._sync_caches_to_copier()
                    assert _Copiers.copier.k_cache is eng.k_cache
                    # Waited for, and still this engine's to return.
                    assert (eng._unread is not None) == in_flight
                    assert not in_flight or eng._unread.host is not None
                    monkeypatch.setattr(eng, "offload_handlers", None)
                assert len(reqs["a"].output) == 5       # not taken early
            return after

        eng, _, _, cfg = self._pair("dense", monkeypatch)
        ahead = self._serve(eng, cfg, after=end_one(eng, True), plan=plan)
        held, other, _, _ = self._pair("dense", monkeypatch)
        assert self._serve(held, cfg, other, after=end_one(held, False),
                           plan=plan) == ahead
        assert len(ahead["a"]) == (9 if how == "offload" else 5)
        assert len(ahead["b"]) == (4 if how == "reset" else 9)
        assert (eng.block_manager.pool_stats()
                == held.block_manager.pool_stats())
        assert eng.block_manager.num_free() == held.block_manager.num_free()
        assert eng.telemetry.debug_vars()["lookahead"]["drained"] == {
            "offload" if how == "offload" else "aborted": 1}
        assert held.telemetry.debug_vars()["lookahead"]["drained"] == {}

    @pytest.mark.parametrize("kind", ["sharded", "hybrid"])
    def test_an_engine_that_cannot_defer_takes_no_prev(self, kind,
                                                       monkeypatch):
        """A mesh keeps the synchronous order and the programs it had,
        over one pool or (``hybrid``) over a window pool beside a global
        one, which without a mesh defers like any other engine: no ``prev``
        operand, no ``src`` in the packed inputs, no ``ahead`` on a
        dispatch, nothing unread between steps."""
        import jax
        from jax.sharding import Mesh

        from llmd_kv_cache_tpu.models import engine as engine_module
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetryConfig)

        monkeypatch.setattr(engine_module, "_launch_counts", {})
        tiny = LlamaConfig.tiny()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
        if kind == "hybrid":
            tiny = dataclasses.replace(tiny, sliding_window=8,
                                       swa_layers=(1,))
            alone = MiniEngine(EngineConfig(
                model=tiny, num_pages=64, num_swa_pages=64,
                max_pages_per_seq=16, max_batch=2, pod_identifier="q"),
                seed=0)
            assert alone._defers is True and alone._prev is not None
        eng = MiniEngine(EngineConfig(
            model=tiny, num_pages=64, num_swa_pages=64,
            max_pages_per_seq=16, max_batch=2, pod_identifier="p",
            telemetry=EngineTelemetryConfig()), seed=0, mesh=mesh)
        assert eng._defers is False and eng._prev is None
        from test_telemetry import _recorded

        seen = _recorded(eng._phases)
        calls = []
        program = eng._decode_forward
        monkeypatch.setattr(eng, "_decode_forward", lambda *a, **kw: (
            calls.append(kw), program(*a, **kw))[1])
        eng.enqueue("a", list(range(1, 8)), max_new_tokens=9)
        eng.enqueue("b", list(range(9, 14)), max_new_tokens=6)
        while eng.requests:
            eng.step()
            assert eng._unread is None
        assert calls and not any("prev" in kw for kw in calls)
        assert _decodes(seen) and not any(
            "ahead" in d for d in _decodes(seen))
        assert eng.telemetry.debug_vars()["lookahead"] == {
            "launched_ahead": 0, "drained": {}}
