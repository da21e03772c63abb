"""Engine data-plane observability tests (ISSUE 5).

Covers the engine telemetry layer end to end: the config-bucketed
histogram primitive and its Prometheus exposition, request-lifecycle
records (monotone timestamps, TTFT/ITL/TPOT populated from a scripted
``MiniEngine`` run), KV-pool gauges, score→serve trace continuity (one
trace from ``IndexerService.get_pod_scores`` through admission, prefill,
and decode-step spans), the ``ScoreResponse.traceparent`` wire field, and
the guarded ``/debug/profile`` admin endpoint.
"""

import json
import urllib.error
import urllib.request

import msgpack
import pytest

from llmd_kv_cache_tpu.metrics import collector
from llmd_kv_cache_tpu.telemetry import recording_tracing
from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
    EngineTelemetry,
    EngineTelemetryConfig,
    ProfileInProgress,
    ProfilerCapture,
)


def make_engine(telemetry=None, **cfg_kw):
    import jax  # noqa: F401  (engine import needs a jax backend)

    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
    from llmd_kv_cache_tpu.models.llama import LlamaConfig

    return MiniEngine(
        EngineConfig(
            model=LlamaConfig.tiny(), num_pages=64, max_pages_per_seq=16,
            model_name="tiny", pod_identifier="pod-a", telemetry=telemetry,
            **cfg_kw,
        ),
        seed=0,
    )


class TestBucketHistogram:
    def test_observe_count_sum_and_cumulative_buckets(self):
        h = collector.BucketHistogram("h_unit", "doc", (0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(55.55)
        snap = h.snapshot()
        # Cumulative, Prometheus-style, with a +Inf catch-all.
        assert snap["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 3, "+Inf": 4}

    def test_percentiles(self):
        h = collector.BucketHistogram("h_pct", "doc", (1.0, 2.0, 4.0))
        assert h.percentile(0.5) == 0.0  # empty
        for _ in range(100):
            h.observe(1.5)
        p50 = h.percentile(0.5)
        assert 1.0 <= p50 <= 2.0
        h.observe(100.0)  # overflow bucket clamps to the last bound
        assert h.percentile(1.0) == 4.0

    def test_reset(self):
        h = collector.BucketHistogram("h_reset", "doc", (1.0,))
        h.observe(0.5)
        h.reset()
        assert h.count == 0 and h.sum == 0.0

    def test_factory_dedupes_by_name_and_exports(self):
        from prometheus_client import generate_latest

        a = collector.bucket_histogram("kvtpu_engine_test_seconds", "doc", (0.1, 1.0))
        b = collector.bucket_histogram("kvtpu_engine_test_seconds", "doc", (9.9,))
        assert a is b  # first caller's buckets win
        a.observe(0.05)
        text = generate_latest().decode()
        assert 'kvtpu_engine_test_seconds_bucket{le="0.1"}' in text
        assert "kvtpu_engine_test_seconds_count" in text

    def test_forgotten_family_starts_empty(self):
        from prometheus_client import generate_latest

        name = "kvtpu_engine_forget_test_seconds"
        old = collector.bucket_histogram(name, "doc", (0.1, 1.0))
        old.observe(0.05)
        kept = collector.bucket_histogram("kvtpu_engine_kept_test_seconds",
                                          "doc", (1.0,))
        collector.forget_bucket_histograms(name)
        assert name + "_count" not in generate_latest().decode()
        new = collector.bucket_histogram(name, "doc", (0.1, 1.0))
        assert new is not old and new.count == 0
        old.observe(0.05)      # a holder of the old one: unexported
        assert f"{name}_count 0.0" in generate_latest().decode()
        assert collector.bucket_histogram(
            "kvtpu_engine_kept_test_seconds", "doc", (1.0,)) is kept


class TestRequestLifecycle:
    @pytest.fixture(scope="class")
    def served_engine(self):
        """One scripted continuous-batching run shared by the assertions:
        two requests enqueued, stepped to completion, then a warm repeat
        of the first prompt for the prefix-hit path."""
        eng = make_engine(telemetry=EngineTelemetryConfig(pool_gauge_every=1))
        tel = eng.telemetry
        assert tel is not None
        base = {h.name: h.count for h in (tel.ttft, tel.itl, tel.tpot,
                                          tel.step_seconds)}
        prompt = list(range(1, 13))
        eng.enqueue("r0", prompt, max_new_tokens=6)
        eng.enqueue("r1", list(range(20, 30)), max_new_tokens=6)
        while eng.step():
            pass
        eng.enqueue("r2", prompt, max_new_tokens=2)
        while eng.step():
            pass
        return eng, tel, base

    def test_lifecycle_timestamps_monotone(self, served_engine):
        _, tel, _ = served_engine
        done = {s["request_id"]: s for s in tel.finished}
        assert {"r0", "r1", "r2"} <= set(done)
        for s in done.values():
            assert s["outcome"] == "finished"
            assert s["tokens"] > 0
            assert (s["enqueue_ts"] <= s["admit_ts"] <= s["first_token_ts"]
                    <= s["last_token_ts"] <= s["finish_ts"])

    def test_phase_histograms_populated(self, served_engine):
        _, tel, base = served_engine
        assert tel.ttft.count - base["kvtpu_engine_ttft_seconds"] == 3
        # r0/r1 decode 5 tokens each after the first; r2 decodes 1.
        assert tel.itl.count - base["kvtpu_engine_itl_seconds"] >= 10
        assert tel.tpot.count - base["kvtpu_engine_tpot_seconds"] == 3
        assert tel.step_seconds.count > base["kvtpu_engine_decode_step_seconds"]

    def test_prefix_hit_blocks_recorded(self, served_engine):
        _, tel, _ = served_engine
        done = {s["request_id"]: s for s in tel.finished}
        assert done["r0"]["prefix_hit_blocks"] == 0  # cold
        assert done["r2"]["prefix_hit_blocks"] > 0   # warm repeat of r0

    def test_pool_gauges_scraped(self, served_engine):
        eng, tel, _ = served_engine
        dv = tel.debug_vars()
        pool = dv["pool"]["full"]
        assert pool["total_pages"] == 64
        assert 0 < pool["free_pages"] < 64
        assert pool["cached_blocks"] > 0
        stats = eng.block_manager.pool_stats()
        assert stats["free_pages"] == pool["free_pages"]

    def test_metrics_exposition(self, served_engine):
        from prometheus_client import generate_latest

        text = generate_latest().decode()
        for family in ("kvtpu_engine_ttft_seconds_bucket",
                       "kvtpu_engine_itl_seconds_count",
                       "kvtpu_engine_tpot_seconds_count",
                       "kvtpu_engine_requests_total",
                       "kvtpu_engine_decode_steps_total",
                       "kvtpu_engine_kv_pool_free_pages"):
            assert family in text, family

    def test_debug_vars_shape(self, served_engine):
        _, tel, _ = served_engine
        dv = tel.debug_vars()
        assert dv["requests"]["active"] == 0
        assert dv["requests"]["finished_window"] >= 3
        assert dv["phases"]["ttft_seconds"]["count"] >= 3
        assert dv["phases"]["ttft_seconds"]["p50"] > 0.0
        assert dv["steps"] > 0
        assert dv["last_profile"] is None

    def test_abort_counts_as_aborted(self):
        eng = make_engine(telemetry=EngineTelemetryConfig())
        eng.enqueue("ra", list(range(1, 9)), max_new_tokens=32)
        eng.step()
        eng.abort_request("ra")
        done = {s["request_id"]: s for s in eng.telemetry.finished}
        assert done["ra"]["outcome"] == "aborted"

    def test_telemetry_disabled_paths(self):
        assert make_engine(telemetry=None).telemetry is None
        eng = make_engine(telemetry=EngineTelemetryConfig(enabled=False))
        assert eng.telemetry is None
        eng.enqueue("r0", list(range(1, 9)), max_new_tokens=2)
        while eng.step():
            pass


class TestConfig:
    def test_from_dict_camel_and_snake(self):
        cfg = EngineTelemetryConfig.from_dict({
            "ttftBuckets": [0.5, 1.0], "pool_gauge_every": 4,
            "profileDir": "/tmp/xp", "flightRecords": False,
        })
        assert cfg.ttft_buckets == (0.5, 1.0)
        assert cfg.pool_gauge_every == 4
        assert cfg.profile_dir == "/tmp/xp"
        assert cfg.flight_records is False
        assert EngineTelemetryConfig.from_dict(None).enabled is True


class TestScoreServeTrace:
    def test_single_trace_from_score_to_decode(self):
        """Acceptance: one request driven through GetPodScores and
        enqueue/step yields ONE trace containing score, admission,
        prefill, and decode-step spans."""
        from llmd_kv_cache_tpu.core import TokenProcessorConfig
        from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch
        from llmd_kv_cache_tpu.events.pool import PoolConfig
        from llmd_kv_cache_tpu.scoring import IndexerConfig
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerService,
            ScoreRequest,
        )

        block = 4
        prompt = list(range(1, 13))
        svc = IndexerService(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size_tokens=block)),
            PoolConfig(concurrency=1),
        )
        svc.start()
        try:
            svc.pool.process_event_batch(
                EventBatch(timestamp=0.0, events=[
                    BlockStoredEvent(block_hashes=[1, 2, 3], tokens=prompt,
                                     parent_hash=0, block_size=block)]),
                "pod-a", "tiny")
            with recording_tracing() as exporter:
                resp = svc.get_pod_scores(ScoreRequest(
                    tokens=prompt, model_name="tiny"))
                assert resp.error == ""
                assert resp.scores.get("pod-a", 0) > 0
                assert resp.traceparent.startswith("00-")

                eng = make_engine(telemetry=EngineTelemetryConfig())
                eng.enqueue("r0", prompt, max_new_tokens=4,
                            traceparent=resp.traceparent)
                while eng.step():
                    pass
        finally:
            svc.stop()

        spans = exporter.spans
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        for name in ("llm_d.kv_cache.indexer.GetPodScores",
                     "llm_d.kv_cache.engine.admission",
                     "llm_d.kv_cache.engine.prefill_chunk",
                     "llm_d.kv_cache.engine.decode_step"):
            assert by_name.get(name), f"missing span {name}"
        score_trace = by_name["llm_d.kv_cache.indexer.GetPodScores"][0].trace_id
        engine_spans = [s for s in spans
                        if s.name.startswith("llm_d.kv_cache.engine.")]
        assert len(engine_spans) >= 3
        assert {s.trace_id for s in engine_spans} == {score_trace}

    def test_untraced_request_creates_no_spans(self):
        with recording_tracing() as exporter:
            eng = make_engine(telemetry=EngineTelemetryConfig())
            eng.enqueue("r0", list(range(1, 9)), max_new_tokens=3)
            while eng.step():
                pass
        assert not [s for s in exporter.spans
                    if s.name.startswith("llm_d.kv_cache.engine.")]


class TestScoreResponseWire:
    def test_round_trip_with_traceparent(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        resp = ScoreResponse(scores={"pod-a": 1.0}, traceparent=tp)
        decoded = ScoreResponse.from_bytes(resp.to_bytes())
        assert decoded.traceparent == tp
        assert decoded.scores == {"pod-a": 1.0}

    def test_old_peer_payload_decodes_empty_traceparent(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        old = msgpack.packb({"scores": {"pod-a": 1.0}, "error": ""},
                            use_bin_type=True)
        decoded = ScoreResponse.from_bytes(old)
        assert decoded.traceparent == ""
        assert decoded.degraded is False


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read()


class TestProfileEndpoint:
    def test_unconfigured_profiler_is_404(self):
        from llmd_kv_cache_tpu.services.admin import AdminServer

        server = AdminServer(port=0)
        try:
            port = server.start()
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(port, "/debug/profile")
            assert err.value.code == 404
        finally:
            server.stop()

    def test_bad_duration_is_400_and_busy_is_409(self, tmp_path):
        from llmd_kv_cache_tpu.services.admin import AdminServer

        cap = ProfilerCapture(str(tmp_path / "xplane"))
        server = AdminServer(port=0)
        server.register_profiler(cap.capture)
        try:
            port = server.start()
            for q in ("?duration_s=abc", "?duration_s=0", "?duration_s=999"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    _get(port, f"/debug/profile{q}")
                assert err.value.code == 400, q
            # A capture in flight → 409 (checked before jax is touched).
            assert cap._lock.acquire(blocking=False)
            try:
                with pytest.raises(urllib.error.HTTPError) as err:
                    _get(port, "/debug/profile?duration_s=0.1")
                assert err.value.code == 409
            finally:
                cap._lock.release()
        finally:
            server.stop()

    def test_no_profile_dir_raises(self):
        with pytest.raises(RuntimeError, match="profileDir"):
            ProfilerCapture("").capture(0.1)

    def test_capture_smoke(self, tmp_path):
        """Real jax.profiler capture through the endpoint; skipped when the
        platform can't run the profiler (some CPU builds)."""
        from llmd_kv_cache_tpu.services.admin import AdminServer

        profile_dir = tmp_path / "xplane"
        cap = ProfilerCapture(str(profile_dir))
        try:
            cap.capture(0.05)
        except RuntimeError as exc:
            pytest.skip(f"jax.profiler capture unsupported here: {exc}")
        assert cap.last is not None and cap.last["duration_s"] == 0.05
        assert any(profile_dir.rglob("*")), "no xplane artifacts written"

        server = AdminServer(port=0)
        server.register_profiler(cap.capture)
        try:
            port = server.start()
            status, body = _get(port, "/debug/profile?duration_s=0.05")
            assert status == 200
            assert json.loads(body)["dir"] == str(profile_dir)
        finally:
            server.stop()

    def test_profile_in_progress_direct(self, tmp_path):
        cap = ProfilerCapture(str(tmp_path))
        assert cap._lock.acquire(blocking=False)
        try:
            with pytest.raises(ProfileInProgress):
                cap.capture(0.1)
        finally:
            cap._lock.release()


class TestAttachAdmin:
    def test_engine_debug_section_and_kvdiag_summary(self):
        import importlib.util
        from pathlib import Path

        from llmd_kv_cache_tpu.services.admin import AdminServer

        eng = make_engine(telemetry=EngineTelemetryConfig(pool_gauge_every=1))
        eng.enqueue("r0", list(range(1, 9)), max_new_tokens=3)
        while eng.step():
            pass
        server = AdminServer(port=0)
        eng.telemetry.attach_admin(server)
        try:
            port = server.start()
            status, body = _get(port, "/debug/engine")
            assert status == 200
            doc = json.loads(body)
            assert doc["pool"]["full"]["total_pages"] == 64
            assert doc["phases"]["ttft_seconds"]["count"] >= 1

            # No profile_dir configured → the profiler endpoint stays 404.
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(port, "/debug/profile")
            assert err.value.code == 404

            spec = importlib.util.spec_from_file_location(
                "kvdiag",
                Path(__file__).resolve().parents[1] / "hack" / "kvdiag.py")
            kvdiag = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(kvdiag)
            report = kvdiag.snapshot("127.0.0.1", port)
            assert report["engine"]["pool"]["full"]["total_pages"] == 64
            assert report["engine"]["phases"]["ttft_seconds"]["count"] >= 1
            assert any(k.startswith("kvtpu_engine_")
                       for k in report["metrics"])
        finally:
            server.stop()


class TestRestoreMetrics:
    def test_restore_counters_record(self):
        before = collector.ENGINE_RESTORE_JOBS.labels("success")._value.get()
        collector.record_engine_restore("success", 0.25)
        collector.record_engine_restore("timeout")
        after = collector.ENGINE_RESTORE_JOBS.labels("success")._value.get()
        assert after == before + 1
