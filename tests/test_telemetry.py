"""Telemetry tests: span facade, W3C propagation, flight recorder,
event-lag bookkeeping, admin endpoint, and the end-to-end trace.

The cross-hop trace test exercises the full ISSUE-3 path with real
transports: tokenizer gRPC (UDS/TCP) with ``traceparent`` metadata, the
ZMQ event wire with the payload-embedded traceparent, and the pool's
ingest span parenting — all captured by the in-repo recording exporter
(no OpenTelemetry SDK needed).
"""

import json
import os
import signal
import threading
import time
import urllib.request

import msgpack
import numpy as np
import pytest

from llmd_kv_cache_tpu.telemetry import (
    FlightRecorder,
    attach_failpoint_listener,
    current_traceparent,
    flight_recorder,
    format_traceparent,
    init_tracing,
    install_signal_dump,
    parse_traceparent,
    recording_tracing,
    set_flight_recorder,
    tracer,
)
from llmd_kv_cache_tpu.telemetry.flight_recorder import KIND_SCORE


def wait_until(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class TestSpanFacade:
    def test_spans_noop_without_provider(self):
        with tracer().span("test.span", foo=1) as span:
            span.set_attribute("bar", 2)  # must not raise

    def test_noop_span_chains_and_accepts_kwargs(self):
        # Satellite: the no-op path must swallow attribute kwargs and
        # support chained mutators without allocating per call.
        cm1 = tracer().span("llm_d.kv_cache.a", model="m", tokens=7)
        cm2 = tracer().span("llm_d.kv_cache.b")
        assert cm1 is cm2  # shared allocation-free context manager
        with cm1 as span:
            assert span.set_attribute("k", 1).set_attribute("k2", 2) is span
            assert span.add_event("e", {"a": 1}) is span

    def test_noop_span_reraises(self):
        with pytest.raises(ValueError):
            with tracer().span("llm_d.kv_cache.err"):
                raise ValueError("boom")


class TestTraceparent:
    def test_round_trip(self):
        tp = format_traceparent(0xABC, 0xDEF)
        assert tp == f"00-{0xABC:032x}-{0xDEF:016x}-01"
        assert parse_traceparent(tp) == (0xABC, 0xDEF, 1)

    def test_unsampled_flag(self):
        tp = format_traceparent(1, 2, sampled=False)
        assert tp.endswith("-00")
        assert parse_traceparent(tp) == (1, 2, 0)

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-zz-11-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # zero span id
        "00-" + "1" * 31 + "-" + "1" * 16 + "-01",  # short trace id
    ])
    def test_malformed_dropped(self, bad):
        assert parse_traceparent(bad) is None

    def test_current_traceparent_none_outside_span(self):
        assert current_traceparent() is None


class TestRecordingExporter:
    def test_parentage_and_attributes(self):
        with recording_tracing() as exporter:
            with tracer().span("llm_d.kv_cache.outer", model="m") as outer:
                outer.set_attribute("extra", 1)
                with tracer().span("llm_d.kv_cache.inner"):
                    pass
            outer_rec = exporter.find("llm_d.kv_cache.outer")[0]
            inner_rec = exporter.find("llm_d.kv_cache.inner")[0]
            assert outer_rec.attributes == {"model": "m", "extra": 1}
            assert outer_rec.parent_span_id is None
            assert inner_rec.trace_id == outer_rec.trace_id
            assert inner_rec.parent_span_id == outer_rec.span_id
            assert outer_rec.end_time is not None

    def test_explicit_parent_traceparent_wins(self):
        with recording_tracing() as exporter:
            tp = format_traceparent(0x1234, 0x5678)
            with tracer().span("llm_d.kv_cache.remote_child",
                               parent_traceparent=tp):
                pass
            rec = exporter.find("llm_d.kv_cache.remote_child")[0]
            assert rec.trace_id == 0x1234
            assert rec.parent_span_id == 0x5678

    def test_exception_recorded_with_error_status(self):
        # Satellite: error exits must record the exception, not drop it.
        with recording_tracing() as exporter:
            with pytest.raises(RuntimeError):
                with tracer().span("llm_d.kv_cache.fails"):
                    raise RuntimeError("kaput")
            rec = exporter.find("llm_d.kv_cache.fails")[0]
            assert rec.status == "ERROR"
            assert "kaput" in (rec.status_description or "")
            assert any(name == "exception" and attrs["exception.type"] == "RuntimeError"
                       for name, attrs in rec.events)

    def test_current_traceparent_inside_span(self):
        with recording_tracing() as exporter:
            with tracer().span("llm_d.kv_cache.ambient"):
                tp = current_traceparent()
            rec = exporter.find("llm_d.kv_cache.ambient")[0]
            assert tp == rec.traceparent
        assert current_traceparent() is None


class TestInitTracing:
    def test_init_tracing_none_exporter_disables(self, monkeypatch):
        monkeypatch.setenv("OTEL_TRACES_EXPORTER", "none")
        assert init_tracing() is False

    def test_init_tracing_installs_provider(self, monkeypatch):
        monkeypatch.delenv("OTEL_TRACES_EXPORTER", raising=False)
        monkeypatch.setenv("OTEL_SERVICE_NAME", "kvtpu-test")
        monkeypatch.setenv("OTEL_EXPORTER_OTLP_ENDPOINT", "http://127.0.0.1:1")
        installed = init_tracing()
        if installed:  # exporter packages present in this image
            from opentelemetry import trace

            provider = trace.get_tracer_provider()
            assert type(provider).__name__ == "TracerProvider"
            # spans now record through the facade without error (export to the
            # dead endpoint is batched/async and harmless)
            with tracer().span("test.live", x=1):
                pass


class TestFlightRecorder:
    def test_wraparound_keeps_newest(self):
        rec = FlightRecorder(capacity=8)
        for i in range(20):
            rec.record("score", {"i": i})
        snap = rec.snapshot()
        assert len(snap) == 8
        assert [r["seq"] for r in snap] == list(range(12, 20))
        assert snap[-1]["data"] == {"i": 19}
        assert snap[0]["kind"] == "score"

    def test_concurrent_writers_never_tear(self):
        rec = FlightRecorder(capacity=64)
        n_threads, per_thread = 8, 500

        def writer(tid):
            for i in range(per_thread):
                rec.record("ingest", {"tid": tid, "i": i})

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        # Readers race the writers on purpose: every observed record must
        # be whole (the ring stores immutable tuples, never torn state).
        for _ in range(50):
            for r in rec.snapshot():
                assert set(r) == {"seq", "ts", "mono", "kind", "data"}
                assert r["kind"] == "ingest"
        for t in threads:
            t.join()
        snap = rec.snapshot()
        assert len(snap) == 64
        seqs = [r["seq"] for r in snap]
        assert seqs == sorted(seqs)
        # All sequence numbers were claimed exactly once across threads.
        assert rec.record("score") == n_threads * per_thread

    def test_dump_json_and_clear(self):
        rec = FlightRecorder(capacity=4)
        rec.record("offload", {"job_id": 1, "unjsonable": object()})
        doc = json.loads(rec.dump_json(indent=2))
        assert doc["capacity"] == 4
        assert doc["records"][0]["kind"] == "offload"
        rec.clear()
        assert rec.snapshot() == []

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_sigusr2_dump_to_file(self, tmp_path):
        rec = FlightRecorder(capacity=16)
        rec.record("failover", {"op": "lookup", "reason": "breaker_open"})
        out = tmp_path / "ring.json"
        previous = install_signal_dump(path=str(out), recorder=rec)
        try:
            os.kill(os.getpid(), signal.SIGUSR2)
            assert wait_until(out.exists)
            doc = json.loads(out.read_text())
            assert doc["records"][0]["kind"] == "failover"
        finally:
            signal.signal(signal.SIGUSR2, previous)

    def test_failpoint_trip_lands_in_ring(self):
        from llmd_kv_cache_tpu.resilience.failpoints import FailpointRegistry

        rec = FlightRecorder(capacity=16)
        set_flight_recorder(rec)
        try:
            registry = FailpointRegistry(seed=1)
            attach_failpoint_listener(registry)
            registry.arm("test.fp", times=1)
            assert registry.should_fire("test.fp") is True
            kinds = [r["kind"] for r in rec.snapshot()]
            assert "failpoint" in kinds
            fp = [r for r in rec.snapshot() if r["kind"] == "failpoint"][0]
            assert fp["data"] == {"name": "test.fp"}
        finally:
            set_flight_recorder(None)


class TestEventLag:
    def _msg(self, pod, seq, ts, tokens, block=4):
        from llmd_kv_cache_tpu.events import RawMessage

        ev = ["BlockStored", [seq + 1000], None, tokens, block]
        return RawMessage(
            topic=f"kv@{pod}@m", sequence=seq,
            payload=msgpack.packb([ts, [ev]], use_bin_type=True),
        )

    @pytest.fixture
    def pool(self):
        from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
        from llmd_kv_cache_tpu.events import Pool, PoolConfig
        from llmd_kv_cache_tpu.index.base import create_index

        processor = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=4))
        p = Pool(PoolConfig(concurrency=2), create_index(None), processor)
        p.start()
        yield p
        p.shutdown()

    def test_lag_and_seq_gap_tracking(self, pool):
        base = time.time() - 1.0  # published one second ago
        for seq in (0, 1, 3):  # hole at 2
            pool.add_task(self._msg("pod-a", seq, base, [1, 2, 3, 4]))
        pool.add_task(self._msg("pod-b", 0, base, [5, 6, 7, 8]))
        pool.join()

        stats = pool.lag_stats()
        assert set(stats["pods"]) == {"pod-a", "pod-b"}
        a = stats["pods"]["pod-a"]
        assert a["messages"] == 3
        assert a["seq_gaps"] == 1
        assert a["last_seq"] == 3
        assert a["lag_s"] == pytest.approx(1.0, abs=0.5)
        assert stats["pods"]["pod-b"]["seq_gaps"] == 0
        assert stats["staleness_s"] == pytest.approx(1.0, abs=0.5)
        assert stats["lag_p50_s"] > 0.0
        assert stats["lag_p99_s"] >= stats["lag_p50_s"]
        assert len(stats["queue_depths"]) == 2
        assert pool.index_staleness_s() == pytest.approx(1.0, abs=0.5)

    def test_out_of_order_is_not_a_gap(self, pool):
        now = time.time()
        for seq in (1, 0, 2):  # reordered, not lost
            pool.add_task(self._msg("pod-a", seq, now, [1, 2, 3, 4]))
        pool.join()
        assert pool.lag_stats()["pods"]["pod-a"]["seq_gaps"] == 0

    def test_empty_pool_stats(self, pool):
        stats = pool.lag_stats()
        assert stats["pods"] == {}
        assert stats["staleness_s"] == 0.0
        assert "lag_p50_s" not in stats


class TestCacheEfficiencyLedger:
    def test_score_and_event_attribution(self):
        from llmd_kv_cache_tpu.scoring.indexer import CacheEfficiencyLedger

        ledger = CacheEfficiencyLedger()
        ledger.record_score({"pod-a": 3.0, "pod-b": 1.0}, total_blocks=8, hit_blocks=4)
        ledger.record_score({"pod-b": 2.0}, total_blocks=4, hit_blocks=2)
        ledger.record_score({}, total_blocks=2, hit_blocks=0)
        ledger.record_store("pod-a", 5)
        ledger.record_evict("pod-a", 2)
        ledger.record_clear("pod-b")

        snap = ledger.snapshot()
        assert snap["score_calls"] == 3
        assert snap["lookup_blocks"] == 14
        assert snap["lookup_hit_blocks"] == 6
        assert snap["lookup_miss_blocks"] == 8
        a, b = snap["pods"]["pod-a"], snap["pods"]["pod-b"]
        assert a["appearances"] == 1 and a["wins"] == 1
        assert a["score_total"] == 3.0
        assert a["stored_blocks"] == 5 and a["evicted_blocks"] == 2
        assert b["appearances"] == 2 and b["wins"] == 1
        assert b["clears"] == 1

    def test_indexer_feeds_ledger(self):
        from llmd_kv_cache_tpu.core.keys import PodEntry
        from llmd_kv_cache_tpu.scoring import Indexer

        indexer = Indexer()
        tokens = list(range(64))
        keys = indexer.compute_block_keys(tokens, "m")
        indexer.kv_block_index.add(None, keys, [PodEntry("pod-x", "gpu")])
        scores = indexer.score_tokens(tokens, "m")
        assert scores["pod-x"] > 0
        snap = indexer.ledger.snapshot()
        assert snap["score_calls"] == 1
        assert snap["pods"]["pod-x"]["wins"] == 1


class TestAdminServer:
    def _get(self, port, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, r.read()

    def test_endpoints(self):
        from llmd_kv_cache_tpu.services.admin import AdminServer

        rec = FlightRecorder(capacity=16)
        set_flight_recorder(rec)
        server = AdminServer(port=0)
        server.register_debug("lag", lambda: {"pods": {"pod-a": {"lag_s": 0.5}}})
        server.register_debug("broken", lambda: 1 / 0)
        try:
            port = server.start()
            assert port > 0
            rec.record(KIND_SCORE, {"model": "m", "scores": {"pod-a": 1.0}})

            status, body = self._get(port, "/healthz")
            assert status == 200 and json.loads(body) == {"status": "ok"}

            status, body = self._get(port, "/metrics")
            assert status == 200 and b"kvcache_" in body

            status, body = self._get(port, "/debug/flight-recorder")
            doc = json.loads(body)
            assert doc["records"][0]["kind"] == "score"

            status, body = self._get(port, "/debug/lag")
            assert json.loads(body)["pods"]["pod-a"]["lag_s"] == 0.5

            status, body = self._get(port, "/debug/vars")
            doc = json.loads(body)
            assert doc["flight_recorder"][0]["kind"] == "score"
            assert doc["lag"]["pods"]["pod-a"]["lag_s"] == 0.5
            assert "error" in doc["broken"]  # broken provider isolated

            with pytest.raises(urllib.error.HTTPError) as err:
                self._get(port, "/nope")
            assert err.value.code == 404
        finally:
            server.stop()
            set_flight_recorder(None)

    def test_metrics_only_server_hides_debug(self):
        from llmd_kv_cache_tpu.services.admin import AdminServer

        server = AdminServer(port=0, expose_debug=False)
        try:
            port = server.start()
            status, _ = self._get(port, "/healthz")
            assert status == 200
            with pytest.raises(urllib.error.HTTPError) as err:
                self._get(port, "/debug/vars")
            assert err.value.code == 404
        finally:
            server.stop()

    def test_kvdiag_snapshot(self):
        import importlib.util
        from pathlib import Path

        from llmd_kv_cache_tpu.services.admin import AdminServer

        spec = importlib.util.spec_from_file_location(
            "kvdiag", Path(__file__).resolve().parents[1] / "hack" / "kvdiag.py"
        )
        kvdiag = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kvdiag)

        rec = FlightRecorder(capacity=16)
        set_flight_recorder(rec)
        rec.record(KIND_SCORE, {"model": "m"})
        server = AdminServer(port=0)
        server.register_debug("lag", lambda: {"pods": {}, "staleness_s": 0.0})
        server.register_debug("ledger", lambda: {"score_calls": 0, "pods": {}})
        try:
            port = server.start()
            report = kvdiag.snapshot("127.0.0.1", port)
            assert report["healthz"]["body"] == {"status": "ok"}
            assert report["debug"]["flight_recorder"][0]["kind"] == "score"
            assert "lag" in report["debug"] and "ledger" in report["debug"]
            assert any(k.startswith("kvcache_") for k in report["metrics"])
        finally:
            server.stop()
            set_flight_recorder(None)


class TestEndToEndTrace:
    """One trace across tokenize (gRPC) → score → publish (ZMQ) → ingest
    → index add, asserted via the recording exporter."""

    def test_full_request_trace(self, tmp_path):
        from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
        from llmd_kv_cache_tpu.events import (
            BlockStoredEvent,
            Pool,
            PoolConfig,
            ZMQSubscriber,
        )
        from llmd_kv_cache_tpu.events.publisher import KVEventPublisher
        from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
        from llmd_kv_cache_tpu.index.instrumented import TracedIndex
        from llmd_kv_cache_tpu.scoring import Indexer
        from llmd_kv_cache_tpu.services.tokenizer import (
            UdsTokenizerClient,
            serve_uds,
        )

        block = 4
        with recording_tracing() as exporter:
            sock = str(tmp_path / "tok.sock")
            server = serve_uds(sock)
            client = UdsTokenizerClient(sock, timeout_s=10.0)

            processor = ChunkedTokenDatabase(
                TokenProcessorConfig(block_size_tokens=block)
            )
            index = TracedIndex(InMemoryIndex(InMemoryIndexConfig(size=10_000)))
            pool = Pool(PoolConfig(concurrency=1), index, processor)
            pool.start()
            endpoint = "tcp://127.0.0.1:15733"
            pub = KVEventPublisher(
                endpoint, pod_identifier="pod-a", model_name="m", bind=True
            )
            sub = ZMQSubscriber(endpoint, "kv@", pool.add_task, bind=False)
            sub.start()
            time.sleep(0.3)  # PUB/SUB slow-joiner settle

            indexer = Indexer()
            try:
                with tracer().span("llm_d.kv_cache.request") as root_span:
                    tokens = client.encode("simple", "hello traced world").token_ids
                    indexer.score_tokens(tokens, "m")
                    event = BlockStoredEvent(
                        block_hashes=[11], tokens=tokens[:block],
                        parent_hash=0, block_size=block,
                    )
                    # Republish until the slow-joiner window has passed;
                    # every publish carries the ambient traceparent.
                    assert wait_until(
                        lambda: (
                            pub.publish([event]) or
                            exporter.find("llm_d.kv_cache.events.ingest")
                        ),
                        timeout=10.0, interval=0.2,
                    ), "ingest span never arrived over the ZMQ hop"
                assert wait_until(
                    lambda: exporter.find("llm_d.kv_cache.index.add")
                )
            finally:
                sub.stop()
                pub.close()
                pool.shutdown()
                client.close()
                server.stop(grace=None)

            root = exporter.find("llm_d.kv_cache.request")[0]
            assert root.parent_span_id is None

            # gRPC hop: client span under root, server span under client.
            rpc = exporter.find("llm_d.kv_cache.tokenizer.rpc")[0]
            assert rpc.trace_id == root.trace_id
            assert rpc.parent_span_id == root.span_id
            assert rpc.attributes["method"] == "Tokenize"
            served = exporter.find("llm_d.kv_cache.tokenizer.Tokenize")[0]
            assert served.trace_id == root.trace_id
            assert served.parent_span_id == rpc.span_id

            # Score path joins the same trace ambiently.
            score = exporter.find("llm_d.kv_cache.score_tokens")[0]
            assert score.trace_id == root.trace_id
            assert score.parent_span_id == root.span_id

            # ZMQ hop: ingest parents under root via the wire traceparent;
            # the index write parents under ingest inside the worker thread.
            ingest = exporter.find("llm_d.kv_cache.events.ingest")[0]
            assert ingest.trace_id == root.trace_id
            assert ingest.parent_span_id == root.span_id
            assert ingest.attributes["pod"] == "pod-a"
            adds = [
                s for s in exporter.find("llm_d.kv_cache.index.add")
                if s.trace_id == root.trace_id
            ]
            assert adds, "index.add span did not join the request trace"
            ingest_ids = {
                s.span_id for s in exporter.find("llm_d.kv_cache.events.ingest")
            }
            assert adds[0].parent_span_id in ingest_ids


# -- engine phases (telemetry.tracing.phase / EnginePhases) ------------------

from llmd_kv_cache_tpu.telemetry import tracing  # noqa: E402


def _phase_engine(telemetry=True, ragged=False, sink=None, device=None,
                  **over):
    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
    from llmd_kv_cache_tpu.models.llama import LlamaConfig
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetryConfig,
    )

    tiny = LlamaConfig.tiny()
    cfg = dict(model=tiny, num_pages=64, max_pages_per_seq=16, max_batch=4,
               max_prefill_tokens=2 * tiny.page_size,
               pod_identifier="pod-x", ragged_attention=ragged,
               telemetry=EngineTelemetryConfig() if telemetry else None)
    cfg.update(over)
    return MiniEngine(EngineConfig(**cfg), event_sink=sink,
                      device=device), tiny


def _drain(eng, limit=200):
    steps = 0
    while eng.requests and steps < limit:
        eng.step()
        steps += 1
    assert not eng.requests
    return steps


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` on an
    ``EnginePhases``: keeps every phase it was opened for, in the order of
    their starts, as ``[name, attrs, open?]``."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **attrs):
        log = self.seen

        class _Ann:
            def __enter__(self):
                self.rec = [name, dict(attrs), True]
                log.append(self.rec)

            def set_metadata(self, **kw):
                self.rec[1].update(kw)

            def __exit__(self, *exc):
                self.rec[2] = False

        return _Ann()


def _recorded(phases):
    phases._annotation = ann = _Annotations()
    return ann.seen


class TestPhaseFacade:
    def test_off_is_the_shared_noop_and_reads_no_clock(self, monkeypatch):
        assert tracing.phase(None, tracing.PHASE_STEP_INPUTS) is tracing._NOOP_CM
        assert tracing.phase(None, tracing.PHASE_STEP_SNAPSHOT,
                             programs=2) is tracing._NOOP_CM
        with tracing.phase(None, tracing.PHASE_STEP_COMMIT) as sp:
            assert sp is tracing.NOOP_SPAN
            sp.set_attribute("blocks", 3)      # takes it, keeps nothing
        # A phase without a request span stays the no-op even with a
        # traceparent; one with a span opens only that span (itself the
        # no-op here: no exporter, no provider).
        tp = format_traceparent(7, 9)
        assert tracing.phase(None, tracing.PHASE_STEP_FETCH, tp) is tracing._NOOP_CM
        assert tracing.phase(None, tracing.PHASE_STEP_DISPATCH, tp,
                             request_id="r") is tracing._NOOP_CM

        eng, tiny = _phase_engine(telemetry=False)
        assert eng._phases is None and eng.block_manager.phases is None
        reads = []
        for clock in ("perf_counter_ns", "perf_counter", "time_ns"):
            real = getattr(time, clock)
            monkeypatch.setattr(
                time, clock,
                lambda real=real, clock=clock: reads.append(clock) or real())
        opened = []
        monkeypatch.setattr(tracing, "_Phase",
                            lambda *a: opened.append(a) or tracing._NOOP_CM)
        eng.enqueue("a", list(range(1, 3 * tiny.page_size)), max_new_tokens=3)
        _drain(eng)
        assert reads == [] and opened == []

    def test_off_sites_build_nothing(self):
        """The engine's own dispatch site: sizes go in positionally and,
        off, nothing is made of them."""
        eng, _ = _phase_engine(telemetry=False)
        assert eng._dispatch_phase(None, 4, 4, 8) is tracing._NOOP_CM
        import sys

        eng._dispatch_phase(None, 4, 4, 8)      # warm whatever caches
        before = sys.getallocatedblocks()
        for _ in range(1000):
            with eng._dispatch_phase(None, 4, 4, 8):
                pass
            with tracing.phase(None, tracing.PHASE_STEP_SNAPSHOT, programs=2):
                pass
        assert sys.getallocatedblocks() - before <= 2

    def test_on_opens_nested_annotations_in_order_with_attrs(self):
        phases = tracing.EnginePhases("pod-q")
        seen = _recorded(phases)
        phases.begin_step()
        with tracing.phase(phases, tracing.PHASE_STEP_COMMIT,
                           request_id="r") as sp:
            with tracing.phase(phases, tracing.PHASE_STEP_EMIT, events=2):
                phases.transfers += 1
                phases.bytes += 64
                assert [r[2] for r in seen] == [True, True]   # nested
            sp.set_attribute("blocks", 5)
        with tracing.phase(phases, tracing.PHASE_STEP_DISPATCH, programs=1):
            pass
        assert [r[0] for r in seen] == [
            tracing.PHASE_STEP_COMMIT, tracing.PHASE_STEP_EMIT,
            tracing.PHASE_STEP_DISPATCH]
        assert not any(r[2] for r in seen)
        commit, emit, dispatch = (r[1] for r in seen)
        assert emit == {"events": 2, "pod": "pod-q", "step": 1,
                        "transfers": 1, "bytes": 64}
        assert commit["blocks"] == 5 and commit["request_id"] == "r"
        assert commit["transfers"] == 1  # what moved inside it, nested too
        assert dispatch == {"pod": "pod-q", "step": 1, "programs": 1}
        assert phases.programs == 1
        phases.begin_step()
        assert (phases.step, phases.programs, phases.transfers,
                phases.bytes) == (2, 0, 0, 0)

    def test_exception_inside_a_phase_still_closes_it(self):
        phases = tracing.EnginePhases("p")
        seen = _recorded(phases)
        with pytest.raises(RuntimeError):
            with tracing.phase(phases, tracing.PHASE_STEP_FETCH):
                raise RuntimeError("device lost")
        assert seen == [[tracing.PHASE_STEP_FETCH,
                         {"pod": "p", "step": 0}, False]]

    def test_names_are_in_one_place(self):
        listed = {v for k, v in vars(tracing).items()
                  if k.startswith("PHASE_") and isinstance(v, str)}
        assert listed == set(tracing.PHASE_NAMES)
        assert len(set(tracing.PHASE_NAMES)) == len(tracing.PHASE_NAMES)
        assert set(tracing._SPAN_OF_PHASE) <= listed


class TestEnginePhases:
    @pytest.mark.parametrize("telemetry", [False, True])
    def test_traceparent_request_still_yields_engine_spans(self, telemetry):
        with recording_tracing() as exporter:
            with tracer().span("llm_d.kv_cache.request") as root:
                tp = root.traceparent
            eng, tiny = _phase_engine(telemetry=telemetry)
            prompt = list(range(300, 300 + 3 * tiny.page_size))
            eng.enqueue("r1", prompt, max_new_tokens=3, traceparent=tp)
            eng.enqueue("r2", prompt[:9], max_new_tokens=2)  # untraced
            _drain(eng)
            adm = exporter.find("llm_d.kv_cache.engine.admission")
            chunks = exporter.find("llm_d.kv_cache.engine.prefill_chunk")
            decodes = exporter.find("llm_d.kv_cache.engine.decode_step")
        assert len(adm) == 1 and adm[0].attributes["prefix_hit_blocks"] == 0
        # 3 pages of prompt at 2 pages a chunk: two prefill chunks.
        assert [c.attributes["prefill_pos"] for c in chunks] == [
            0, 2 * tiny.page_size]
        assert len(decodes) == 2      # 3 tokens: the first is the prefill's
        for sp in adm + chunks + decodes:
            assert sp.trace_id == root.trace_id
            assert sp.parent_span_id == root.span_id
            assert sp.attributes["request_id"] == "r1"
            assert sp.attributes["process"] == "pod-x"

    @pytest.mark.parametrize("ragged", [False, True])
    def test_every_phase_once_per_program(self, ragged):
        events = []
        eng, tiny = _phase_engine(ragged=ragged, sink=events.extend)
        seen = _recorded(eng._phases)
        page = tiny.page_size
        eng.enqueue("a", list(range(1, 1 + 3 * page)), max_new_tokens=4)
        eng.enqueue("b", list(range(500, 500 + page + 3)), max_new_tokens=3)
        steps = _drain(eng)
        by_step = {}
        for name, attrs, still_open in seen:
            assert not still_open and attrs["pod"] == "pod-x"
            by_step.setdefault(attrs["step"], []).append((name, attrs))
        # Admission happened before the first step.
        assert [n for n, _ in by_step.pop(0)] == 2 * [
            tracing.PHASE_ENQUEUE_ADMIT, tracing.PHASE_ENQUEUE_HASH,
            tracing.PHASE_ENQUEUE_LOOKUP]
        assert sorted(by_step) == list(range(1, steps + 1))
        names_seen = set()
        launched = fetched = 0
        for step, phases in sorted(by_step.items()):
            names = [n for n, _ in phases]
            names_seen.update(names)
            count = names.count
            # Once per step, in this order around everything else.
            assert names[:2] == [tracing.PHASE_STEP_OFFLOAD_POLL,
                                 tracing.PHASE_STEP_SCHEDULE]
            assert names[-1] == tracing.PHASE_STEP_FINISH
            assert count(tracing.PHASE_STEP_FINISH) == 1
            # Once per program dispatched; a program whose tokens the
            # host reads is fetched once. Every program samples inside
            # itself: nothing is dispatched besides the step programs.
            # A lone engine launches a decode program a step ahead, so a
            # step may read a program an earlier step launched: its last
            # one launches nothing.
            programs = count(tracing.PHASE_STEP_DISPATCH)
            fetched += count(tracing.PHASE_STEP_FETCH)
            launched += programs
            assert programs + count(tracing.PHASE_STEP_FETCH) >= 1
            assert count(tracing.PHASE_STEP_INPUTS) == programs
            assert fetched <= launched
            assert set(names) <= set(tracing.PHASE_NAMES)
            finish = phases[-1][1]
            assert finish["programs"] == programs == sum(
                a.get("programs", 0) for _, a in phases[:-1])
            # One transfer a program, and it rides the dispatch phase.
            assert finish["transfers"] == programs == sum(
                a.get("transfers", 0) for n, a in phases
                if n == tracing.PHASE_STEP_DISPATCH)
            assert not any("transfers" in a for n, a in phases
                           if n == tracing.PHASE_STEP_INPUTS)
            for n, a in phases:
                if n == tracing.PHASE_STEP_DISPATCH:
                    assert 0 < a["rows"] and 0 < a["tokens"] <= a["padded"]
                    assert a["bytes"] > 0
            # A commit emits the stored blocks' event batch inside it.
            assert count(tracing.PHASE_STEP_EMIT) == count(
                tracing.PHASE_STEP_COMMIT)
            for at, n in enumerate(names):
                if n == tracing.PHASE_STEP_EMIT:
                    assert names[at - 1] == tracing.PHASE_STEP_COMMIT
        # ``step.snapshot`` is opened for a model that keeps a sequence
        # state beside its pages only (tests/test_gated_deltanet.py),
        # ``step.window`` for one that keeps a window pool beside a global
        # one (tests/test_mellum2.py).
        assert names_seen == {n for n in tracing.PHASE_NAMES
                              if n.startswith("step.")
                              } - {tracing.PHASE_STEP_SNAPSHOT,
                                   tracing.PHASE_STEP_WINDOW} | {
                                  tracing.PHASE_REQUEST_FIRST_TOKEN}
        assert events                  # the sink did receive the batches
        commits = [a for n, a, _ in seen if n == tracing.PHASE_STEP_COMMIT]
        assert sorted(c["blocks"] for c in commits) == [1, 3]
        assert {c["request_id"] for c in commits} == {"a", "b"}
        emits = [a for n, a, _ in seen if n == tracing.PHASE_STEP_EMIT]
        assert all(e["events"] >= 1 for e in emits)

    def test_lookup_counts_its_victims_and_emits_them_once(self):
        """An admission that has to evict carries ``evicted`` on
        ``enqueue.lookup`` and opens one ``step.emit`` inside it, however
        many blocks leave."""
        events = []
        eng, tiny = _phase_engine(sink=events.extend, num_pages=12)
        page = tiny.page_size
        for r in range(3):     # nine idle blocks, two free pages
            eng.enqueue(f"r{r}", list(range(100 * r, 100 * r + 3 * page)),
                        max_new_tokens=1)
            _drain(eng)
        before = eng.block_manager.evictions
        seen = _recorded(eng._phases)
        eng.enqueue("big", list(range(900, 900 + 6 * page + 1)),
                    max_new_tokens=1)
        names = [n for n, _, _ in seen]
        assert names == [tracing.PHASE_ENQUEUE_ADMIT,
                         tracing.PHASE_ENQUEUE_HASH,
                         tracing.PHASE_ENQUEUE_LOOKUP,
                         tracing.PHASE_STEP_EMIT]
        lookup, emit = seen[2][1], seen[3][1]
        assert lookup["blocks"] == 6 and lookup["hit_blocks"] == 0
        # 7 pages + 1 of room against 2 free: 6 victims, one event.
        assert lookup["evicted"] == 6 == eng.block_manager.evictions - before
        assert emit["events"] == 1
        # One that evicts nothing says so.
        del seen[:]
        _drain(eng)
        del seen[:]
        eng.enqueue("hit", list(range(900, 900 + 2 * page)), max_new_tokens=1)
        (lookup,) = [a for n, a, _ in seen
                     if n == tracing.PHASE_ENQUEUE_LOOKUP]
        assert lookup["evicted"] == 0 and lookup["hit_blocks"] == 2

    @pytest.mark.parametrize("backend", ["xla", "pallas", "ragged"])
    def test_a_step_is_one_program_and_one_transfer(self, backend):
        """A decode-only ``step()`` dispatches one program fed by one
        transfer; a step in which a prefill finishes, two and two on the
        padded path (the chunk's program, then the decode program), one and
        one on the ragged path, and never a decode program ahead; and
        where a padded engine that only decodes and has the device to
        itself enters its look-ahead, the next step's decode program too,
        after which a step is one and one again. After a prefill
        ``last_logits`` is the ``[vocab]`` float32 row the benchmark's
        probe reads."""
        over = dict(xla={}, ragged=dict(ragged=True), pallas=dict(
            use_pallas_decode=True, use_pallas_prefill=True))[backend]
        eng, tiny = _phase_engine(**over)
        want = {"xla": "xla", "pallas": "pallas", "ragged": "xla"}[backend]
        assert eng.attention_backends["decode"]["backend"] == want
        seen = _recorded(eng._phases)
        page = tiny.page_size

        def finish_of_step():
            del seen[:]
            emitted = eng.step()
            (finish,) = [a for n, a, _ in seen
                         if n == tracing.PHASE_STEP_FINISH]
            moved = [a.get("transfers", 0) for n, a, _ in seen
                     if n == tracing.PHASE_STEP_DISPATCH]
            return emitted, finish["programs"], finish["transfers"], moved

        a = eng.enqueue("a", list(range(1, 1 + page + 3)), max_new_tokens=6)
        # Step 1: a's one chunk, nothing to decode yet.
        emitted, programs, transfers, moved = finish_of_step()
        assert (programs, transfers, moved) == (1, 1, [1])
        row = np.asarray(a.last_logits, np.float32)
        assert row.shape == (tiny.vocab_size,) and np.isfinite(row).all()
        assert emitted == {"a": int(row.argmax())} and a.output == [
            emitted["a"]]
        # Step 2: decode only.
        emitted, programs, transfers, moved = finish_of_step()
        assert (programs, transfers, moved) == (1, 1, [1])
        assert list(emitted) == ["a"] and len(a.output) == 2
        # Step 3: b's prefill finishes beside a's decode.
        b = eng.enqueue("b", list(range(700, 700 + page)), max_new_tokens=2)
        emitted, programs, transfers, moved = finish_of_step()
        both = 1 if backend == "ragged" else 2
        assert (programs, transfers, moved) == (both, both, [1] * both)
        assert set(emitted) == {"a", "b"}
        if backend != "ragged":
            # A request in prefill: nothing is launched ahead.
            assert [a.get("ahead") for n, a, _ in seen
                    if n == tracing.PHASE_STEP_DISPATCH] == [None, 0]
            assert eng._unread is None
        row_b = np.asarray(b.last_logits, np.float32)
        assert row_b.shape == (tiny.vocab_size,)
        assert b.output == [int(row_b.argmax())]
        # a's row is still the one its prefill left.
        np.testing.assert_array_equal(
            np.asarray(a.last_logits, np.float32), row)
        # Step 4: both decode; the padded engine launches the next step's
        # program too (without b, whose unread token is its last) ...
        emitted, programs, transfers, moved = finish_of_step()
        assert (programs, transfers, moved) == (both, both, [1] * both)
        assert set(emitted) == {"a", "b"} and len(a.output) == 4
        if backend != "ragged":
            assert [(a["ahead"], a["rows"]) for n, a, _ in seen
                    if n == tracing.PHASE_STEP_DISPATCH] == [(0, 2), (1, 1)]
        # ... and from then on a step is one program launched, one read.
        emitted, programs, transfers, moved = finish_of_step()
        assert (programs, transfers, moved) == (1, 1, [1])
        assert set(emitted) == {"a"} and len(a.output) == 5
        _drain(eng)
        assert len(a.output) == 6 and len(b.output) == 2

    def test_phases_land_in_a_profiler_capture(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        eng, tiny = _phase_engine()
        eng.enqueue("warm", list(range(1, 2 * tiny.page_size)),
                    max_new_tokens=2)
        _drain(eng)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.enqueue("a", list(range(1, 2 * tiny.page_size)),
                        max_new_tokens=2)
            _drain(eng)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in tracing.PHASE_NAMES:
                        found.setdefault(ev.name, dict(ev.stats))
        assert {tracing.PHASE_STEP_INPUTS, tracing.PHASE_STEP_DISPATCH,
                tracing.PHASE_STEP_FETCH, tracing.PHASE_STEP_FINISH} <= set(found)
        assert found[tracing.PHASE_STEP_DISPATCH]["pod"] == "pod-x"
        assert int(found[tracing.PHASE_STEP_DISPATCH]["transfers"]) > 0
        # A program's owner rides the capture as the other attributes do.
        assert found[tracing.PHASE_STEP_DISPATCH]["program"] == "forward"
        assert int(found[tracing.PHASE_STEP_FETCH]["launch"]) >= int(
            found[tracing.PHASE_STEP_DISPATCH]["launch"]) > 0


# -- launches: a program's owner (step.dispatch / step.fetch) -----------------


class TestLaunches:
    @pytest.mark.parametrize("backend", ["xla", "pallas", "ragged"])
    def test_programs_name_and_number_themselves_across_engines(
            self, backend, monkeypatch):
        """Two replicas on one device stepped in turn: every dispatch takes
        the next ``launch`` of the device, whichever engine it is; a fetch
        names the launch it waits for; a chunk nobody reads has none;
        ``program`` is the name the jit gives the module."""
        from llmd_kv_cache_tpu.models import engine, llama

        monkeypatch.setattr(engine, "_launch_counts", {})
        over = dict(xla={}, ragged=dict(ragged=True), pallas=dict(
            use_pallas_decode=True, use_pallas_prefill=True))[backend]
        a, tiny = _phase_engine(pod_identifier="pod-a", **over)
        b, _ = _phase_engine(pod_identifier="pod-b", **over)
        assert a._launches is b._launches
        a._phases._annotation = b._phases._annotation = ann = _Annotations()
        page = tiny.page_size
        # Three pages at two a chunk: the first chunk's token is not read.
        a.enqueue("a", list(range(1, 1 + 3 * page)), max_new_tokens=3)
        b.enqueue("b", list(range(500, 500 + 3 * page)), max_new_tokens=3)
        while a.requests or b.requests:
            for eng in (a, b):
                if eng.requests:
                    eng.step()
        dispatches = [r[1] for r in ann.seen
                      if r[0] == tracing.PHASE_STEP_DISPATCH]
        fetches = [r[1] for r in ann.seen if r[0] == tracing.PHASE_STEP_FETCH]
        launches = [d["launch"] for d in dispatches]
        assert launches == list(range(1, len(dispatches) + 1))
        assert {d["pod"] for d in dispatches} == {"pod-a", "pod-b"}
        by_launch = {d["launch"]: d for d in dispatches}
        for f in fetches:        # the same engine's, in the same step
            d = by_launch[f["launch"]]
            assert (d["pod"], d["step"]) == (f["pod"], f["step"])
        assert len({f["launch"] for f in fetches}) == len(fetches)
        unread = [d for d in dispatches
                  if d["launch"] not in {f["launch"] for f in fetches}]
        if backend == "ragged":
            want = {llama.forward_ragged.__name__}
        else:
            # Each engine's first chunk, and nothing else.
            assert [(d["pod"], d["prefill_pos"]) for d in unread] == [
                ("pod-a", 0), ("pod-b", 0)]
            want = ({llama.PROGRAM_PREFILL, llama.PROGRAM_DECODE}
                    if backend == "pallas" else {llama.forward.__name__})
        assert {d["program"] for d in dispatches} == want
        if backend == "pallas":
            assert all(
                d["program"] == (llama.PROGRAM_PREFILL if "prefill_pos" in d
                                 else llama.PROGRAM_DECODE)
                for d in dispatches)

    def test_a_device_has_its_own_count(self, monkeypatch):
        from llmd_kv_cache_tpu.models import engine

        import jax

        monkeypatch.setattr(engine, "_launch_counts", {})
        cpu = jax.devices()[0]
        one, _ = _phase_engine(device=cpu)
        other, _ = _phase_engine()         # JAX's default: a key of its own
        same, _ = _phase_engine(device=cpu)
        assert one._launches is same._launches is not other._launches
        packed = np.zeros(1, np.int32)
        assert [eng._launch_input(packed, tracing.NOOP_SPAN) is not None
                and eng._launch for eng in (one, other, same, one)] == [
                    1, 1, 2, 3]
        assert (one._launch, other._launch, same._launch) == (3, 1, 2)

    def test_off_numbers_and_opens_nothing(self, monkeypatch):
        """An untraced engine numbers its programs too (it reads from the
        numbers whether it has the chip to itself) and opens no phase."""
        from llmd_kv_cache_tpu.models import engine

        monkeypatch.setattr(engine, "_launch_counts", {})
        eng, tiny = _phase_engine(telemetry=False)
        eng.enqueue("a", list(range(1, 2 * tiny.page_size)), max_new_tokens=2)
        _drain(eng)
        assert eng._launch == 2 and eng._phases is None
        assert eng._fetch_phase(eng._launch) is tracing._NOOP_CM


# -- a request's way to its first token (``request.first_token``) -------------


def _markers(seen):
    return [a for n, a, _ in seen if n == tracing.PHASE_REQUEST_FIRST_TOKEN]


def _chunks_of(seen, rid):
    return [a for n, a, _ in seen if n == tracing.PHASE_STEP_DISPATCH
            and a.get("request_id") == rid]


MARKER_KEYS = {"pod", "step", "request_id", "prompt_tokens", "cached_tokens",
               "chunks", "first_launch", "last_launch", "decodes_between",
               "behind_chunks", "queued_ns", "behind_ns", "prefill_ns"}


class TestFirstTokenMarker:
    @pytest.fixture(scope="class")
    def three(self):
        """Three requests enqueued at once into one engine with phases:
        ``a`` of three chunks, ``b`` of two, ``c`` of one, each long enough
        to decode while the next prefills."""
        from llmd_kv_cache_tpu.models import engine

        counts, engine._launch_counts = engine._launch_counts, {}
        try:
            eng, tiny = _phase_engine()
            seen = _recorded(eng._phases)
            page = tiny.page_size
            prompts = {"a": list(range(1, 1 + 5 * page)),
                       "b": list(range(500, 500 + 3 * page + 3)),
                       "c": list(range(900, 900 + page + 3))}
            for rid, prompt in prompts.items():
                eng.enqueue(rid, prompt, max_new_tokens=8)
            _drain(eng)
        finally:
            engine._launch_counts = counts
        return eng, seen, prompts

    def test_one_marker_a_request_inside_its_commit(self, three):
        _, seen, prompts = three
        marks = _markers(seen)
        assert [m["request_id"] for m in marks] == ["a", "b", "c"]
        for m in marks:
            assert set(m) == MARKER_KEYS and m["pod"] == "pod-x"
            assert m["prompt_tokens"] == len(prompts[m["request_id"]])
            assert m["cached_tokens"] == 0
        names = [n for n, _, _ in seen]
        for at, n in enumerate(names):
            if n == tracing.PHASE_REQUEST_FIRST_TOKEN:
                # Opened while the request's ``step.commit`` was open, in
                # the same step, and closed at once.
                commit = max(i for i in range(at)
                             if names[i] == tracing.PHASE_STEP_COMMIT)
                assert seen[commit][1]["request_id"] == seen[at][1][
                    "request_id"]
                assert seen[commit][1]["step"] == seen[at][1]["step"]
                assert seen[at][2] is False

    def test_chunks_and_launches_are_its_dispatches(self, three):
        _, seen, _ = three
        for m in _markers(seen):
            own = _chunks_of(seen, m["request_id"])
            assert m["chunks"] == len(own) >= 1
            assert m["first_launch"] == own[0]["launch"]
            assert m["last_launch"] == own[-1]["launch"]
        assert [m["chunks"] for m in _markers(seen)] == [3, 2, 1]

    def test_a_request_stands_behind_the_chunks_ahead_of_it(self, three):
        _, seen, _ = three
        a, b, c = _markers(seen)
        assert (a["behind_chunks"], a["behind_ns"]) == (0, 0)
        assert b["behind_chunks"] == a["chunks"]
        assert c["behind_chunks"] == a["chunks"] + b["chunks"]
        for m in (a, b, c):
            assert 0 <= m["behind_ns"] <= m["queued_ns"]
            assert m["prefill_ns"] > 0
        # The steps ``b`` stood behind are ``a``'s whole prefill.
        assert 0 < b["behind_ns"] < c["behind_ns"]
        assert b["queued_ns"] < c["queued_ns"]
        assert a["queued_ns"] < b["behind_ns"]

    def test_decodes_between_are_the_decode_programs_beside_its_chunks(
            self, three):
        _, seen, _ = three
        a, b, c = _markers(seen)
        assert a["decodes_between"] == 0          # nothing decoded yet
        for m in (b, c):
            first = next(i for i, (n, at, _) in enumerate(seen)
                         if n == tracing.PHASE_STEP_DISPATCH
                         and at.get("request_id") == m["request_id"])
            last = next(i for i, (n, at, _) in enumerate(seen)
                        if n == tracing.PHASE_REQUEST_FIRST_TOKEN
                        and at["request_id"] == m["request_id"])
            decodes = [at for n, at, _ in seen[first:last]
                       if n == tracing.PHASE_STEP_DISPATCH
                       and "request_id" not in at]
            assert m["decodes_between"] == len(decodes)
        # ``a`` decodes one step beside each of ``b``'s chunks.
        assert b["decodes_between"] == b["chunks"]
        assert c["decodes_between"] == c["chunks"]

    def test_debug_vars_show_the_same_split_untraced(self, three):
        eng, seen, _ = three
        recent = {r["request_id"]: r for r in
                  eng.telemetry.debug_vars()["requests"]["recent"]}
        for m in _markers(seen):
            r = recent[m["request_id"]]
            for key in ("chunks", "first_launch", "last_launch",
                        "decodes_between", "behind_chunks", "prompt_tokens",
                        "cached_tokens"):
                assert r[key] == m[key], key
            assert int(r["queued_s"] * 1e9) == m["queued_ns"]
            assert int(r["behind_s"] * 1e9) == m["behind_ns"]
            assert int((r["first_token_ts"] - r["sched_ts"]) * 1e9) == m[
                "prefill_ns"]
            # No gate held any of them: the step that first picked a
            # request (``admit_ts`` is its start) ran its first chunk.
            assert r["enqueue_ts"] <= r["admit_ts"] == r["sched_ts"] <= r[
                "first_token_ts"]

    def test_a_prefix_hit_carries_what_was_cached(self):
        eng, tiny = _phase_engine()
        seen = _recorded(eng._phases)
        page = tiny.page_size
        prompt = list(range(1, 1 + 4 * page))
        eng.enqueue("first", prompt, max_new_tokens=2)
        _drain(eng)
        req = eng.enqueue("again", prompt + [7, 8, 9], max_new_tokens=2)
        assert req.cached_len == 4 * page
        _drain(eng)
        first, again = _markers(seen)
        assert (first["cached_tokens"], first["chunks"]) == (0, 2)
        assert again["cached_tokens"] == 4 * page
        assert again["prompt_tokens"] == 4 * page + 3
        assert again["chunks"] == 1 == len(_chunks_of(seen, "again"))

    def test_a_gate_that_holds_the_head_is_queued_not_prefill(self):
        """A restore gate (stood in for here) holds ``late`` at the head of
        the queue for three steps after its first pick while ``early``
        decodes: those steps are ``late``'s wait, not its prefill, and their
        decode programs do not stand between its chunks."""
        import time

        eng, tiny = _phase_engine()
        seen = _recorded(eng._phases)
        page = tiny.page_size
        eng.enqueue("early", list(range(1, 1 + page)), max_new_tokens=12)
        eng.step()
        late = eng.enqueue("late", list(range(500, 500 + 3 * page)),
                           max_new_tokens=2)
        held = []

        def gate(req):
            held.append(time.monotonic())
            if len(held) <= 3:
                time.sleep(0.01)
                return False
            req.restore_job = None
            return True

        late.restore_job, eng._poll_deferred_restore = object(), gate
        _drain(eng)
        early, m = _markers(seen)
        assert (early["request_id"], m["request_id"]) == ("early", "late")
        assert len(held) == 4
        # Three steps of 10 ms and more before its first chunk's step.
        assert m["queued_ns"] >= 30e6 and m["behind_ns"] == 0
        first = next(i for i, (n, at, _) in enumerate(seen)
                     if n == tracing.PHASE_STEP_DISPATCH
                     and at.get("request_id") == "late")
        decodes = [i for i, (n, at, _) in enumerate(seen)
                   if n == tracing.PHASE_STEP_DISPATCH
                   and "request_id" not in at]
        gated = [i for i in decodes if i < first]
        assert len(gated) >= 3
        assert m["chunks"] == 2 and m["decodes_between"] == 2
        st = next(r for r in eng.telemetry.debug_vars()["requests"]["recent"]
                  if r["request_id"] == "late")
        assert st["admit_ts"] <= held[0] < held[3] and (
            st["admit_ts"] < st["sched_ts"] <= held[3])

    def test_add_request_emits_it_with_nothing_queued(self):
        eng, tiny = _phase_engine()
        seen = _recorded(eng._phases)
        prompt = list(range(1, 1 + 3 * tiny.page_size))
        eng.add_request("sync", prompt, max_new_tokens=2)
        (m,) = _markers(seen)
        assert set(m) == MARKER_KEYS
        assert (m["queued_ns"], m["behind_ns"], m["behind_chunks"],
                m["decodes_between"]) == (0, 0, 0, 0)
        own = _chunks_of(seen, "sync")
        assert m["chunks"] == len(own) == 2
        assert (m["first_launch"], m["last_launch"]) == (
            own[0]["launch"], own[-1]["launch"])
        assert m["prefill_ns"] > 0 and m["step"] == 0

    def test_a_ragged_chunk_carries_the_decode_rows(self):
        """The ragged scheduler's program that holds a chunk is a chunk:
        the rows that decode ride it, and no decode program stands between
        a pick and its first token."""
        eng, tiny = _phase_engine(ragged=True)
        seen = _recorded(eng._phases)
        page = tiny.page_size
        eng.enqueue("a", list(range(1, 1 + 3 * page)), max_new_tokens=6)
        eng.enqueue("b", list(range(500, 500 + 3 * page)), max_new_tokens=3)
        _drain(eng)
        a, b = _markers(seen)
        assert (a["chunks"], b["chunks"]) == (2, 2)
        assert b["behind_chunks"] == a["chunks"]
        assert b["decodes_between"] == 0 and b["behind_ns"] <= b["queued_ns"]
        assert len(_chunks_of(seen, "b")) == 2

    def test_without_telemetry_no_record_is_built_and_nothing_opens(
            self, monkeypatch):
        from llmd_kv_cache_tpu.telemetry import engine_telemetry

        built, opened = [], []
        real = engine_telemetry._ReqState.__init__
        monkeypatch.setattr(
            engine_telemetry._ReqState, "__init__",
            lambda self, *a, **kw: built.append(a) or real(self, *a, **kw))
        monkeypatch.setattr(tracing, "_Phase",
                            lambda *a: opened.append(a) or tracing._NOOP_CM)
        eng, tiny = _phase_engine(telemetry=False)
        assert eng.telemetry is None and eng._phases is None
        eng.enqueue("a", list(range(1, 3 * tiny.page_size)), max_new_tokens=3)
        eng.add_request("b", list(range(50, 60)), max_new_tokens=2)
        _drain(eng)
        assert built == [] and opened == []


class TestFirstTokenClock:
    """``EngineTelemetry``'s record of a request's way to its first token,
    driven by hand: the hooks the engine calls, in its order."""

    @staticmethod
    def _tel():
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetry,
            EngineTelemetryConfig,
        )

        return EngineTelemetry(EngineTelemetryConfig(flight_records=False))

    def test_behind_counts_only_steps_that_ran_another_requests_chunk(self):
        tel = self._tel()
        tel.on_admitted("old", 0)
        tel.on_admitted("new", 0)
        t0 = tel.begin_step()
        tel.on_first_schedule("old", t0 - 0.002)
        tel.on_step(0.010, False, (), True)      # old's chunk: new waits
        tel.begin_step()
        tel.on_step(0.004, True, (), False)      # a gate held old: no chunk
        tel.begin_step()
        tel.on_step(0.020, True, (), True)
        new, old = tel._requests["new"], tel._requests["old"]
        assert (new.behind_chunks, old.behind_chunks) == (2, 0)
        assert new.behind_s == pytest.approx(0.030) and old.behind_s == 0.0
        assert old.queued_s == pytest.approx(0.002)
        t1 = tel.begin_step()
        tel.on_first_schedule("new", t1 - 0.5)
        tel.on_dispatch("new")
        tel.on_step(0.010, True, (), True)       # its own chunk
        assert new.behind_chunks == 2 and new.queued_s == pytest.approx(0.5)
        # A second pick of a request, and its second chunk, change nothing
        # of its first.
        tel.begin_step()
        tel.on_first_schedule("new", 0.0)
        tel.on_dispatch("new")
        assert new.admit_ts == new.sched_ts == t1
        assert new.queued_s == pytest.approx(0.5)

    def test_a_gate_after_the_pick_is_part_of_the_wait(self):
        """A restore or handoff gate holds the queue's head after its first
        pick: no chunk runs, the engine decodes on, and the request's wait
        ends with the step that runs its first chunk; the decode programs
        of the steps it was held are not between its chunks."""
        tel = self._tel()
        tel.on_admitted("r", 0)
        tel.on_admitted("next", 0)
        t0 = tel.begin_step()
        tel.on_first_schedule("r", t0 - 0.003)
        tel.on_dispatch(None)
        tel.on_step(0.004, True, (), False)      # held: a decode step alone
        st = tel._requests["r"]
        assert (st.admit_ts, st.sched_ts) == (t0, None)
        tel.begin_step()
        tel.on_first_schedule("r", None)
        tel.on_dispatch(None)
        tel.on_step(0.004, True, (), False)      # held again
        t2 = tel.begin_step()
        tel.on_dispatch("r")                     # the gate let it through
        tel.on_launch(11)
        tel.on_dispatch(None)
        tel.on_step(0.012, True, (), True)
        assert (st.admit_ts, st.sched_ts) == (t0, t2)
        assert st.queued_s == pytest.approx(0.003 + (t2 - t0))
        tel.begin_step()
        tel.on_dispatch("r")
        tel.on_launch(13)
        split = tel.on_first_token("r", 64, 32).first_token_split()
        assert split["decodes_between"] == 1 and split["chunks"] == 2
        assert split["queued_ns"] == int(st.queued_s * 1e9)
        assert split["prefill_ns"] == int((st.first_token_ts - t2) * 1e9)
        # Nobody's chunk ran while the gate held the head: the one behind
        # it stood behind the one step that ended with a chunk of ``r``.
        assert tel._requests["next"].behind_chunks == 1
        assert split["behind_ns"] == 0

    def test_launches_go_to_the_request_whose_chunk_was_announced(self):
        tel = self._tel()
        tel.on_admitted("r", 0)
        tel.begin_step()
        tel.on_first_schedule("r", None)
        tel.on_launch(3)                 # nothing announced: nobody's
        tel.on_dispatch("r")
        tel.on_launch(4)
        tel.on_dispatch(None)            # a decode program
        tel.on_launch(5)
        tel.on_dispatch(None)
        tel.on_launch(6)
        tel.on_dispatch("r")
        tel.on_launch(9)
        tel.on_dispatch("gone")          # an unknown request: nobody's
        tel.on_launch(10)
        st = tel.on_first_token("r", prompt_tokens=40, cached_tokens=16)
        assert st is tel._requests["r"]
        split = st.first_token_split()
        assert split == {
            "request_id": "r", "prompt_tokens": 40, "cached_tokens": 16,
            "chunks": 2, "first_launch": 4, "last_launch": 9,
            "decodes_between": 2, "behind_chunks": 0, "queued_ns": 0,
            "behind_ns": 0, "prefill_ns": split["prefill_ns"]}
        assert split["prefill_ns"] >= 0
        assert tel.on_first_token("unknown") is None

    def test_decodes_before_the_first_chunk_are_not_between(self):
        tel = self._tel()
        tel.on_admitted("r", 0)
        for _ in range(5):
            tel.on_dispatch(None)
        tel.begin_step()
        tel.on_first_schedule("r", None)
        tel.on_dispatch("r")
        tel.on_dispatch(None)
        assert tel.on_first_token("r").decodes_between == 1

    @pytest.mark.parametrize("picked", [False, True])
    def test_a_request_that_ends_before_its_first_token(self, picked):
        """Nothing stands between a pick and a first token that never came."""
        tel = self._tel()
        tel.on_admitted("r", 0)
        tel.on_dispatch(None)
        if picked:
            tel.begin_step()
            tel.on_first_schedule("r", None)
            tel.on_dispatch("r")
            tel.on_launch(7)
            tel.on_dispatch(None)
        tel.on_finish("r", "aborted")
        (summary,) = tel.finished
        assert summary["first_token_ts"] is None
        assert summary["decodes_between"] == 0
        assert summary["chunks"] == int(picked)
        assert (summary["sched_ts"] is not None) is picked

    def test_the_synchronous_path_is_its_own_first_pick(self):
        tel = self._tel()
        tel.on_admitted("r", 2)
        st = tel.on_first_token("r", 24, 16)
        assert st.admit_ts == st.sched_ts == st.enqueue_ts
        assert (st.queued_s, st.decodes_between) == (0.0, 0)
        assert st.first_token_split()["prefill_ns"] == int(
            (st.first_token_ts - st.enqueue_ts) * 1e9)


# -- the router's and the pool's phases ---------------------------------------


def _router_and_pool(pods=("pod-0", "pod-1")):
    from llmd_kv_cache_tpu.core import TokenProcessorConfig
    from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
    from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig
    from llmd_kv_cache_tpu.scoring.router import KVAwareRouter

    indexer = Indexer(IndexerConfig(
        token_processor_config=TokenProcessorConfig(block_size_tokens=4)))
    pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                indexer.token_processor)
    return KVAwareRouter(indexer, list(pods)), pool


def _store(pool, pod, tokens, first_hash=100):
    from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch

    blocks = len(tokens) // 4
    pool.process_event_batch(EventBatch(timestamp=time.time(), events=[
        BlockStoredEvent(block_hashes=list(range(first_hash,
                                                 first_hash + blocks)),
                         tokens=list(tokens), block_size=4)]), pod, "m")


class TestControlPlanePhases:
    ROUTE = (tracing.PHASE_ROUTE_DECIDE, tracing.PHASE_ROUTE_EXPIRE,
             tracing.PHASE_ROUTE_HASH, tracing.PHASE_ROUTE_LOOKUP,
             tracing.PHASE_ROUTE_SCORE, tracing.PHASE_ROUTE_SPECULATE)

    @pytest.fixture
    def recorded(self, monkeypatch):
        """The process's owner switched on, its annotations recorded."""
        owner = tracing.Phases()
        owner._annotation = ann = _Annotations()
        monkeypatch.setattr(tracing, "_process_phases", owner)
        return ann.seen

    def test_route_opens_each_phase_once_nested_in_order(self, recorded):
        router, pool = _router_and_pool()
        prompt = list(range(1, 1 + 5 * 4))
        _store(pool, "pod-1", prompt[:12])
        del recorded[:]
        opened_under = []
        real = tracing._Phase.__enter__

        def enter(self):
            opened_under.append([r[0] for r in recorded if r[2]])
            return real(self)

        tracing._Phase.__enter__ = enter
        try:
            assert router.route(prompt, "m") == "pod-1"
        finally:
            tracing._Phase.__enter__ = real
        assert tuple(r[0] for r in recorded) == self.ROUTE
        assert not any(r[2] for r in recorded)
        # The five parts open inside the decision, one after another.
        assert opened_under == [[]] + 5 * [[tracing.PHASE_ROUTE_DECIDE]]
        decide = recorded[0][1]
        assert decide == {"keys": 5, "pods": 2, "pod": "pod-1", "best": 3.0,
                          "speculative": 5, "expired": 0}
        # Neither pod nor step of an engine on the parts.
        assert all(r[1] == {} for r in recorded[1:])

    def test_round_robin_reads_best_0_and_expiry_is_counted(self, recorded):
        router, _ = _router_and_pool()
        router.config.speculative_ttl_s = 0.0
        assert router.route(list(range(1, 9)), "m") == "pod-0"
        first = recorded[0][1]
        assert (first["best"], first["keys"], first["speculative"]) == (0.0, 2, 2)
        del recorded[:]
        # No full block: nothing to look up, and the two entries expired.
        assert router.route([1, 2], "m") == "pod-1"
        assert tuple(r[0] for r in recorded) == tuple(
            n for n in self.ROUTE if n != tracing.PHASE_ROUTE_LOOKUP)
        assert recorded[0][1] == {"keys": 0, "pods": 2, "pod": "pod-1",
                                  "best": 0.0, "speculative": 0, "expired": 2}

    def test_ingest_carries_pod_events_and_keys(self, recorded):
        from llmd_kv_cache_tpu.events.model import (
            AllBlocksClearedEvent, BlockRemovedEvent, BlockStoredEvent,
            EventBatch)

        _, pool = _router_and_pool()
        pool.process_event_batch(EventBatch(timestamp=time.time(), events=[
            BlockStoredEvent(block_hashes=[1, 2, 3],
                             tokens=list(range(12)), block_size=4),
            BlockRemovedEvent(block_hashes=[1, 2]),
            AllBlocksClearedEvent()]), "pod-7", "m")
        assert recorded == [[tracing.PHASE_INGEST,
                             {"pod": "pod-7", "events": 3, "keys": 5}, False]]

    def test_off_they_are_the_shared_noop_and_build_nothing(
            self, monkeypatch):
        """No engine of the process has phases: every site of the router
        and the pool is the engine's off path."""
        import sys

        monkeypatch.setattr(tracing, "_process_phases", None)
        assert tracing.process_phases() is None
        opened = []
        monkeypatch.setattr(tracing, "_Phase",
                            lambda *a: opened.append(a) or tracing._NOOP_CM)
        router, pool = _router_and_pool()
        prompt = list(range(1, 1 + 5 * 4))
        _store(pool, "pod-1", prompt[:12])
        assert router.route(prompt, "m") == "pod-1"
        assert opened == []
        names = (*self.ROUTE, tracing.PHASE_INGEST)
        for name in names:
            assert tracing.phase(tracing.process_phases(),
                                 name) is tracing._NOOP_CM
        before = sys.getallocatedblocks()
        for _ in range(1000):
            for name in names:
                with tracing.phase(router._phases or tracing.process_phases(),
                                   name):
                    pass
        assert sys.getallocatedblocks() - before <= 2

    def test_on_with_the_first_engine_that_has_phases(self, monkeypatch):
        """As the benchmark's fleet builds them: the pool before the
        engines, the router after, neither told anything."""
        monkeypatch.setattr(tracing, "_process_phases", None)
        router, pool = _router_and_pool()
        _phase_engine(telemetry=False)
        assert tracing.process_phases() is None
        eng, _ = _phase_engine(telemetry=True)
        owner = tracing.process_phases()
        assert isinstance(owner, tracing.Phases) and owner is not eng._phases
        _phase_engine(telemetry=True)
        assert tracing.process_phases() is owner        # one a process
        owner._annotation = ann = _Annotations()
        _store(pool, "pod-0", list(range(1, 9)))
        router.route(list(range(1, 9)), "m")
        assert [r[0] for r in ann.seen] == [tracing.PHASE_INGEST, *self.ROUTE]

    def test_a_router_takes_an_owner_of_its_own(self, monkeypatch):
        """A process without engines (the scorer's): the caller says so."""
        from llmd_kv_cache_tpu.scoring.router import KVAwareRouter

        monkeypatch.setattr(tracing, "_process_phases", None)
        router, _ = _router_and_pool()
        mine = tracing.Phases()
        mine._annotation = ann = _Annotations()
        router = KVAwareRouter(router.indexer, router.pods, phases=mine)
        router.route(list(range(1, 9)), "m")
        assert [r[0] for r in ann.seen][0] == tracing.PHASE_ROUTE_DECIDE
        assert tracing.process_phases() is None
