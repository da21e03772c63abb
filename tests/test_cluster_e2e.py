"""Multi-process cluster e2e: the chart's topology as OS processes.

VERDICT r2 #6 (match: the reference's Kind cluster run,
``tests/kind-vllm-cpu.sh:15-60``, and
``examples/kv_cache_index_service/server/server.go:42-65``): an indexer
gRPC service, three engine pods (separate Python processes publishing KV
events over real ZMQ), and an evictor, all sharing one storage root.
Scores are read over the gRPC wire; one pod is SIGKILLed mid-run and a
replacement restores a previously-served prefix bit-exactly from the
shared storage tier.

``TestClusterTopology`` is marked slow (three subprocess engine inits,
~15 s each on first jit). ``TestShardedClusterE2E`` is the fast tier-1
counterpart for the sharded control plane: four in-process indexer shard
replicas behind real gRPC servers, scatter-gather scoring through
``ShardRouter``, one shard killed mid-run with zero scoring outage, then
rejoined via snapshot bootstrap + cross-replica anti-entropy.
"""

import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
MODEL = "tiny"
ZMQ_PORT = 15910
GRPC_PORT = 15911
ADMIN_PORT = 15912


@pytest.fixture(autouse=True)
def _no_sli_history():
    """The collectors below scrape this process's own metric registry, and
    a target's first scrape counts its whole history as one round: slow
    restores, TTFTs or scores left in the three SLI families by a test file
    that ran earlier in the same worker fire an alert in a fleet these
    tests assert is healthy (``kvdiag --fleet`` then exits 3). Each test
    starts from empty families: the labelled one is cleared, the two
    get-or-create histograms are forgotten (the services and engines a test
    builds create them anew)."""
    from llmd_kv_cache_tpu.metrics import collector

    collector.OFFLOAD_RESTORE_SECONDS.clear()
    collector.forget_bucket_histograms("kvtpu_engine_ttft_seconds",
                                       "kvcache_score_latency_seconds")


def wait_until(cond, timeout=60.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def spawn(argv, **kw):
    env = dict(os.environ)
    # One process per chip: children of a test run stay on the CPU.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    return subprocess.Popen(
        argv, env=env, cwd=str(REPO),
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, **kw)


def start_pod(pod_id, control, store, admin=False):
    argv = [
        sys.executable, "examples/engine_pod_main.py",
        "--pod-id", pod_id,
        "--zmq-endpoint", f"tcp://127.0.0.1:{ZMQ_PORT}",
        "--control-dir", str(control),
        "--model-name", MODEL,
        "--offload-root", str(store),
    ]
    if admin:
        argv += ["--admin-port", "auto"]
    return spawn(argv)


def serve_on(control, pod_id, name, prompt, timeout=90.0):
    # 90 s: a pod's FIRST serve includes its prefill jit compile, which
    # under full-suite CPU contention (3 engine pods + indexer + evictor
    # as OS processes) has been observed to exceed 30 s; wait_until
    # returns the moment the reply lands, so the slack is free.
    req = control / f"{pod_id}.{name}.req.json"
    out = control / f"{pod_id}.{name}.out.json"
    req.write_text(json.dumps({
        "request_id": name, "prompt": prompt, "max_new_tokens": 4}))
    assert wait_until(out.exists, timeout=timeout), f"{pod_id} never served {name}"
    return json.loads(out.read_text())["output"]


@pytest.mark.slow
class TestClusterTopology:
    def test_cluster_scores_converge_and_survive_pod_restart(self, tmp_path):
        control = tmp_path / "ctl"
        store = tmp_path / "store"
        control.mkdir()
        store.mkdir()
        procs = {}
        try:
            procs["indexer"] = spawn([
                sys.executable, "examples/indexer_service_main.py",
                "--zmq-endpoint", f"tcp://127.0.0.1:{ZMQ_PORT}",
                "--grpc-address", f"127.0.0.1:{GRPC_PORT}",
                "--block-size", "4",
                "--admin-port", str(ADMIN_PORT),
            ])
            for pod in ("pod-0", "pod-1", "pod-2"):
                # pod-0 gets the admin endpoint so kvdiag's engine section
                # can be exercised against a live serving pod below.
                procs[pod] = start_pod(pod, control, store,
                                       admin=(pod == "pod-0"))
            assert wait_until(
                lambda: all((control / f"pod-{i}.ready").exists()
                            for i in range(3)),
                timeout=90.0), "pods never became ready"

            # Each pod serves its own prompt; KV events flow pod → ZMQ →
            # indexer pool → index.
            prompts = {f"pod-{i}": list(range(10 * (i + 1), 10 * (i + 1) + 8))
                       for i in range(3)}
            outputs = {p: serve_on(control, p, "r1", prompts[p])
                       for p in prompts}

            from llmd_kv_cache_tpu.services.indexer_service import (
                IndexerServiceClient,
            )

            client = IndexerServiceClient(f"127.0.0.1:{GRPC_PORT}")
            try:
                # Convergent scores over the gRPC wire: each prompt's top
                # score lands on the pod that served it.
                for pod, prompt in prompts.items():
                    assert wait_until(
                        lambda p=pod, t=prompt: (
                            lambda s: s and max(s, key=s.get) == p
                        )(client.get_pod_scores(t, MODEL)),
                        timeout=20.0), f"scores never converged onto {pod}"

                # Live-cluster diagnostic snapshot: kvdiag against the
                # indexer's admin endpoint must surface the flight
                # recorder, per-pod event lag, and the efficiency ledger.
                diag = subprocess.run(
                    [sys.executable, "hack/kvdiag.py",
                     "--port", str(ADMIN_PORT)],
                    cwd=str(REPO), capture_output=True, text=True, timeout=30)
                assert diag.returncode == 0, diag.stderr
                report = json.loads(diag.stdout)
                assert report["healthz"]["body"] == {"status": "ok"}
                records = report["debug"]["flight_recorder"]
                assert any(r["kind"] == "score" for r in records)
                lag_pods = report["debug"]["lag"]["pods"]
                assert {"pod-0", "pod-1", "pod-2"} <= set(lag_pods)
                assert all(p["messages"] > 0 for p in lag_pods.values())
                ledger = report["debug"]["ledger"]
                assert ledger["score_calls"] > 0
                assert set(ledger["pods"]) & {"pod-0", "pod-1", "pod-2"}
                assert any(name.startswith("kvcache_")
                           for name in report["metrics"])

                # kvdiag against an ENGINE pod's admin endpoint: the
                # report grows a top-level engine summary (KV-pool
                # occupancy + request phase percentiles) fed by the
                # telemetry layer, and the kvtpu_engine_* families are
                # exposed on /metrics.
                pod0_admin = int(
                    (control / "pod-0.admin_port").read_text())
                diag = subprocess.run(
                    [sys.executable, "hack/kvdiag.py",
                     "--port", str(pod0_admin)],
                    cwd=str(REPO), capture_output=True, text=True, timeout=30)
                assert diag.returncode == 0, diag.stderr
                engine_report = json.loads(diag.stdout)
                eng = engine_report["engine"]
                assert eng["pool"]["full"]["total_pages"] > 0
                assert eng["phases"]["ttft_seconds"]["count"] > 0
                assert eng["requests"]["finished_window"] > 0
                assert any(name.startswith("kvtpu_engine_")
                           for name in engine_report["metrics"])

                # Kill pod-1 mid-run (SIGKILL: crash, not graceful stop).
                procs["pod-1"].kill()
                procs["pod-1"].wait(timeout=10)

                # The rest of the fleet keeps serving.
                assert serve_on(control, "pod-0", "r2", prompts["pod-0"]) \
                    == outputs["pod-0"]

                # A replacement pod joins (same identity, fresh process,
                # cold HBM) and restores pod-1's prefix from the SHARED
                # storage tier — bit-exact across processes.
                (control / "pod-1.ready").unlink()
                procs["pod-1b"] = start_pod("pod-1", control, store)
                assert wait_until(
                    (control / "pod-1.ready").exists, timeout=90.0)
                restored = serve_on(control, "pod-1", "r3", prompts["pod-1"])
                assert restored == outputs["pod-1"]

                # The restarted pod's events re-register it in the index.
                assert wait_until(
                    lambda: (lambda s: s and max(s, key=s.get) == "pod-1")(
                        client.get_pod_scores(prompts["pod-1"], MODEL)),
                    timeout=20.0)
            finally:
                client.close()

            # Evictor over the same store: with a permissive watermark it
            # idles (nothing deleted); with cleanup forced on it prunes
            # idle block files and the folder cleaner strips empty dirs.
            n_files = sum(1 for _ in store.rglob("*.bin"))
            assert n_files > 0  # write-through offload populated the store
            ev_env = dict(os.environ,
                          KVTPU_EVICTOR_STORE_ROOT=str(store),
                          KVTPU_EVICTOR_CLEANUP_THRESHOLD="0.0",
                          KVTPU_EVICTOR_TARGET_THRESHOLD="0.0",
                          KVTPU_EVICTOR_MIN_IDLE_SECONDS="0",
                          KVTPU_EVICTOR_POLL_INTERVAL_S="0.2",
                          KVTPU_EVICTOR_EMPTY_DIR_TTL_S="0")
            ev_env.pop("PYTHONPATH", None)
            ev_env["PYTHONPATH"] = str(REPO)
            procs["evictor"] = subprocess.Popen(
                [sys.executable, "examples/evictor_main.py"],
                env=ev_env, cwd=str(REPO),
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
            assert wait_until(
                lambda: sum(1 for _ in store.rglob("*.bin")) < n_files,
                timeout=30.0), "evictor never pruned the shared store"
        finally:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs.values():
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


SHARD_PORTS = range(15920, 15924)  # clear of the slow-test ports above
MODEL = "m"
BLOCK = 4


class TestShardedClusterE2E:
    """Fast 4-shard toy cluster: in-process replicas, real gRPC wire.

    Acceptance shape from the ISSUE: kill one shard with zero scoring
    outage (replica failover keeps scores exact, not merely degraded),
    then rejoin it via snapshot bootstrap and converge the event loss
    through peer anti-entropy.
    """

    def _make_service(self, addr, addrs, snap_root):
        from llmd_kv_cache_tpu.cluster.config import ClusterConfig
        from llmd_kv_cache_tpu.core import TokenProcessorConfig
        from llmd_kv_cache_tpu.events import PoolConfig
        from llmd_kv_cache_tpu.recovery import RecoveryConfig
        from llmd_kv_cache_tpu.scoring.indexer import IndexerConfig
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerService,
            serve,
        )

        cfg = IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK),
            recovery_config=RecoveryConfig(
                snapshot_dir=str(snap_root / addr.replace(":", "_")),
                snapshot_interval_s=0.0,  # manual snapshots only
                warmup_staleness_bound_s=1e9,  # no warmup gate in-test
            ),
            cluster_config=ClusterConfig(
                shard_addresses=list(addrs),
                shard_id=addr,
                replication_factor=2,
                breaker_reset_timeout_s=0.2,
            ),
        )
        svc = IndexerService(cfg, PoolConfig(concurrency=1))
        svc.start()
        return svc, serve(addr, svc)

    def _ingest(self, services, pod, tokens, engine_base):
        """Broadcast one root-parent BlockStored batch to every replica's
        pool (the full-stream broadcast each ShardFilterIndex filters)."""
        from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch

        n = len(tokens) // BLOCK
        batch = EventBatch(
            timestamp=time.time(),
            events=[BlockStoredEvent(
                block_hashes=list(range(engine_base, engine_base + n)),
                tokens=list(tokens), parent_hash=0, block_size=BLOCK,
                device_tier="gpu",
            )],
        )
        for svc in services:
            svc.pool.process_event_batch(batch, pod, MODEL)

    def test_four_shard_kill_and_rejoin(self, tmp_path):
        from llmd_kv_cache_tpu.cluster import ShardRouter
        from llmd_kv_cache_tpu.cluster.config import ClusterConfig
        from llmd_kv_cache_tpu.cluster.remote import ShardClient
        from llmd_kv_cache_tpu.core import TokenProcessorConfig

        addrs = [f"127.0.0.1:{p}" for p in SHARD_PORTS]
        services, servers = {}, {}
        router = None
        try:
            for addr in addrs:
                services[addr], servers[addr] = self._make_service(
                    addr, addrs, tmp_path)

            # pod-a holds the full 32-block prefix, pod-b the first half.
            t1 = list(range(1, 1 + 32 * BLOCK))
            self._ingest(services.values(), "pod-a", t1, 1000)
            self._ingest(services.values(), "pod-b", t1[:16 * BLOCK], 2000)

            router = ShardRouter(
                ClusterConfig(
                    shard_addresses=addrs,
                    replication_factor=2,
                    fanout_chunk_blocks=8,
                    breaker_reset_timeout_s=0.2,
                ),
                token_processor_config=TokenProcessorConfig(
                    block_size_tokens=BLOCK),
            )
            res = router.score(t1, MODEL)
            assert res.scores["pod-a"] == pytest.approx(32.0)
            assert res.scores["pod-b"] == pytest.approx(16.0)
            assert not res.degraded and res.degraded_shards == []
            keys1 = router.token_processor.tokens_to_kv_block_keys(
                0, t1, MODEL)
            assert res.hit_blocks == len(keys1)

            # Snapshot, then take down the shard that primaries block 0 —
            # the worst case for the longest-prefix chain.
            victim = router.ring.owner(keys1[0])
            assert services[victim].recovery.snapshot_now(reason="test")
            servers[victim].stop(grace=0)
            services[victim].stop()

            # Zero scoring outage: replica owners (rf=2) serve the dead
            # shard's keys, scores stay exact and are NOT degraded.
            res2 = router.score(t1, MODEL)
            assert res2.scores == res.scores
            assert res2.degraded_shards == []

            # Events the dead shard misses while down.
            survivors = [services[a] for a in addrs if a != victim]
            t2 = list(range(501, 501 + 32 * BLOCK))
            self._ingest(survivors, "pod-c", t2, 3000)
            res3 = router.score(t2, MODEL)
            assert res3.scores["pod-c"] == pytest.approx(32.0)
            assert res3.degraded_shards == []

            # Rejoin: fresh service on the same identity bootstraps the
            # owned key range from its snapshot...
            svc2, server2 = self._make_service(victim, addrs, tmp_path)
            services[victim], servers[victim] = svc2, server2
            owned1 = [k for k in keys1
                      if victim in router.ring.owners(k, 2)]
            assert owned1, "sample too small to exercise the victim"
            assert set(svc2.indexer.kv_block_index.lookup(owned1)) \
                == set(owned1)
            # ...while the outage window's events are genuinely absent...
            keys2 = router.token_processor.tokens_to_kv_block_keys(
                0, t2, MODEL)
            owned2 = [k for k in keys2
                      if victim in router.ring.owners(k, 2)]
            assert owned2
            assert svc2.indexer.kv_block_index.lookup(owned2) == {}
            # ...until one peer anti-entropy round repairs them.
            svc2.attach_peer_digest_source()
            stats = svc2.reconcile_now()
            assert stats["repaired_added"] >= len(owned2), stats
            assert set(svc2.indexer.kv_block_index.lookup(owned2)) \
                == set(owned2)

            # The rejoined shard answers its range over the real wire...
            peer = ShardClient(victim)
            try:
                def _served():
                    try:
                        hits = peer.lookup_blocks(owned2)["hits"]
                    except Exception:
                        return False
                    return set(hits) == set(owned2)

                assert wait_until(_served, timeout=15.0)
            finally:
                peer.close()

            # ...and the router's breaker re-admits it after the reset
            # window, with scores still exact.
            def _healed():
                r = router.score(t2, MODEL)
                return (r.scores.get("pod-c") == pytest.approx(32.0)
                        and not r.degraded_shards)

            assert wait_until(_healed, timeout=15.0, interval=0.25)
        finally:
            if router is not None:
                router.close()
            for server in servers.values():
                server.stop(grace=0)
            for svc in services.values():
                try:
                    svc.stop()
                except Exception:
                    pass  # victim's first incarnation is already stopped


FLEET_GRPC_PORTS = range(15950, 15954)   # clear of the port ranges above
FLEET_ADMIN_PORTS = range(15960, 15964)
FLEET_COLLECTOR_PORT = 15970


class TestFleetObservabilityE2E:
    """The fleet observability plane over the sharded toy cluster.

    Acceptance shape from ISSUE 10: the telemetry collector attached to
    the 4-shard cluster plus a prefill/decode pair assembles ONE
    cross-process trace — GetPodScores → handoff prefill commits →
    engine decode steps — spanning at least three logical processes,
    with per-segment critical-path attribution; killing a shard fires
    the availability burn-rate alert (multi-window, fast_burn) and the
    alert clears once the shard is rebuilt on the same identity.
    """

    def _make_service(self, addr, admin_port, addrs):
        from llmd_kv_cache_tpu.cluster.config import ClusterConfig
        from llmd_kv_cache_tpu.core import TokenProcessorConfig
        from llmd_kv_cache_tpu.events import PoolConfig
        from llmd_kv_cache_tpu.scoring.indexer import IndexerConfig
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerService,
            serve,
        )
        from llmd_kv_cache_tpu.telemetry import FleetTelemetryConfig

        cfg = IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK),
            admin_port=admin_port,
            cluster_config=ClusterConfig(
                shard_addresses=list(addrs),
                shard_id=addr,
                replication_factor=2,
                breaker_reset_timeout_s=0.2,
            ),
            # Span export on: the admin endpoint grows /debug/spans and
            # every shard's spans land in the (shared, in-process) ring.
            fleet_telemetry=FleetTelemetryConfig(span_export=True),
        )
        svc = IndexerService(cfg, PoolConfig(concurrency=1))
        svc.start()
        return svc, serve(addr, svc)

    def _ingest(self, services, pod, tokens, engine_base):
        from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch

        n = len(tokens) // BLOCK
        batch = EventBatch(
            timestamp=time.time(),
            events=[BlockStoredEvent(
                block_hashes=list(range(engine_base, engine_base + n)),
                tokens=list(tokens), parent_hash=0, block_size=BLOCK,
                device_tier="gpu",
            )],
        )
        for svc in services:
            svc.pool.process_event_batch(batch, pod, MODEL)

    def test_fleet_trace_assembly_and_burn_rate_alert(self, tmp_path):
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.offload.handoff import HandoffCoordinator
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerServiceClient,
        )
        from llmd_kv_cache_tpu.services.telemetry_collector import (
            CollectorConfig,
            ScrapeTarget,
            TelemetryCollector,
        )
        from llmd_kv_cache_tpu.telemetry.incident import (
            IncidentConfig,
            firing_alerts,
            load_bundle,
        )
        from llmd_kv_cache_tpu.telemetry.tracing import (
            set_process_identity,
            uninstall_span_exporter,
        )

        addrs = [f"127.0.0.1:{p}" for p in FLEET_GRPC_PORTS]
        admin_ports = dict(zip(addrs, FLEET_ADMIN_PORTS))
        services, servers = {}, {}
        client = None
        collector = None
        try:
            for addr in addrs:
                services[addr], servers[addr] = self._make_service(
                    addr, admin_ports[addr], addrs)

            prompt = list(range(1, 1 + 8 * BLOCK))
            self._ingest(services.values(), "decode-0", prompt, 1000)

            # 1) Score over the real gRPC wire. The server's GetPodScores
            # span is the trace root; its traceparent rides back on the
            # response (PR 7 score→serve continuity).
            client = IndexerServiceClient(addrs[0])
            resp = client.score(prompt, MODEL)
            tp = resp.traceparent
            assert tp.startswith("00-"), resp
            trace_id_hex = tp.split("-")[1]

            # 2) Prefill-side handoff under the same trace: pairing span +
            # one prefill_commit per landed chunk (process = prefill-0).
            coord = HandoffCoordinator()
            coord.begin("r1", "prefill-0", "decode-0",
                        total_blocks=4, traceparent=tp)
            coord.on_chunk_start("r1", [1, 2])
            coord.on_chunk_landed("r1", [1, 2])
            coord.on_chunk_start("r1", [3, 4])
            coord.on_chunk_landed("r1", [3, 4])
            coord.prefill_finished("r1")

            # 3) Decode-side serve under the same trace: a real engine's
            # admission/prefill_chunk/decode_step spans (process=decode-0).
            tiny = LlamaConfig.tiny()
            engine = MiniEngine(EngineConfig(
                model=tiny, num_pages=64, max_pages_per_seq=16,
                model_name=MODEL, pod_identifier="decode-0",
                max_prefill_tokens=tiny.page_size))
            req = engine.enqueue(
                "r1", list(range(300, 300 + 2 * tiny.page_size)),
                max_new_tokens=3, traceparent=tp)
            deadline = time.monotonic() + 120.0
            while not req.done and time.monotonic() < deadline:
                engine.step()
            assert req.done
            coord.decode_settled("r1", "complete")

            # 4) The collector scrapes all four shard admin endpoints.
            # Manual rounds (interval 0) keep the test deterministic;
            # tight SLO windows let the chaos phase run in seconds.
            collector = TelemetryCollector(CollectorConfig(
                targets=tuple(
                    ScrapeTarget(name=f"shard-{i}",
                                 address=f"127.0.0.1:{p}",
                                 role="indexer-shard")
                    for i, p in enumerate(FLEET_ADMIN_PORTS)),
                scrape_interval_s=0.0,
                admin_port=FLEET_COLLECTOR_PORT,
                trace_idle_s=0.2,
                slo_latency_threshold_s=0.0,  # retain every trace
                fast_windows=(0.6, 1.2),
                slow_window=2.4,
                breaker_reset_s=0.3,
                incident=IncidentConfig(directory=str(tmp_path)),
            ))
            collector.start()  # admin endpoint only; rounds driven below
            round1 = collector.scrape_once()
            assert round1["reachable"] == len(addrs)
            time.sleep(0.3)  # > trace_idle_s: the request trace goes idle
            collector.scrape_once()

            # One assembled trace, ≥3 logical processes, with the
            # score → prefill commit → decode step chain on its path.
            trace = collector.assembler.find_trace(trace_id_hex)
            assert trace is not None, collector.assembler.debug_view()
            assert trace["retained_reason"] == "slo_breach"
            assert {"prefill-0", "decode-0", addrs[0]} <= set(
                trace["processes"])
            path_names = [seg["name"] for seg in trace["critical_path"]]
            assert "llm_d.kv_cache.indexer.GetPodScores" in path_names
            assert "llm_d.kv_cache.handoff.prefill_commit" in path_names
            assert "llm_d.kv_cache.engine.decode_step" in path_names
            assert len(trace["critical_path_processes"]) >= 3
            # Attribution is complete: on-path self times tile the trace.
            assert sum(s["self_time_s"] for s in trace["critical_path"]) \
                == pytest.approx(trace["duration_s"], abs=1e-3)
            # Real spans are never billed more than their own lifetime;
            # the gap between score and serve (engine init here) shows up
            # as the synthetic (untracked) segment instead.
            for seg in trace["critical_path"]:
                if seg["name"] != "(untracked)":
                    # 1e-6: self_time_s is rounded to microseconds
                    assert seg["self_time_s"] <= \
                        (seg["end"] - seg["start"]) + 1e-6

            # Fleet rollup: the merged score-latency histogram yields
            # percentiles for the shard role and the fleet overall.
            rollup = collector.rollup_view()
            for role in ("all", "indexer-shard"):
                pcts = rollup[role]["kvcache_score_latency_seconds"]
                assert pcts["count"] > 0 and pcts["p50"] >= 0.0

            # kvdiag --fleet against the collector's admin endpoint: one
            # snapshot carries traces + rollup + SLO state.
            diag = subprocess.run(
                [sys.executable, "hack/kvdiag.py",
                 "--port", str(FLEET_COLLECTOR_PORT), "--fleet"],
                cwd=str(REPO), capture_output=True, text=True, timeout=30)
            assert diag.returncode == 0, diag.stderr
            fleet = json.loads(diag.stdout)["fleet"]
            assert any(t["trace_id"] == trace["trace_id"]
                       for t in fleet["retained_traces"])
            dominant = next(
                t["dominant_segment"] for t in fleet["retained_traces"]
                if t["trace_id"] == trace["trace_id"])
            assert dominant["self_time_s"] > 0.0
            assert set(fleet["slo"]) == {
                "ttft", "score_latency", "restore_latency", "availability",
                "index_divergence"}
            assert fleet["alerts"] == []  # healthy fleet: nothing firing

            # 5) Chaos: kill one shard. Scrapes of its admin endpoint
            # fail, the availability SLI burns 250x budget, and once both
            # fast windows agree the fast_burn alert fires.
            victim = addrs[-1]
            servers[victim].stop(grace=0)
            services[victim].stop()
            availability = collector.slos.get("availability")
            deadline = time.monotonic() + 15.0
            while (availability.alert_severity != "fast_burn"
                   and time.monotonic() < deadline):
                collector.scrape_once()
                time.sleep(0.1)
            assert availability.alert_severity == "fast_burn", \
                availability.debug_view()
            slo_view = collector.slos.debug_view()["availability"]
            assert slo_view["alert"]["fires"] >= 1
            assert slo_view["error_budget_remaining"] < 1.0

            # 5b) The fire edge auto-opened an incident: the black box
            # fanned out over the live admin plane and bundled evidence
            # from every still-reachable shard, with the skew offsets
            # the scrape loop estimated from each shard's /debug/time.
            collector.incidents.wait(timeout=15.0)
            assert collector.incidents.opened >= 1
            summary = next(
                s for s in collector.incidents.debug_view()["recent"]
                if s["trigger"] == "slo:availability")
            assert summary["pods_captured"] >= len(addrs) - 1
            doc = load_bundle(summary["path"])
            alive = [f"shard-{i}" for i in range(len(addrs) - 1)]
            for name in alive:
                assert doc["pods"][name]["reachable"], doc["pods"][name]
                assert "flight_recorder" in doc["pods"][name]
            assert doc["pods"][f"shard-{len(addrs) - 1}"]["reachable"] \
                is False
            assert set(doc["offsets"]) >= set(alive)
            assert any(a["name"] == "availability"
                       for a in firing_alerts(doc))
            # The offline viewer replays the bundle with no pod running.
            diag = subprocess.run(
                [sys.executable, "hack/kvdiag.py",
                 "--incident", summary["path"]],
                cwd=str(REPO), capture_output=True, text=True, timeout=30)
            assert diag.returncode == 0, diag.stderr
            assert "slo:availability" in diag.stdout

            # 6) Recovery: same identity, fresh service. Good rounds
            # resume, the bad samples age out of the fast windows, and
            # the alert clears (possibly stepping down through slow_burn
            # while the long window drains).
            services[victim], servers[victim] = self._make_service(
                victim, admin_ports[victim], addrs)
            deadline = time.monotonic() + 20.0
            while (availability.alert_severity is not None
                   and time.monotonic() < deadline):
                collector.scrape_once()
                time.sleep(0.1)
            assert availability.alert_severity is None, \
                availability.debug_view()
            assert collector.scrape_once()["reachable"] == len(addrs)
        finally:
            if client is not None:
                client.close()
            if collector is not None:
                collector.stop()
            for server in servers.values():
                server.stop(grace=0)
            for svc in services.values():
                try:
                    svc.stop()
                except Exception:
                    pass  # the victim's first incarnation already stopped
            uninstall_span_exporter()
            set_process_identity(None)


WS_ENGINE_ADMIN_PORT = 15980    # clear of the port ranges above
WS_INDEXER_ADMIN_PORT = 15981
WS_COLLECTOR_PORT = 15982


class TestWorkingSetFleetE2E:
    """ISSUE 12 acceptance: ``kvdiag --fleet`` against a live two-pod
    cluster prints the merged what-if capacity table, the never-read
    offload fraction, and the cross-pod duplicate share — all fed by
    real traffic through the three tracker hooks (engine admission +
    offload write-through on pod 1, index lookups on pod 2), exported
    over real HTTP at /debug/workingset, and sample-weight merged by
    the collector.
    """

    @staticmethod
    def _tracker():
        from llmd_kv_cache_tpu.telemetry.workingset import (
            WorkingSetConfig,
            WorkingSetTracker,
        )

        # rate 1.0: the merge math is exercised by the HTTP round trip,
        # not by sampling noise — the numbers below stay deterministic.
        return WorkingSetTracker(WorkingSetConfig(
            enabled=True, sample_rate=1.0, window_s=3600.0))

    @staticmethod
    def _admin(port, tracker):
        from llmd_kv_cache_tpu.services.admin import AdminServer

        admin = AdminServer(port=port)
        # The collector's main leg needs /debug/spans to answer; these
        # pods export no spans, so an empty source stands in.
        admin.register_spans_source(
            lambda since: {"spans": [], "next_seq": since, "dropped": 0})
        admin.register_workingset_source(tracker.export_since)
        admin.start()
        return admin

    def test_kvdiag_fleet_prints_whatif_table_from_two_pods(self, tmp_path):
        from llmd_kv_cache_tpu.core.keys import PodEntry
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec
        from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig
        from llmd_kv_cache_tpu.services.telemetry_collector import (
            CollectorConfig,
            ScrapeTarget,
            TelemetryCollector,
        )

        # Pod 1: a real engine with the storage tier on. Serving the
        # same prompt twice feeds the hbm reuse stream (second pass is
        # a full resident-prefix hit); write-through offload feeds the
        # written-never-read ledger, and nothing ever restores, so the
        # whole offload stays never-read.
        tiny = LlamaConfig.tiny()
        engine_tracker = self._tracker()
        engine = MiniEngine(
            EngineConfig(model=tiny, num_pages=64, max_pages_per_seq=16,
                         model_name=MODEL, pod_identifier="engine-0"),
            offload_spec=SharedStorageOffloadSpec(
                root=str(tmp_path), model_name=MODEL,
                page_size=tiny.page_size, num_layers=tiny.num_layers,
                kv_heads=tiny.num_kv_heads, head_dim=tiny.head_dim,
                io_threads=2, parallel_agnostic=True))
        engine.attach_workingset(engine_tracker)
        prompt = list(range(100, 100 + 2 * tiny.page_size))
        engine.generate("w1", prompt, max_new_tokens=2)
        engine.generate("w2", prompt, max_new_tokens=2)
        engine.flush_offload()

        # Pod 2: a real indexer whose lookup path feeds the index reuse
        # stream and the cross-pod duplication ledger — one block set
        # indexed on two pods (duplicated), one on a single pod.
        indexer_tracker = self._tracker()
        # In-memory backend: the Python lookup path returns the per-key
        # pod map the duplication ledger needs (the fused native path
        # feeds the reuse stream only).
        indexer = Indexer(IndexerConfig.from_dict(
            {"kvBlockIndexConfig": {"inMemoryConfig": {}}}))
        indexer.attach_workingset(indexer_tracker)
        block = indexer.token_processor.block_size
        dup_tokens = list(range(1, 1 + 4 * block))
        solo_tokens = list(range(5000, 5000 + 4 * block))
        indexer.kv_block_index.add(
            None, indexer.compute_block_keys(dup_tokens, MODEL),
            [PodEntry("pod-a", "gpu"), PodEntry("pod-b", "gpu")])
        indexer.kv_block_index.add(
            None, indexer.compute_block_keys(solo_tokens, MODEL),
            [PodEntry("pod-a", "gpu")])
        for _ in range(3):
            indexer.score_tokens(dup_tokens, MODEL)
            indexer.score_tokens(solo_tokens, MODEL)

        engine_tracker.rotate(force=True)
        indexer_tracker.rotate(force=True)

        pod_admins = []
        collector = None
        try:
            pod_admins.append(
                self._admin(WS_ENGINE_ADMIN_PORT, engine_tracker))
            pod_admins.append(
                self._admin(WS_INDEXER_ADMIN_PORT, indexer_tracker))
            collector = TelemetryCollector(CollectorConfig(
                targets=(
                    ScrapeTarget(name="engine-0",
                                 address=f"127.0.0.1:{WS_ENGINE_ADMIN_PORT}"),
                    ScrapeTarget(name="indexer-0",
                                 address=f"127.0.0.1:{WS_INDEXER_ADMIN_PORT}"),
                ),
                scrape_interval_s=0.0,
                admin_port=WS_COLLECTOR_PORT))
            collector.start()
            assert collector.scrape_once()["reachable"] == 2

            view = collector.workingset_view()
            assert view["targets"] == ["engine-0", "indexer-0"]
            assert view["hbm_capacity_blocks"] == 64  # engine num_pages

            # kvdiag --fleet over the wire: the human-facing table.
            diag = subprocess.run(
                [sys.executable, "hack/kvdiag.py",
                 "--port", str(WS_COLLECTOR_PORT), "--fleet"],
                cwd=str(REPO), capture_output=True, text=True, timeout=30)
            assert diag.returncode == 0, diag.stderr
            ws = json.loads(diag.stdout)["fleet"]["workingset"]

            assert ws["windows"] == 2
            assert ws["targets"] == ["engine-0", "indexer-0"]
            table = ws["whatif_table"]
            assert [row.split("x")[0] for row in table] == \
                ["0.5", "1", "2", "4"]
            assert "(64 blocks)" in table[1]  # 1x = current HBM
            ratios = [float(r["est_hit_ratio"]) for r in ws["whatif"]]
            assert ratios == sorted(ratios)  # MRC: more HBM never hurts
            # The second pass over an 8-block resident prompt hits; at
            # >= current capacity the model must see those hits.
            assert ratios[-1] > 0.0

            # Write-through offloaded blocks that nothing restored.
            assert ws["never_read_offload_fraction"] == 1.0
            # 4 of 8 tracked index blocks live on two pods.
            assert ws["cross_pod_duplicate_share"] == 0.5

            # Both pods' streams made it into the per-scope rollup.
            assert ws["scopes"]["hbm"]["accesses"] > 0
            assert ws["scopes"]["index"]["accesses"] == 6 * 4
            assert ws["scopes"]["index"]["measured_hit_ratio"] == 1.0
        finally:
            if collector is not None:
                collector.stop()
            for admin in pod_admins:
                admin.stop()


AUDIT_GRPC_PORTS = range(15990, 15994)   # clear of the port ranges above
AUDIT_ADMIN_PORTS = range(15994, 15998)
AUDIT_COLLECTOR_PORT = 15998


class TestAuditChaosE2E:
    """ISSUE 18 acceptance: the ground-truth audit plane under injected
    event loss.

    Four full-view indexer replicas (the replicated-indexer topology —
    scoring stays exact behind any one of them, unlike the key-sharded
    cluster above whose scatter-gather lives client-side) serve scores
    over real gRPC with the audit ring on. The healthy path closes the
    score->serve loop through a real engine (prediction joined to the
    realized outcome via ScoreFeedback) with calibration error and
    routing regret both zero. Then one engine pod's BlockStoredEvents
    are lost before reaching any replica: the continuous divergence
    audit reports ghost blocks on exactly that pod, the
    ``index_divergence`` SLI burns to fast_burn, and ``kvdiag --fleet``
    exits 3 naming the degraded pod. Anti-entropy reconciliation repairs
    the replicas from engine truth and the alert clears.
    """

    def _make_service(self, addr, admin_port):
        from llmd_kv_cache_tpu.core import TokenProcessorConfig
        from llmd_kv_cache_tpu.events import PoolConfig
        from llmd_kv_cache_tpu.scoring.indexer import IndexerConfig
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerService,
            serve,
        )
        from llmd_kv_cache_tpu.telemetry import FleetTelemetryConfig

        cfg = IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK),
            admin_port=admin_port,
            # audit=True: every score decision lands in the pod's
            # AuditLog ring, exported at /debug/audit for the collector's
            # score-vs-reality join.
            fleet_telemetry=FleetTelemetryConfig(
                span_export=True, audit=True),
        )
        svc = IndexerService(cfg, PoolConfig(concurrency=1))
        svc.start()
        return svc, serve(addr, svc)

    def _ingest(self, services, pod, tokens, engine_base):
        from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch

        n = len(tokens) // BLOCK
        batch = EventBatch(
            timestamp=time.time(),
            events=[BlockStoredEvent(
                block_hashes=list(range(engine_base, engine_base + n)),
                tokens=list(tokens), parent_hash=0, block_size=BLOCK,
                device_tier="gpu",
            )],
        )
        for svc in services:
            svc.pool.process_event_batch(batch, pod, MODEL)

    def _kvdiag(self, *extra):
        return subprocess.run(
            [sys.executable, "hack/kvdiag.py",
             "--port", str(AUDIT_COLLECTOR_PORT), "--fleet", *extra],
            cwd=str(REPO), capture_output=True, text=True, timeout=30)

    def test_event_loss_fires_divergence_sli_and_reconcile_clears_it(self):
        from llmd_kv_cache_tpu.core.keys import PodEntry
        from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
        from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.recovery import IndexDigestSource
        from llmd_kv_cache_tpu.services.indexer_service import (
            IndexerServiceClient,
            ScoreFeedback,
        )
        from llmd_kv_cache_tpu.services.telemetry_collector import (
            CollectorConfig,
            ScrapeTarget,
            TelemetryCollector,
        )
        from llmd_kv_cache_tpu.telemetry.tracing import (
            set_process_identity,
            uninstall_span_exporter,
        )

        addrs = [f"127.0.0.1:{p}" for p in AUDIT_GRPC_PORTS]
        admin_ports = dict(zip(addrs, AUDIT_ADMIN_PORTS))
        services, servers = {}, {}
        client = None
        collector = None
        try:
            for addr in addrs:
                services[addr], servers[addr] = self._make_service(
                    addr, admin_ports[addr])
            assert services[addrs[0]].audit_log is not None

            # Healthy event plane: three engine pods' stored blocks reach
            # every replica.
            live = list(range(1, 1 + 2 * BLOCK))
            self._ingest(services.values(), "decode-live", live, 2000)
            self._ingest(services.values(), "decode-a",
                         list(range(401, 401 + 4 * BLOCK)), 2100)
            self._ingest(services.values(), "decode-b",
                         list(range(801, 801 + 4 * BLOCK)), 2200)

            client = IndexerServiceClient(addrs[0])
            assert wait_until(
                lambda: client.score(live, MODEL).scores.get("decode-live")
                == pytest.approx(2.0), timeout=15.0)

            # Each replica audits against engine ground truth. So far the
            # event plane was lossless, so truth == the replica's own view
            # and every audit round is clean.
            truths = {}
            for addr, svc in services.items():
                truth = InMemoryIndex(InMemoryIndexConfig())
                truth.restore_state(svc.indexer.kv_block_index.dump_state())
                truths[addr] = truth
                svc.attach_digest_source(IndexDigestSource(truth))
            assert wait_until(
                lambda: all(not svc.audit_now()["divergent"]
                            for svc in services.values()), timeout=10.0)

            collector = TelemetryCollector(CollectorConfig(
                targets=tuple(
                    ScrapeTarget(name=f"indexer-{i}",
                                 address=f"127.0.0.1:{p}",
                                 role="indexer")
                    for i, p in enumerate(AUDIT_ADMIN_PORTS)),
                scrape_interval_s=0.0,
                admin_port=AUDIT_COLLECTOR_PORT,
                fast_windows=(0.6, 1.2),
                slow_window=2.4,
                breaker_reset_s=0.3,
            ))
            collector.start()
            assert collector.scrape_once()["reachable"] == len(addrs)

            # 1) Healthy path: score over the wire, route on the response,
            # serve on a real engine with the ScoreFeedback attached. The
            # engine's prefix cache holds exactly what the index promised
            # (warm-up request below), so predicted == realized.
            tiny = LlamaConfig.tiny()
            assert tiny.page_size == BLOCK  # index blocks == engine pages
            engine = MiniEngine(EngineConfig(
                model=tiny, num_pages=64, max_pages_per_seq=16,
                model_name=MODEL, pod_identifier="decode-live",
                max_prefill_tokens=tiny.page_size))
            engine.attach_audit(services[addrs[0]].audit_log)
            # Warm-up: caches `live` in engine HBM. Its outcome carries no
            # feedback and no trace - the joiner must count it unjoined,
            # never score it.
            engine.generate("audit-warm", live, max_new_tokens=2)

            prompt2 = live + list(range(7001, 7001 + BLOCK))
            resp = client.score(prompt2, MODEL)
            assert resp.scores.get("decode-live") == pytest.approx(2.0)
            fb = ScoreFeedback.from_response(
                resp, "decode-live", total_blocks=len(prompt2) // BLOCK)
            req = engine.enqueue("audit-r1", prompt2, max_new_tokens=3,
                                 traceparent=resp.traceparent, feedback=fb)
            deadline = time.monotonic() + 120.0
            while not req.done and time.monotonic() < deadline:
                engine.step()
            assert req.done

            collector.scrape_once()
            audit = collector.audit_view()
            assert audit["joined"] >= 1
            assert audit["unjoined_outcomes"] >= 1  # the feedback-less warm-up
            # Honest routing: the 2 predicted blocks were served from HBM.
            assert audit["mean_abs_error_blocks"] == pytest.approx(0.0)
            assert audit["regret_rate"] == 0.0
            cal = audit["pods"]["decode-live"]
            assert cal["calibration_ratio"] == pytest.approx(1.0)
            assert cal["regrets"] == 0
            assert audit["divergence"] == {}

            diag = self._kvdiag()
            assert diag.returncode == 0, diag.stderr
            fleet = json.loads(diag.stdout)["fleet"]
            assert fleet["alerts"] == []
            assert "index_divergence" in fleet["slo"]
            assert fleet["audit"]["mean_abs_error_blocks"] == \
                pytest.approx(0.0)
            assert fleet["audit"]["regret_rate"] == 0.0
            assert fleet["audit"]["degraded_pods"] == []

            # 2) Chaos: pod decode-lost stores three blocks but its events
            # never reach any replica (lost on the wire). Engine truth knows;
            # the index does not -> ghost blocks on exactly that pod.
            lost_tokens = list(range(9001, 9001 + 3 * BLOCK))
            lost_keys = services[addrs[0]].indexer.compute_block_keys(
                lost_tokens, MODEL)
            for truth in truths.values():
                truth.add(None, lost_keys, [PodEntry("decode-lost", "gpu")])

            for svc in services.values():
                res = svc.audit_now()
                assert set(res["divergent"]) == {"decode-lost"}, res
                assert res["divergent"]["decode-lost"] == {
                    "phantom": 0, "ghost": len(lost_keys)}

            tracker = collector.slos.get("index_divergence")
            deadline = time.monotonic() + 15.0
            while (tracker.alert_severity != "fast_burn"
                   and time.monotonic() < deadline):
                for svc in services.values():
                    svc.audit_now()
                collector.scrape_once()
                time.sleep(0.1)
            assert tracker.alert_severity == "fast_burn", \
                tracker.debug_view()
            # The divergence picture names exactly the lossy pod.
            audit = collector.audit_view()
            assert set(audit["divergence"]) == {"decode-lost"}
            assert audit["divergence"]["decode-lost"]["ghost"] == \
                len(lost_keys)

            # kvdiag --fleet is the pager: exit 3, the degraded pod named,
            # and the healthy-path calibration still clean.
            diag = self._kvdiag()
            assert diag.returncode == 3, diag.stderr
            fleet = json.loads(diag.stdout)["fleet"]
            assert {a["slo"] for a in fleet["alerts"]} == \
                {"index_divergence"}
            assert fleet["audit"]["degraded_pods"] == ["decode-lost"]
            assert set(fleet["audit"]["divergence"]) == {"decode-lost"}
            assert fleet["audit"]["mean_abs_error_blocks"] == \
                pytest.approx(0.0)
            quiet = self._kvdiag("--quiet")
            assert quiet.returncode == 3
            assert "index_divergence:fast_burn" in quiet.stdout
            assert "degraded_pods=decode-lost" in quiet.stdout

            # 3) Repair: anti-entropy reconciles each replica against engine
            # truth; the lost blocks become scoreable and the audit goes
            # clean, so the SLI's bad samples age out and the alert clears.
            for svc in services.values():
                svc.reconcile_now()
            assert client.score(lost_tokens, MODEL).scores.get(
                "decode-lost") == pytest.approx(3.0)
            deadline = time.monotonic() + 20.0
            while (tracker.alert_severity is not None
                   and time.monotonic() < deadline):
                for svc in services.values():
                    svc.audit_now()
                collector.scrape_once()
                time.sleep(0.1)
            assert tracker.alert_severity is None, tracker.debug_view()
            assert collector.audit_view()["divergence"] == {}
            # The healed episode observed its divergence age.
            from prometheus_client import REGISTRY
            healed = REGISTRY.get_sample_value(
                "kvtpu_index_divergence_age_seconds_count")
            assert healed is not None and healed >= 1.0

            quiet = self._kvdiag("--quiet")
            assert quiet.returncode == 0, quiet.stdout + quiet.stderr
            assert quiet.stdout.strip() == "kvdiag: ok"
        finally:
            if client is not None:
                client.close()
            if collector is not None:
                collector.stop()
            for server in servers.values():
                server.stop(grace=0)
            for svc in services.values():
                try:
                    svc.stop()
                except Exception:
                    pass
            uninstall_span_exporter()
            set_process_identity(None)
