"""Cheap regression cover for bench.py helpers (the slow arms run under
the driver; these keep the harness itself from rotting)."""

import json
import subprocess
import sys

sys.path.insert(0, "/root/repo")

import bench


class TestWorkload:
    def test_deterministic(self):
        import numpy as np

        a = bench.build_workload(np.random.default_rng(42), n_requests=8)
        b = bench.build_workload(np.random.default_rng(42), n_requests=8)
        assert a == b

    def test_shared_prefixes(self):
        import numpy as np

        wl = bench.build_workload(np.random.default_rng(0), n_requests=32,
                                  n_prefixes=4, prefix_len=16, suffix_len=4)
        prefixes = {tuple(p[:16]) for p in wl}
        assert len(prefixes) <= 4  # requests reuse the prefix pool
        assert all(len(p) == 20 for p in wl)


class TestQueueingTTFTs:
    def test_no_arrivals_returns_bare_service(self):
        assert bench.queueing_ttfts([1.0, 2.0], ["a", "b"], None) == [1.0, 2.0]

    def test_fifo_queue_wait_accumulates_per_pod(self):
        # Both requests hit pod "a"; the second arrives at t=0 but waits
        # for the first's service to finish.
        ttfts = bench.queueing_ttfts([1.0, 1.0], ["a", "a"], [0.0, 0.0])
        assert ttfts == [1.0, 2.0]

    def test_independent_pods_do_not_queue(self):
        ttfts = bench.queueing_ttfts([1.0, 1.0], ["a", "b"], [0.0, 0.0])
        assert ttfts == [1.0, 1.0]

    def test_idle_gap_resets_queue(self):
        # Second arrival lands after the first completes: no wait.
        ttfts = bench.queueing_ttfts([1.0, 1.0], ["a", "a"], [0.0, 5.0])
        assert ttfts == [1.0, 1.0]


class TestRunConcurrent:
    """The concurrent arm against real tiny engines: every request gets a
    TTFT, queueing shows up, and decode load is served to completion."""

    @staticmethod
    def _fleet(n_pods=2, num_pages=64):
        from llmd_kv_cache_tpu.core import TokenProcessorConfig
        from llmd_kv_cache_tpu.models import engine as engine_mod
        from llmd_kv_cache_tpu.models.llama import LlamaConfig
        from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig

        cfg = LlamaConfig.tiny()
        indexer = Indexer(IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=cfg.page_size)))
        pods = bench.make_pods(
            n_pods, cfg, engine_mod, indexer,
            pod_kw={"num_pages": num_pages, "max_pages_per_seq": 16})
        return pods, indexer

    def test_all_requests_served_with_queueing(self):
        import numpy as np

        pods, _ = self._fleet()
        wl = bench.build_workload(np.random.default_rng(3), n_requests=8,
                                  n_prefixes=2, prefix_len=12, suffix_len=4,
                                  vocab=200)
        # Two bursts: 4 requests at t=0 (they must queue behind each
        # other's service) and 4 long after (no queueing).
        arrivals = [0.0, 0.0, 0.0, 0.0, 1e6, 1e6 + 1, 1e6 + 2, 1e6 + 3]
        ttfts, hit, out_tps, decode = bench.run_concurrent(
            pods, wl, bench.make_rr_router(), arrivals,
            max_new_tokens=4)
        assert len(ttfts) == 8 and all(t > 0 for t in ttfts)
        assert 0.0 <= hit <= 1.0
        # 8 requests x 4 decoded tokens over a positive makespan.
        assert out_tps > 0
        # Decode latency accounting: 3 inter-token gaps per request (4
        # tokens), one TPOT per request, all positive virtual times.
        assert len(decode["itl"]) == 8 * 3
        assert len(decode["tpot"]) == 8
        assert all(g > 0 for g in decode["itl"])
        assert all(t > 0 for t in decode["tpot"])
        # Every request decoded to completion through step().
        for p in pods.values():
            assert not p._running
        # The t=0 burst on each pod queues: later requests of the burst
        # wait for earlier ones, so the burst's worst TTFT strictly
        # exceeds its best (same pods serve one prefill at a time).
        burst = sorted(ttfts[:4])
        assert burst[-1] > burst[0]

    def test_page_pressure_defers_admission(self):
        import numpy as np

        # A pool sized for ~1.5 in-flight requests: the second concurrent
        # admission must retry until the first finishes, not crash.
        pods, _ = self._fleet(n_pods=1, num_pages=24)
        wl = bench.build_workload(np.random.default_rng(4), n_requests=4,
                                  n_prefixes=1, prefix_len=12, suffix_len=4,
                                  vocab=200)
        arrivals = [0.0, 0.0, 0.0, 0.0]
        ttfts, _, _, _ = bench.run_concurrent(
            pods, wl, lambda *_a, **_kw: "pod-0", arrivals,
            max_new_tokens=4)
        assert len(ttfts) == 4 and all(t > 0 for t in ttfts)


class TestBenchModes:
    def test_index_bench_emits_valid_json(self):
        result = bench.bench_index_add()
        assert result["unit"] == "ns/op"
        assert result["value"] > 0
        assert result["vs_baseline"] > 0
        json.dumps(result)

    def test_python_fallback_mode(self):
        result = bench.bench_index_add(native=False)
        assert "python" in result["metric"]

    def test_cli_index_mode(self):
        out = subprocess.run(
            [sys.executable, "bench.py", "--index"],
            capture_output=True, text=True, timeout=300, cwd="/root/repo",
            env={"PATH": "/usr/bin:/bin:/opt/venv/bin"},
        )
        line = out.stdout.strip().splitlines()[-1]
        parsed = json.loads(line)
        assert set(parsed) == {"metric", "value", "unit", "vs_baseline"}


class TestRoutingBenchNeedsAChip:
    """The routing benchmark runs in-process on a TPU or not at all: no
    probe, no child process, no CPU or index-microbenchmark stand-in."""

    def test_default_mode_refuses_without_a_tpu(self):
        import pytest

        with pytest.raises(SystemExit) as exc:
            bench._dispatch(["bench.py"])
        # SystemExit with a message exits 1 and prints it to stderr; no
        # result line is produced.
        assert "needs a TPU" in str(exc.value.code)
        assert "'cpu'" in str(exc.value.code)

    def test_the_ladder_is_gone(self):
        for name in ("guarded_main", "_accelerator_healthy",
                     "_run_ttft_subprocess"):
            assert not hasattr(bench, name)


class TestPerfSentinel:
    """The perf-regression gate's verdict-line grammar and exit codes
    (``hack/perf_sentinel.py``, wired into ``make perf-check``)."""

    @staticmethod
    def _sentinel():
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "perf_sentinel", "/root/repo/hack/perf_sentinel.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    BASELINE = {
        "benches": {
            "pyprof-overhead": {"baseline": 0.5,
                                "max_regression_pct": 100.0,
                                "direction": "lower_is_better"},
        },
        "hot_functions": {
            "llm_d.kv_cache.score_tokens": {"tracing.py:export": 0.25},
        },
    }

    def _result(self, value, export_share=0.01):
        return {"metric": "pyprof_overhead_pct", "value": value,
                "unit": "%", "vs_baseline": 1.0,
                "hot_functions": {"llm_d.kv_cache.score_tokens": {
                    "samples": 100,
                    "functions": {"native.py:score": 1.0 - export_share,
                                  "tracing.py:export": export_share}}}}

    def test_healthy_run_passes_every_check(self):
        sentinel = self._sentinel()
        lines, failed = sentinel.evaluate(
            self.BASELINE, {"pyprof-overhead": self._result(0.6)})
        assert failed == 0
        assert lines[0] == ("PERF PASS bench:pyprof-overhead "
                            "value=0.6 baseline=0.5 limit=1")
        assert lines[1] == ("PERF PASS hotfn:llm_d.kv_cache.score_tokens:"
                            "tracing.py:export share=0.01 max=0.25")
        assert lines[-1] == "PERF OVERALL PASS checks=2 failed=0"

    def test_bench_regression_fails_with_verdict_line(self):
        sentinel = self._sentinel()
        lines, failed = sentinel.evaluate(
            self.BASELINE, {"pyprof-overhead": self._result(1.31)})
        assert failed == 1
        assert lines[0].startswith(
            "PERF FAIL bench:pyprof-overhead value=1.31")
        assert "(regression +162.0%)" in lines[0]
        assert lines[-1] == "PERF OVERALL FAIL checks=2 failed=1"

    def test_injected_hot_function_regression_fails(self):
        # The headline latency gate still passes, but a capped function
        # claims 40% of the span's samples: the sentinel must FAIL.
        sentinel = self._sentinel()
        lines, failed = sentinel.evaluate(
            self.BASELINE,
            {"pyprof-overhead": self._result(0.6, export_share=0.4)})
        assert failed == 1
        assert ("PERF FAIL hotfn:llm_d.kv_cache.score_tokens:"
                "tracing.py:export share=0.4 max=0.25") in lines
        assert lines[-1] == "PERF OVERALL FAIL checks=2 failed=1"

    def test_missing_gated_bench_fails_loudly(self):
        sentinel = self._sentinel()
        lines, failed = sentinel.evaluate(self.BASELINE, {})
        assert failed == 1
        assert "PERF FAIL bench:pyprof-overhead missing=1" in lines

    def test_absent_function_passes_trivially(self):
        sentinel = self._sentinel()
        result = self._result(0.6)
        del result["hot_functions"]["llm_d.kv_cache.score_tokens"][
            "functions"]["tracing.py:export"]
        lines, failed = sentinel.evaluate(
            self.BASELINE, {"pyprof-overhead": result})
        assert failed == 0
        assert ("PERF PASS hotfn:llm_d.kv_cache.score_tokens:"
                "tracing.py:export share=0 max=0.25") in lines

    def test_cli_exit_codes_and_grammar(self, tmp_path):
        baseline = tmp_path / "baseline.json"
        baseline.write_text(json.dumps(self.BASELINE))
        good = tmp_path / "good.json"
        good.write_text(json.dumps(self._result(0.6)))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(self._result(0.6, export_share=0.9)))

        def run(results):
            return subprocess.run(
                [sys.executable, "/root/repo/hack/perf_sentinel.py",
                 "--baseline", str(baseline),
                 "--results", f"pyprof-overhead={results}"],
                capture_output=True, text=True, timeout=60)

        ok = run(good)
        assert ok.returncode == 0
        verdicts = [l for l in ok.stdout.splitlines() if l.startswith("PERF")]
        assert len(verdicts) == 3  # bench + hotfn + OVERALL
        assert verdicts[-1].startswith("PERF OVERALL PASS")

        regressed = run(bad)
        assert regressed.returncode == 1
        assert "PERF OVERALL FAIL checks=2 failed=1" in regressed.stdout

    def test_committed_manifest_matches_a_live_overhead_result(self):
        # The committed baseline must gate every bench the Makefile
        # feeds it (perf-check runs both telemetry overhead benches),
        # with headroom wide enough that a nominal run passes — and a
        # bench missing from the results must fail, so perf-check can
        # never silently skip one.
        with open("/root/repo/benchmarking/perf_baseline.json") as f:
            manifest = json.load(f)
        assert "pyprof-overhead" in manifest["benches"]
        assert "workingset" in manifest["benches"]
        assert "controller" in manifest["benches"]
        assert "graytail" in manifest["benches"]
        assert "audit" in manifest["benches"]
        assert "fencing" in manifest["benches"]
        assert "hotpath-fleet" in manifest["benches"]
        assert "incident" in manifest["benches"]
        sentinel = self._sentinel()
        nominal = {
            "pyprof-overhead": {
                "metric": "pyprof_overhead_pct", "value": 0.08,
                "unit": "%", "vs_baseline": 1.0, "hot_functions": {}},
            "workingset": {
                "metric": "workingset_overhead_pct", "value": 0.4,
                "unit": "% of score p50", "vs_baseline": 1.0},
            "controller": {
                "metric": "flap_executed_actions", "value": 1,
                "unit": "actions", "vs_baseline": 1.0},
            "graytail": {
                "metric": "hedging_overhead_pct", "value": 0.2,
                "unit": "% of score p50", "vs_baseline": 1.0},
            "audit": {
                "metric": "audit_overhead_pct", "value": 0.6,
                "unit": "% of score p50", "vs_baseline": 1.0},
            "fencing": {
                "metric": "fence_overhead_pct", "value": 0.3,
                "unit": "% of score p50", "vs_baseline": 1.0},
            "hotpath-fleet": {
                "metric": "batched_fanout_ratio", "value": 7.0,
                "unit": "batched/per-chunk sustained GetPodScores/s ratio",
                "vs_baseline": 1.0},
            "incident": {
                "metric": "incident_trigger_overhead_pct", "value": 0.55,
                "unit": "% of score p50", "vs_baseline": 1.0},
        }
        # The nominal set must cover the whole committed manifest — a
        # bench added to the baseline without a result arm here is the
        # exact silent-skip this test exists to prevent.
        assert set(nominal) == set(manifest["benches"])
        _, failed = sentinel.evaluate(manifest, nominal)
        assert failed == 0
        missing_one = dict(nominal)
        del missing_one["workingset"]
        _, failed = sentinel.evaluate(manifest, missing_one)
        assert failed == 1  # workingset bench result went missing
