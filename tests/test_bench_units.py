"""Cheap regression cover for bench.py's host modes, its refusal of
anything else, and the perf sentinel that reads them."""

import json
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import bench


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


class TestNoDeviceModes:
    """``bench.py`` asks the device nothing: with no mode, or with a flag
    of one of the device modes it used to have, it exits non-zero with no
    result line and points at the benchmark that does."""

    @pytest.mark.parametrize("flag", [
        None, "--ttft", "--ttft-load", "--offload", "--decode",
        "--decode-hybrid", "--ragged", "--fp8-bandwidth", "--disagg"])
    def test_refuses_and_names_kvbench(self, flag):
        out = subprocess.run(
            [sys.executable, "bench.py"] + ([flag] if flag else []),
            capture_output=True, text=True, timeout=60, cwd=ROOT,
            env={"PATH": "/usr/bin:/bin:/opt/venv/bin"})
        assert out.returncode != 0
        assert out.stdout.strip() == ""
        assert "kvbench/run.py" in out.stderr


def _documents():
    return [p.relative_to(ROOT).as_posix() for p in (
        ROOT / "Makefile", ROOT / "README.md",
        ROOT / "benchmarking" / "perf_baseline.json",
        ROOT / "benchmarking" / "README.md", ROOT / "examples" / "README.md",
        *sorted((ROOT / "docs").glob("*.md")),
        *sorted((ROOT / ".github" / "workflows").glob("*.yaml")))]


class TestDocumentsNameLiveModes:
    """Every ``bench.py --<mode>`` and ``make bench-<target>`` a document
    names exists: the documents cannot drift from the files again."""

    @pytest.mark.parametrize("document", _documents())
    def test_named_modes_and_targets_exist(self, document):
        text = (ROOT / document).read_text()
        modes = set(re.findall(r"bench\.py`*\s+`*(--[a-z][a-z-]*)", text))
        assert modes <= set(bench.MODES), sorted(modes - set(bench.MODES))
        makefile = (ROOT / "Makefile").read_text()
        targets = set(re.findall(r"^([a-z][a-z0-9-]*):", makefile, re.M))
        named = set(re.findall(r"make\s+(bench[a-z0-9-]*)", text))
        assert named <= targets, sorted(named - targets)


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
        assert "hotpath-evict" in manifest["benches"]
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
            "hotpath-evict": {
                "bench": "hotpath-evict", "value": 57.0,
                "unit": "scan p50 / kept-order p50, host time of one "
                        "admission's pages"},
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
