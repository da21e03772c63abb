"""What a traced run says about its time after the window: one rehearsal
(toy widths, the CPU, about half a minute) through ``run.py`` as the driver
starts it, read from its output."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from kvbench.harness import names

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def rehearsal():
    cell = names.benchmark()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "kvbench/run.py", "--workload", cell, "--seed", "5",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return done


def logged(done, head):
    found = [ln for ln in done.stdout.splitlines()
             if ln.startswith(f"[kvbench] {head}")]
    assert len(found) == 1, (head, found)
    return found[0][len(f"[kvbench] {head}"):]


def test_after_the_window_line(rehearsal):
    after = json.loads(logged(rehearsal, "after the window: "))
    stages, counts, tracer = (after["stages_s"], after["counts"],
                              after["tracer"])
    # The tracer's stop is timed on its own thread, and what of it lay past
    # the window's end apart; every later stage has its seconds.
    assert tracer["stop_trace"] > 0 and tracer["start_trace"] >= 0
    assert 0 <= tracer["stop_trace.past_end"] <= tracer["stop_trace"]
    assert tracer["ended_by"] == "seconds"
    assert tracer["slice"] == pytest.approx(2.0, abs=0.5)
    for name in ("join.serving", "join.tracer", "load", "reduce",
                 "op_seconds", "idle_by_span", "longest_gaps", "check_line",
                 "close"):
        assert stages[name] >= 0, name
    # Every reader once, the untraced mode's too (the "all metrics" line).
    bench = names.benchmark()
    readers = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert {n[len("reader."):] for n in stages
            if n.startswith("reader.")} == readers
    assert after["total_s"] == pytest.approx(
        sum(stages.values()) + after["unaccounted_s"], abs=0.01)
    assert after["unaccounted_s"] < 1.0
    # What drives the cost.
    assert counts["trace_bytes"] > 0 and counts["device_ops"] > 0
    assert abs(counts["gaps"] - counts["busy_intervals"]) <= 1
    assert counts["step.work"] > 0
    assert counts["span_intervals"]["step"] >= counts["step.work"] - 2


def test_budget_line_and_last_line(rehearsal):
    budget = logged(rehearsal, "budget: ")
    assert "of the check's 360s" in budget and "after the window" in budget
    assert "WARNING" not in rehearsal.stderr
    assert "[kvbench] exit: " in rehearsal.stderr
    last = json.loads(rehearsal.stdout.splitlines()[-1])
    assert last["correct"] is True
    # The last line's metrics are the readings of the "all metrics" line.
    everything = json.loads(logged(
        rehearsal,
        "all metrics of this run (the last line holds this mode's): "))
    assert last["metrics"] == {n: everything[n] for n in last["metrics"]}
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}


# -- the traced slice's two bounds -------------------------------------------


def test_slice_ends_by_time_by_steps_or_by_stop():
    import threading
    import time

    from kvbench.harness.loop import wait_for_slice

    stop = threading.Event()
    t0 = time.perf_counter()
    count = lambda: int((time.perf_counter() - t0) * 100)   # 100 steps/s
    # Today's engine: the step bound is out of reach, the time runs out.
    assert wait_for_slice(stop, 0.3, 1000, count) == "seconds"
    assert 0.3 <= time.perf_counter() - t0 < 0.45
    # A faster engine: the steps come first, a poll (50 ms) late at most.
    t0 = time.perf_counter()
    assert wait_for_slice(stop, 5.0, 20, count) == "steps"
    assert 0.2 <= time.perf_counter() - t0 < 0.35
    # No step bound in the traffic file: by time alone, one wait.
    t0 = time.perf_counter()
    assert wait_for_slice(stop, 0.1, None, None) == "seconds"
    # The window ended first.
    threading.Timer(0.1, stop.set).start()
    assert wait_for_slice(stop, 5.0, 10**9, lambda: 0) == "stop"
    assert wait_for_slice(stop, 5.0, None, None) == "stop"
