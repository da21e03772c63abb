"""The readers of the gap between tokens on a synthetic run: the mean that
every cell is judged by, and the 95th percentile, judged where it is steady
and per layer everywhere."""

import pytest

from kvbench.harness import names
from kvbench.harness.loop import RequestRecord, Run


def request(idx, times, sampled=True):
    return RequestRecord(idx=idx, arrival=None, prompt_len=8, max_new=8,
                         token_times=list(times), sampled=sampled)


def a_run():
    run = Run(seconds=1.0)
    run.t_sample, run.t_end = 0.0, 1.0
    run.requests = [
        request(0, [0.10, 0.12, 0.14, 0.16, 0.18]),    # four gaps of 20 ms
        request(1, [0.20, 0.22, 0.30, 1.50]),          # 20, 80; one past the end
        request(2, [0.10, 0.50], sampled=False)]       # not sampled
    return run


@pytest.mark.parametrize("name,expected", [
    ("itl_mean_ms", (4 * 20 + 20 + 80) / 6),
    ("itl_p95_ms", 65.0),      # between the 5th and 6th of 20 x 5, 80
    ("itl_ms_p95", 65.0),
])
def test_gap_readers(name, expected):
    assert names.metric(name).compute(a_run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["itl_mean_ms", "itl_p95_ms", "itl_ms_p95"])
def test_no_gap_is_none(name):
    run = Run(seconds=1.0)
    run.t_end = 1.0
    run.requests = [request(0, [0.5])]
    assert names.metric(name).compute(run) is None


def test_every_cell_reports_a_judged_gap_and_the_tail_per_layer():
    bench = names.benchmark()
    for w in bench["workloads"]:
        judged = {m["name"] for m in names.cell_metrics(bench, w["name"], False)}
        layer = {m["name"] for m in names.cell_metrics(bench, w["name"], True)}
        assert "itl_mean_ms" in judged and "itl_ms_p95" in layer
