"""Name resolution: every name in BENCHMARK.json has its file, and a name
without one fails with the path that was looked for."""

import json
import re

import pytest

from kvbench.harness import names

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_resolves():
    bench = names.benchmark()
    for w in bench["workloads"]:
        conf = names.config(bench, w["config"])
        assert conf["kvbench"]["replicas"] >= 1
        traffic = names.traffic(w["traffic"])
        assert callable(names.generator(traffic["generator"]).schedule)
        for traced in (False, True):
            entries = names.cell_metrics(bench, w["name"], traced)
            assert entries
            for m in entries:
                mod = names.metric(m["name"])
                assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
                if traced:
                    assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]


def test_contract_shape():
    bench = names.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        # The metric it moves is reported in every cell where it is.
        moved = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert names.cell_metrics(bench, w["name"], False)
        assert names.cell_metrics(bench, w["name"], True)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.parametrize("call,arg", [
    (names.traffic, "no-such-mix"),
    (names.generator, "no_such_generator"),
    (names.metric, "no_such_metric"),
])
def test_missing_file_names_the_path(call, arg):
    with pytest.raises(names.MissingFile) as err:
        call(arg)
    assert arg in str(err.value) and "kvbench" in str(err.value)


def test_unknown_workload_lists_the_known():
    bench = names.benchmark()
    with pytest.raises(KeyError) as err:
        names.workload(bench, "nothing.here")
    assert bench["workloads"][0]["name"] in str(err.value)


def test_harness_names_no_cell():
    """run.py and harness/ hold no workload, configuration, traffic or
    metric name: those live in data files and BENCHMARK.json."""
    bench = names.benchmark()
    words = {e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[g]}
    words |= {w["traffic"] for w in bench["workloads"]}
    sources = [names.KVBENCH / "run.py", names.KVBENCH / "sweep.py",
               *sorted((names.KVBENCH / "harness").glob("*.py"))]
    for path in sources:
        text = path.read_text()
        for word in words:
            assert not re.search(rf"(?<![\w.\-]){re.escape(word)}(?![\w\-])",
                                 text), (path.name, word)


def test_harness_and_readers_reach_reference_and_counts_by_name_only():
    """The plain reference and the plain counts are the defaults of
    ``names.reference`` / ``names.counts``: nothing else in the command, the
    harness or the readers imports the one or calls the other's counting
    functions, so a configuration that names its own is served by its own.
    (``opcount.peaks`` is the table of the chip, not a count.)"""
    sources = [names.KVBENCH / "run.py", names.KVBENCH / "sweep.py",
               *sorted((names.KVBENCH / "harness").glob("*.py")),
               *sorted((names.KVBENCH / "metrics").glob("*.py"))]
    assert len(sources) > 25
    counting = ("prefill_flops|decode_attention_bytes|dense_flops_per_token"
                "|attention_flops|head_flops|keys_attended")
    banned = [r"kvbench\.reference\b", r"kvbench\s+import\s+[^\n]*\breference",
              r"^\s*(from|import)\s+reference\b",
              rf"opcount\.({counting})\b",
              rf"trace\.opcount\s+import\s+[^\n]*\b({counting})\b"]
    for path in sources:
        text = path.read_text()
        for pattern in banned:
            assert not re.search(pattern, text, re.M), (path.name, pattern)
        if path.parent.name == "metrics":
            assert "opcount" not in text, path.name
    # The one way in.
    text = (names.KVBENCH / "harness" / "names.py").read_text()
    assert '"reference.py"' in text and '"trace/opcount.py"' in text


def test_rehearsal_groups_replace_values():
    doc = {"rate": 8.0, "params": {"a": 1, "b": 2},
           "rehearse": {"rate": 2.0, "params": {"b": 3}}}
    assert names.with_rehearsal(doc, False) == {"rate": 8.0,
                                                "params": {"a": 1, "b": 2}}
    assert names.with_rehearsal(doc, True) == {"rate": 2.0,
                                               "params": {"a": 1, "b": 3}}
