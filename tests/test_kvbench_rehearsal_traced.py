"""Tier-1's traced walk of the benchmark's own path: every cell of
``BENCHMARK.json``, rehearsed on the CPU through ``kvbench/run.py`` with
its profiler on (the untraced walk is ``test_kvbench_rehearsal.py``: two
files, so that ``--dist loadfile`` can give them to two workers).

A rehearsal's numbers are no measurement, so no value is asserted beyond
what the pairing has to stand on.
"""

import json
import os
import re
import subprocess
import sys

import pytest
from test_kvbench_rehearsal import (  # noqa: F401 (the fixture applies here)
    BENCHMARK,
    ROOT,
    require_native,
)

FIRST_TOKEN = ("engine_queue_ms_p50", "behind_prefill_share",
               "engine_ttft_ms_p50", "prefill_own_device_share",
               "ttft_outside_engine_ms_p50")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_a_traced_cell_pairs_programs_launched_ahead(cell):
    """A traced rehearsal of every cell: ``launched_ahead_share`` is
    reported (a replica that only decodes and has the device to itself
    launches its next decode program before it reads the last one's
    tokens), and the pairing of device programs with ``step.dispatch`` /
    ``step.fetch`` (a fetch may follow a later dispatch: it is found by its
    ``launch``) reads no clock fault and leaves unpaired only what the
    slice's head cut: held to that in the cell whose replicas take turns.
    Where two are busy at once the rehearsal's CPU runs their programs side
    by side and a program launched ahead waits for the one it reads, so
    the programs do not start in the order of their launches as a chip's
    do, and the pairing has nothing to stand on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "kvbench/run.py", "--workload", cell, "--seed",
         "2900000555", "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    share = line["metrics"]["launched_ahead_share"]
    assert share["unit"] == "%" and 0.0 <= share["value"] <= 100.0
    # The cells whose models have latent-attention layers say how many of
    # the slice's chunks attended per head (none at a rehearsal's chunks
    # of 64: the form changes from 167 queries on at its widths); the
    # dense cells do not report it.
    latent = cell.startswith(("deepseek-v3.2-exp", "gigachat3.5"))
    assert ("prefill_per_head_share" in line["metrics"]) == latent
    if latent:
        per_head = line["metrics"]["prefill_per_head_share"]
        assert per_head["unit"] == "%" and 0.0 <= per_head["value"] <= 100.0
    # A request's way to its first token, told by the engine (PR 59): the
    # cells that list the five readers print a number for each, 0.0 where
    # the slice held no marker, and the engine's split holds together.
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if m["name"] in FIRST_TOKEN and cell in m["workloads"]}
    assert listed in (set(), set(FIRST_TOKEN))
    if listed:
        got = {name: line["metrics"][name]["value"] for name in FIRST_TOKEN}
        assert min(got.values()) >= 0.0
        # (No ceiling on ``prefill_own_device_share``: a chip runs one
        # program at a time, the rehearsal's CPU starts a program's first
        # ops beside the one before it, and the spans of a request's chunks
        # can add up to more than the time they ran in.)
        assert got["behind_prefill_share"] <= 100.0
        assert got["engine_queue_ms_p50"] <= got["engine_ttft_ms_p50"]
    (found,) = re.findall(
        r"launches: (\d+) step programs, (\d+) placed \(by launch, offset "
        r"(\d+)\), unpaired (\d+), clock_fault (\d+)", out.stdout)
    programs, placed, offset, unpaired, faults = map(int, found)
    assert programs > 0 and placed == programs - unpaired
    if cell == "qwen3-1.7b.sessions":
        # A replica has at most two programs out when the slice begins.
        assert faults == 0 and unpaired == offset <= 4
