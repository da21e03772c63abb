"""Tier-1's walk of the benchmark's own path: every cell of
``BENCHMARK.json``, rehearsed on the CPU through ``kvbench/run.py`` (the
traced walk is ``test_kvbench_rehearsal_traced.py``).

The harness reaches into the program by name (``EngineConfig``'s fields,
``MiniEngine.attention_backends``, ``PHASE_NAMES``, ``llama.PROGRAM_*``); a
program change that breaks that reach fails here, not on the chip. A
rehearsal's numbers are no measurement, so no value is asserted.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from llmd_kv_cache_tpu.index import native

ROOT = Path(__file__).resolve().parents[1]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module", autouse=True)
def require_native():
    # Fails, not skips: a cell is not ``correct`` without the native index.
    assert native.native_available(), "native library unavailable"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_rehearses(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the cell's one device, not the tests' eight
    out = subprocess.run(
        [sys.executable, "kvbench/run.py", "--workload", cell, "--seed", "7",
         "--seconds", "4", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["failed"] == 0
    judged = {m["name"] for m in BENCHMARK["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert judged <= set(line["metrics"])
