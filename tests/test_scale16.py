"""Scale tier beyond the default 8-device mesh: the full sharded training
step on a 16-device virtual CPU mesh, in a subprocess (conftest pins this
process to 8 devices).

Covers the NOTES round-2 item "scale tests >8 virtual devices": the same
dp×tp×sp / dp×tp×ep / dp×pp×tp passes the driver checks at 8, exercised
at 16 where the axis factorizations change (dp=4).
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_dryrun_multichip_16_devices():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the child must not reach for a chip
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(16)"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip OK" in proc.stdout
    assert "MoE OK" in proc.stdout
    assert "PP OK" in proc.stdout
