"""``hack/state_pool_replay.py``: the schedule of a cell whose model keeps
a sequence state, replayed on the host through the tree's own block
manager, state pool, admission and snapshot rules. The replay's totals are
held to the pools' own counters, and the cells' numbers to what the
structures give (a traffic file's ``structure_seed`` fixes them for every
``--seed``): a PR that moves eviction's order, a snapshot rule or a pool's
size moves them here, in seconds and without a chip, and says so by
editing this table."""

import importlib.util
import io
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL_5 = "gigachat3.5-ep16-l5.sessions-32k"
CELL_6 = "solar-open2-ep16-l8.sessions-64k"


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "state_pool_replay", ROOT / "hack" / "state_pool_replay.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", [CELL_5, CELL_6])
def test_a_rehearsed_cells_totals_are_the_pools_own_counters(tool, cell):
    """What the replay adds up request by request, in set-up and in the
    window, is what each replica's ``pool_stats()`` has counted in all."""
    out = io.StringIO()
    got = tool.replay(cell, seconds=12.0, rehearse=True, out=out)
    for pod, replica in got["replicas"].items():
        stats = replica.block_manager.pool_stats()
        for k in tool.COUNTERS:
            assert got["setup"][pod][k] + got["window"][pod][k] == stats[k]
        assert stats["state_working"] == 0
        assert (stats["state_snapshots"]
                == len(replica.state_pool.snapshots) < stats["state_slots"])
    assert 0 < got["sampled"] and 0.0 < got["share"] < 100.0
    lines = out.getvalue().splitlines()
    assert sum("*" in line for line in lines[1:]) == got["sampled"] + 1
    assert "cached_token_share" in lines[-7]


def test_cell_6_rehearsed_reads_what_its_rehearsal_on_the_engine_reads(tool):
    """``kvbench/run.py --workload <cell 6> --seconds 12 --rehearse``
    serves this schedule through real engines and reads
    ``cached_token_share`` 77.17% of its 22 sampled turns."""
    got = tool.replay(CELL_6, seconds=12.0, rehearse=True, out=io.StringIO())
    assert (got["sampled"], round(got["share"], 1)) == (22, 77.2)


# With a snapshot kept at every multiple of 4096 a prefill passed (before
# PR 51) cell 6 read 7 misses, 51.8%, 169,448 tokens and 350 chunks, with
# 36 snapshots evicted for room in set-up; cell 5 the same 8, 82.9%,
# 119,626 and 552, with 3 evicted in set-up and 78 in the window.
@pytest.mark.parametrize(
    "cell,sampled,misses,share,prefilled,chunks,for_room", [
        (CELL_6, 14, 3, 83.1, 59624, 136, 0),
        (CELL_5, 48, 8, 82.9, 119626, 552, 3),
    ])
def test_a_cells_schedule_replayed_at_its_own_sizes(
        tool, cell, sampled, misses, share, prefilled, chunks, for_room):
    got = tool.replay(cell, out=io.StringIO())
    assert (got["sampled"], got["misses"], round(got["share"], 1),
            got["prefilled"], got["chunks"]) == (
        sampled, misses, share, prefilled, chunks)
    counts = [c for part in ("setup", "window") for c in got[part].values()]
    # Snapshots that left to make room for another: a long prefill writes
    # its checkpoints over one another and asks nobody for a slot.
    assert sum(c["state_evictions"] - c["state_orphaned"]
               for c in counts) == for_room
    assert sum(c["state_replaced"] for c in counts) > 30
