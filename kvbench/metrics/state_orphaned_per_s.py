"""Snapshots of sequence state the replicas' state pools dropped during the
window because a page they stood on was evicted under them
(``pool_stats()["state_orphaned"]`` after minus before, all replicas), per
second of window: the pages ran out before the slots did. They are among
``state_evictions_per_s``'s too. Nothing where the pools keep no states or
the program does not count them apart (a parent commit)."""


NAME = "state_orphaned_per_s"
UNIT = "1/s"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    if not all("state_orphaned" in stats
               for stats in run.pool_after.values()):
        return None
    n = sum(run.pool_after[p]["state_orphaned"]
            - run.pool_before[p]["state_orphaned"] for p in run.pool_after)
    return n / run.seconds
