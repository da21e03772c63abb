"""Snapshots of sequence state the replicas' state pools gave up during the
window (``pool_stats()["state_evictions"]`` after minus before, all
replicas: least-recently-used ones that made room, and those whose pages
were evicted under them), per second of window. Nothing where the pools
keep no states."""


NAME = "state_evictions_per_s"
UNIT = "1/s"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    if not all("state_evictions" in stats
               for stats in run.pool_after.values()):
        return None
    n = sum(run.pool_after[p]["state_evictions"]
            - run.pool_before[p]["state_evictions"] for p in run.pool_after)
    return n / run.seconds
