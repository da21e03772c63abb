"""Blocks the replicas' block managers evicted during the window
(``pool_stats()["evictions"]`` after minus before, all replicas), per
second of window. 0 when nothing was evicted."""


NAME = "evictions_per_s"
UNIT = "1/s"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    n = sum(run.pool_after[p]["evictions"] - run.pool_before[p]["evictions"]
            for p in run.pool_after)
    return n / run.seconds
