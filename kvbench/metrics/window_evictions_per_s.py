"""Blocks the replicas' WINDOW pools evicted during the window
(``pool_stats()["window_evictions"]`` after minus before, all replicas:
least-recently-used cached window pages that made room for a running row's
next page), per second of window. Each one is a trailing window some later
turn can no longer resume on. ``evictions_per_s`` reads the global pool's
manager alone. Nothing where the engines keep one pool."""


NAME = "window_evictions_per_s"
UNIT = "1/s"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    if not all("window_evictions" in stats
               for stats in run.pool_after.values()):
        return None
    n = sum(run.pool_after[p]["window_evictions"]
            - run.pool_before[p]["window_evictions"] for p in run.pool_after)
    return n / run.seconds
