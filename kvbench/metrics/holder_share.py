"""Share of the sampled requests whose prompt extends an earlier one that
were admitted with part of it cached (``Request.cached_len > 0`` at
admission, or blocks restored after it). 0 where nothing extends."""


NAME = "holder_share"
UNIT = "%"
LAYER = "router"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    can = [r for r in run.sampled() if r.arrival.extends and r.enqueued]
    if not can:
        return 0.0
    return 100.0 * sum(r.cached_len > 0 for r in can) / len(can)
