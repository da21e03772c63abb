"""Median time from the start of the step that started a request's restore
from the store to the end of the step in which its restored blocks landed."""

from kvbench.harness.stats import percentile

NAME = "restore_ms_p50"
UNIT = "ms"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    return percentile([(r.restore_t1 - r.restore_t0) * 1e3
                       for r in run.sampled()
                       if r.restore_t0 is not None
                       and r.restore_t1 is not None], 50)
