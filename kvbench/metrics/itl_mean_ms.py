"""Gap between consecutive tokens of one request, all gaps of the sampled
requests pooled, mean: the time a user waits for the next token, over all
the gaps of the window (decode steps, and every prefill chunk, commit or
restore that fell between two of them). Judged in every cell, and what the
per-layer metrics of the decode path move; `itl_p95_ms` is judged beside it
only where it is steady (its entry's `workloads`; PERF.md, PR 29)."""

from kvbench.metrics import _read
from kvbench.harness.stats import mean

NAME = "itl_mean_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = ""
SOURCE = "host_clock"


def compute(run):
    return mean(_read.token_gaps_ms(run))
