"""Gap between consecutive tokens of one request, all gaps of the sampled
requests pooled, 95th percentile: `itl_p95_ms` read per layer, in the
traced run of every cell. Judged only where it is steady: in
`mistral-7b-l16.sessions` the share of gaps that hold a prefill chunk lies
at one in twenty, so the percentile falls on either side of that edge
(30-36 or 42-47 ms in twelve runs, p90 27 and p99 154 ms in all of them;
the driver's check read spreads of 5.4% and 11.3% of its median: PERF.md,
PR 29)."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "itl_ms_p95"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"


def compute(run):
    return percentile(_read.token_gaps_ms(run), 95)
