"""Time to first token, 95th percentile of the sampled requests: the tail
the scheduler's queue, eviction and the prefills ahead leave. A per-layer
metric, not a judged one: over the 83 sampled requests of a `sessions`
window it is the fourth-longest wait, and the driver's check read a spread
of 4-6% of its median, more than half the widest bound allowed (PERF.md,
PR 24); in a closed loop at the batch limit it is one request's wait for a
slot."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "ttft_ms_p95"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def compute(run):
    return percentile(_read.first_tokens_ms(run), 95)
