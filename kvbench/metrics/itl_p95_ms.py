"""Gap between consecutive tokens of one request, all gaps of the sampled
requests pooled, 95th percentile: the stall a prefill chunk, a commit or a
restore puts on running decodes."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "itl_p95_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = ""
SOURCE = "host_clock"


def compute(run):
    return percentile(_read.token_gaps_ms(run), 95)
