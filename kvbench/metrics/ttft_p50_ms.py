"""Time to first token, median: from when the request was due (open loop)
or sent (closed loop) to the end of the step that produced its first token."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "ttft_p50_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = ""
SOURCE = "host_clock"


def compute(run):
    return percentile(_read.first_tokens_ms(run), 50)
