"""Median of the harness span around the sink's ``Pool.process_event_batch``
(it runs inside ``step()``)."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "ingest_ms_p50"
UNIT = "ms"
LAYER = "event ingest"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    return percentile(_read.sampled_spans_ms(run, "ingest"), 50)
