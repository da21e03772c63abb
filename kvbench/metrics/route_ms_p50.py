"""Median of the harness span around ``router.route`` (hash the prompt,
look the chain up, score, add speculative entries)."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "route_ms_p50"
UNIT = "ms"
LAYER = "router"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    return percentile(_read.sampled_spans_ms(run, "route"), 50)
