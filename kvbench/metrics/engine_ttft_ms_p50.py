"""Median time to the first token inside the engine, as the engine tells
it: ``queued_ns + prefill_ns`` of the traced slice's ``request.first_token``
markers (``_first_token.py``), from the end of ``enqueue()`` to the first
token standing: what the engine's own ``kvtpu_engine_ttft_seconds``
observes, less the tail of ``enqueue()``. 0.0 where the slice holds no
marker."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _first_token

NAME = "engine_ttft_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    markers = _first_token.of(run)
    if markers is None:
        return None
    return percentile([m.engine_ns * _first_token.MS for m in markers],
                      50) or 0.0
