"""Median of the pool's own ``ingest`` phase in the traced slice
(``events/pool.py``: one event batch applied to the index inside
``Pool.process_event_batch``). A program that opens no such phase (the
parent's) is read through the engine's ``step.emit``, which nests the sink
that calls the pool."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _read

NAME = "ingest_apply_ms_p50"
UNIT = "ms"
LAYER = "event ingest"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    events = (_read.phase_events(run, "ingest")
              or _read.phase_events(run, "step.emit"))
    return percentile([e.dur * 1e-6 for e in events], 50)
