"""Median of what a request's time to its first token holds outside its
engine: per ``request.first_token`` marker of the traced slice
(``_first_token.py``) joined to the harness's record of the same request
(``request_id`` is ``r<idx>``, and the prompt as long), the harness's
``token_times[0] - start`` less the marker's ``queued_ns + prefill_ns``:
the route (two hashes of the prompt with ``enqueue.hash``), the harness's
inbox and ``waiting`` queue, ``enqueue()`` itself, and the step's return.
Both terms are durations: no clock is compared with another. 0.0 where the
slice holds no marker of a request of the window."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _first_token

NAME = "ttft_outside_engine_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    markers = _first_token.of(run)
    if markers is None:
        return None
    outside = [m.outside_ms for m in markers if m.outside_ms is not None]
    return percentile(outside, 50) or 0.0
