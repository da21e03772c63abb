"""Median wait of a request inside its engine before its prefill began, as
the engine tells it: ``queued_ns`` of the traced slice's
``request.first_token`` markers (``_first_token.py``), from the end of
``enqueue()`` to the start of the ``step()`` that first ran a chunk of it:
behind older requests' chunks (``behind_prefill_share``), then at the head
of the queue behind its own restore or handoff gate, and the caller's time
between steps. The program's own twin of
``queue_wait_ms_p50``, which is the harness's bookkeeping from the
request's due time and holds the route, the harness's inbox and
``waiting``, and ``enqueue()`` as well. 0.0 where the slice holds no
marker (a program older than it, or no first token fell there)."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _first_token

NAME = "engine_queue_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    markers = _first_token.of(run)
    if markers is None:
        return None
    return percentile([m.queued_ns * _first_token.MS for m in markers],
                      50) or 0.0
