"""Of the time from the start of the ``step()`` that first ran a chunk of a
request to its first token (``prefill_ns`` of its ``request.first_token``
marker, ``_first_token.py``; a restore or handoff gate that held it at the
head of the queue lies before, in ``queued_ns``), the share during which
the device ran that request's own prefill chunks: the device time of the
programs that ``_launches.of(run)`` placed on the ``step.dispatch``es
naming its
``request_id`` with a ``launch`` from the marker's ``first_launch`` to its
``last_launch``, summed over the slice's markers whose ``chunks`` are all
placed, over the sum of their ``prefill_ns``. Durations on either clock,
so a host clock that lies off the device's moves nothing
(``_launches.timed()`` decides which placed programs count).

The rest of ``prefill_ns`` is the chip on this replica's decode programs
(one between two chunks: the marker's ``decodes_between``), on the other
replica's programs, idle, or the host (inputs, dispatch, the way back, the
commit before the marker). A marker whose first chunks the slice cut is
left out of both sums. 0.0 where the slice holds no marker or no whole
one."""

from kvbench.metrics import _first_token

NAME = "prefill_own_device_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    markers = _first_token.of(run)
    if markers is None:
        return None
    whole = [m for m in markers if m.own_device_ns is not None]
    prefill = sum(m.prefill_ns for m in whole)
    if not prefill:
        return 0.0
    return 100.0 * sum(m.own_device_ns for m in whole) / prefill
