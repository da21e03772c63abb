"""Of the time the traced slice's requests spent inside their engines
before their first token (``queued_ns + prefill_ns`` of every
``request.first_token`` marker, ``_first_token.py``), the share they stood
behind another request's prefill chunks: ``behind_ns``, the wall time of
their engine's steps that ran a chunk of a request ahead of them in its
queue (one prefill at a time, the oldest first). What a scheduler that
prefills more than one request at a time, or the shortest first, has to
win (ROADMAP S13). Sums over the slice, not a median: one request behind a
document's chunks weighs what it waited. 0.0 where the slice holds no
marker."""

from kvbench.metrics import _first_token

NAME = "behind_prefill_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    markers = _first_token.of(run)
    if markers is None:
        return None
    inside = sum(m.engine_ns for m in markers)
    if not inside:
        return 0.0
    return 100.0 * sum(m.behind_ns for m in markers) / inside
