"""A request's way to its first token as its engine tells it: the
``request.first_token`` markers of the traced slice, read once a run
(``of(run)``), for ``engine_queue_ms_p50``, ``behind_prefill_share``,
``engine_ttft_ms_p50``, ``prefill_own_device_share`` and
``ttft_outside_engine_ms_p50``.

The engine opens and closes the marker at once where a request's first token
stands (``telemetry/tracing.py: PHASE_NAMES``; ``MiniEngine._finish_prefill``).
It carries ``request_id``, ``prompt_tokens``, ``cached_tokens``, ``chunks``
(its prefill chunks dispatched), ``first_launch`` and ``last_launch`` (their
``step.dispatch``es' ``launch``), ``decodes_between`` (its engine's decode
programs dispatched from its first chunk to the marker) and three durations
in ns, each the difference of two readings of the engine's one clock:
``queued_ns`` (the end of ``enqueue()`` to the start of the ``step()`` that
first ran a chunk of it: the one that first picked it, unless a restore or
handoff gate held it then), of which ``behind_ns`` (with ``behind_chunks``:
that engine's steps in between whose prefill chunk was another request's),
and ``prefill_ns`` (the start of that ``step()`` to the marker). Durations
are read as they stand: no reader uses the marker's own place on the
trace's clock.

What the readers add from elsewhere: a marker's own chunks on the device
are the programs ``_launches.of(run)`` placed on the ``step.dispatch``es
that name its ``request_id`` with a ``launch`` from ``first_launch`` to
``last_launch`` (no second pairing; ``timed()``'s rule for a clock that lies
off), and the harness's record of the same request is ``run.requests``'s
``r<idx>`` (``harness/loop.py``) whose prompt is ``prompt_tokens`` long.

A program older than the marker (the parent's) opens none: every reader
then reads 0.0, not nothing, as it does of a slice in which no first token
fell. ``cached_tokens``, ``behind_chunks`` and ``decodes_between`` are read
by no metric: ``hack/kvbench_requests.py --trace 1`` prints them a request,
with a slice's split of the median first token and its checks of the
markers against the trace around them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from kvbench.harness.fleet import log
from kvbench.metrics import _launches, _read

MARKER = "request.first_token"
MS = _launches.MS


@dataclass
class Marker:
    event: object               # the host event (trace/reduce.py: Event)
    request_id: str
    prompt_tokens: int
    cached_tokens: int
    chunks: int
    first_launch: int
    last_launch: int
    decodes_between: int
    behind_chunks: int
    queued_ns: int
    behind_ns: int
    prefill_ns: int
    record: object = None       # the harness's RequestRecord, if it is one
    # Device ns of its own chunks; None where the slice does not hold
    # every one of them placed.
    own_device_ns: Optional[float] = None

    @property
    def engine_ns(self) -> int:
        """What the engine's TTFT histogram observes of it."""
        return self.queued_ns + self.prefill_ns

    @property
    def outside_ms(self) -> Optional[float]:
        """The harness's time to the first token less the engine's: the
        route, the harness's inbox and ``waiting``, ``enqueue()`` and the
        step's return. Two durations; no clock is compared."""
        rec = self.record
        if rec is None or not rec.token_times:
            return None
        return (rec.token_times[0] - rec.start) * 1e3 - self.engine_ns * MS


def _marker(event) -> Marker:
    s = event.stats
    return Marker(event, str(s["request_id"]), *(int(s[k]) for k in (
        "prompt_tokens", "cached_tokens", "chunks", "first_launch",
        "last_launch", "decodes_between", "behind_chunks", "queued_ns",
        "behind_ns", "prefill_ns")))


def _join_records(markers: list, run) -> None:
    by_id = {f"r{r.idx}": r for r in run.requests}
    for m in markers:
        rec = by_id.get(m.request_id)
        if rec is not None and rec.prompt_len == m.prompt_tokens:
            m.record = rec


def owner(mine: dict, dispatch) -> Optional[Marker]:
    """Whose chunk a ``step.dispatch`` launched, of the markers ``mine``
    holds by ``request_id``: it names the request, with a ``launch`` from
    the marker's first to its last."""
    s = dispatch.stats
    m = mine.get(str(s.get("request_id")))
    if m is not None and "launch" in s and (
            m.first_launch <= int(s["launch"]) <= m.last_launch):
        return m
    return None


def _join_chunks(markers: list, run) -> None:
    """Each marker's own chunks' device time, where the slice holds all of
    them placed."""
    mine = {m.request_id: m for m in markers}
    found = _launches.of(run)
    placed: dict = {}
    for p in (found.timed() if found is not None else []):
        m = owner(mine, p.dispatch)
        if m is not None:
            placed.setdefault(m.request_id, []).append(p.program.dur)
    for m in markers:
        durs = placed.get(m.request_id, [])
        if m.chunks and len(durs) == m.chunks:
            m.own_device_ns = float(sum(durs))


def of(run) -> Optional[list]:
    """The run's markers in the order they fell, read and joined once;
    None where the run was not traced."""
    if run.trace is None:
        return None
    if getattr(run, "first_tokens", None) is None:
        markers = [_marker(e) for e in _read.phase_events(run, MARKER)]
        if markers:
            _join_records(markers, run)
            _join_chunks(markers, run)
        run.first_tokens = markers
        log(f"first tokens: {len(markers)} markers in the slice, "
            f"{sum(1 for m in markers if m.outside_ms is not None)} of them "
            f"a request of the window with its first token, "
            f"{sum(1 for m in markers if m.own_device_ns is not None)} with "
            f"every chunk placed")
    return run.first_tokens
