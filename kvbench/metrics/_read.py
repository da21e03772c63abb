"""What several metric readers read the same way. A reader takes a
``harness.loop.Run`` and returns a number, or None when the run holds
nothing for it."""

from __future__ import annotations

import re


def first_tokens_ms(run) -> list:
    """Start (due or send) to first token, of every sampled request that
    had a first token inside the window."""
    return [(r.token_times[0] - r.start) * 1e3 for r in run.sampled()
            if r.token_times and r.token_times[0] <= run.t_end]


def token_gaps_ms(run) -> list:
    """Gaps between consecutive tokens of one request, all sampled
    requests pooled, inside the window."""
    out = []
    for r in run.sampled():
        ts = [t for t in r.token_times if t <= run.t_end]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return out


def sampled_steps(run) -> list:
    """Every replica's steps inside the sampled part of the window."""
    return [s for steps in run.steps.values() for s in steps
            if s.t0 >= run.t_sample and s.t1 <= run.t_end]


def sampled_spans_ms(run, name: str) -> list:
    return [(b - a) * 1e3 for a, b in run.spans.get(name, [])
            if a >= run.t_sample and b <= run.t_end]


def sampled_seconds(run) -> float:
    return run.t_end - run.t_sample


def phase_events(run, name: str) -> list:
    """The events of one host span in the traced slice, in start order:
    each has ``start`` and ``dur`` in ns on the trace's clock and ``stats``,
    its attributes. For an engine phase (``telemetry/tracing.py:
    PHASE_NAMES``; on in a traced run) those are ``pod``, ``step`` (the
    ordinal of the ``step()`` it ran in) and whatever the phase carries:
    ``step.finish`` the step's ``programs`` and ``transfers``,
    ``step.dispatch`` its ``rows``, ``step.emit`` its ``events``. Nothing
    where the run was not traced or the trace holds no such event."""
    if run.trace is None:
        return []
    return list(run.trace.events.get(name, []))


def module_events(run, pattern: str) -> list:
    """Device events of the programs whose name matches, over the cell's
    chips (the trace's modules line)."""
    if run.trace is None:
        return []
    rx = re.compile(pattern)
    return [e for p in run.trace.planes for e in run.trace.modules[p]
            if rx.search(e.name)]


def op_events(run, op_pattern: str, program_pattern: str) -> list:
    """Device ops whose name matches, inside programs whose name matches."""
    if run.trace is None:
        return []
    op_rx, prog_rx = re.compile(op_pattern), re.compile(program_pattern)
    return [e for p in run.trace.planes for e in run.trace.ops[p]
            if op_rx.search(e.name)
            and prog_rx.search(e.stats.get("program", ""))]
