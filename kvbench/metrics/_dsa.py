"""What the readers of ``deepseek-v3.2-exp-ep16-l5``'s per-layer metrics
share: the counters the engine's phases carry in a traced run and the
device ops of the decode step program. Everything returns nothing where the
program has no such counter (a parent commit without the mechanism)."""

from __future__ import annotations

from kvbench.metrics import _read

DECODE = r"forward_decode_pallas"


def dispatch_sum(run, key: str):
    """Sum of a counter the decode steps' ``step.dispatch`` phases carry
    (``index_keys``, ``selected_keys``: a layer's, from the rows' lengths),
    or None where no phase carries it."""
    got = [int(e.stats[key]) for e in _read.phase_events(run, "step.dispatch")
           if key in e.stats]
    return sum(got) if got else None


def fetched(run, program: str = "") -> list:
    """The ``step.fetch`` phases that read device counters (of ``program``:
    ``decode`` or ``prefill``; both by default), as their stats."""
    return [e.stats for e in _read.phase_events(run, "step.fetch")
            if "assignments_held" in e.stats
            and (not program or e.stats.get("counted_program") == program)]


def decode_op_seconds(run, pattern: str) -> float:
    return sum(e.dur for e in _read.op_events(run, pattern, DECODE)) * 1e-9


def decode_seconds(run) -> float:
    return sum(e.dur for e in _read.module_events(run, DECODE)) * 1e-9
