"""What the readers of ``gigachat3.5-ep16-l5``'s per-layer metrics share:
the counters the engine's phases carry in a traced run and the device time
of the recurrence's two kernels. Everything returns nothing where the
program has no such counter, kernel or count (a parent commit without the
mechanism; a configuration without linear layers)."""

from __future__ import annotations

from kvbench.metrics import _read

PREFILL = r"forward_prefill_pallas"
DECODE = r"forward_decode_pallas"


def phase_sum(run, phase: str, key: str):
    """Sum of a counter one of the engine's phases carries, or None where
    no such phase carries it."""
    got = [int(e.stats[key]) for e in _read.phase_events(run, phase)
           if key in e.stats]
    return sum(got) if got else None


def kernel_seconds(run, kernel: str, program: str) -> float:
    return sum(e.dur for e in _read.op_events(run, kernel, program)) * 1e-9
