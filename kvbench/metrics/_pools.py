"""What the readers of ``mellum2-12b-ep4``'s per-layer metrics share: the
counters a two-pool engine's phases carry in a traced run (a window pool
beside a global pool) and the paged decode kernel's device time in the
decode program. Everything returns nothing where the program has no such
counter or phase (a parent commit without the mechanism; a model with one
pool)."""

from __future__ import annotations

from kvbench.metrics import _gdn, _read

KERNEL = r"^pallas_paged_decode_attention"


def decode_keys(run):
    """``(full_keys, window_keys)`` summed over the slice's decode
    dispatches (a ``step.dispatch`` without ``prefill_pos``): over the live
    rows, the keys a full layer attends and the keys a window layer does.
    None where no decode dispatch carries them."""
    got = [(int(e.stats["full_keys"]), int(e.stats["window_keys"]))
           for e in _read.phase_events(run, "step.dispatch")
           if "full_keys" in e.stats and "prefill_pos" not in e.stats]
    if not got:
        return None
    return sum(f for f, _ in got), sum(w for _, w in got)


def kernel_seconds(run) -> float:
    """Device seconds of the paged decode kernel inside the decode program
    (both pools' calls: one a layer)."""
    return _gdn.kernel_seconds(run, KERNEL, _gdn.DECODE)
