"""What the readers of ``falcon-h1-34b-l9``'s per-layer metrics share: the
device time of the two mixers' kernels TOGETHER inside one kind of step
program, in a model that runs a state-space mixer and attention side by
side in every layer. Nothing where the program lacks either kernel (a
parent commit without the layer; a model with one mixer a layer)."""

from __future__ import annotations

from kvbench.metrics import _gdn, _read

# (the state-space mixer's kernel, the paged attention kernel) a program
KERNELS = {
    _gdn.DECODE: (r"^mamba2_step", r"^pallas_paged_decode_attention"),
    _gdn.PREFILL: (r"^mamba2_scan", r"^pallas_paged_prefill_attention"),
}


def pair_seconds(run, program: str):
    """Seconds both kernels ran inside ``program``, or None without both."""
    state, pages = (_gdn.kernel_seconds(run, kernel, program)
                    for kernel in KERNELS[program])
    return state + pages if state and pages else None


def pair_share(run, program: str):
    """Both kernels' share (%) of ``program``'s whole device time."""
    pair = pair_seconds(run, program)
    whole = sum(e.dur for e in _read.module_events(run, program)) * 1e-9
    return 100.0 * pair / whole if pair and whole else None
