"""The Mamba-2 decode recurrence's share of the decode programs' device
time over the traced slice: the device time of the kernels a trace calls
``mamba2_step`` inside the decode program over the whole of that program's
executions. What is left is the weights' read (the projections, the
experts, the head), attention over the one layer of pages and the conv.
Nothing where the program has no such kernel."""

from kvbench.metrics import _gdn, _read

NAME = "mamba2_step_share"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^mamba2_step"


def compute(run):
    kernel = _gdn.kernel_seconds(run, KERNEL, _gdn.DECODE)
    whole = sum(e.dur for e in _read.module_events(run, _gdn.DECODE)) * 1e-9
    if not kernel or not whole:
        return None
    return 100.0 * kernel / whole
