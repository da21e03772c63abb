"""The channel-wise decode recurrence's share of its roofline over the
traced slice's decode steps: the bytes of state it must read and write (the
engine's ``state_rows`` counter on a decode step's ``step.dispatch``; times
the linear layers, a state's bytes and 2, ``run.counts.kda_step_bytes``)
over the chip's HBM bandwidth, over the device time of the kernels a trace
calls ``kda_step`` inside the decode program. Memory-bound: a token does
seven operations a state element it moves 8 bytes for."""

from kvbench.metrics import _gdn

NAME = "kda_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^kda_step"


def compute(run):
    rows = _gdn.phase_sum(run, "step.dispatch", "state_rows")
    seconds = _gdn.kernel_seconds(run, KERNEL, _gdn.DECODE)
    if rows is None or not seconds or not hasattr(run.counts,
                                                  "kda_step_bytes"):
        return None
    need = run.counts.kda_step_bytes(run.cfg, rows)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
