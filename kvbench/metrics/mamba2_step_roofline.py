"""The Mamba-2 decode recurrence's share of its roofline over the traced
slice's decode steps: the bytes of state and conv tail it must read and
write (the engine's ``state_rows`` counter on a decode step's
``step.dispatch``; times the Mamba-2 layers, a state's and a tail's bytes
and 2, ``run.counts.mamba2_step_bytes``) over the chip's HBM bandwidth,
over the device time of the kernels a trace calls ``mamba2_step`` inside
the decode program. Memory-bound: a token does five operations a state
element it moves 8 bytes for.

LIVE rows are counted (``state_rows``: the rows that decode), not the rows
the kernel walks: a decode step's shape is ``max_batch`` rows and the
kernel reads and writes the spare slot for every row that decodes nothing,
so with few rows in flight the reading falls by the padded rows' share,
which is the kernel's waste and belongs in the number."""

from kvbench.metrics import _gdn

NAME = "mamba2_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^mamba2_step"


def compute(run):
    rows = _gdn.phase_sum(run, "step.dispatch", "state_rows")
    seconds = _gdn.kernel_seconds(run, KERNEL, _gdn.DECODE)
    if rows is None or not seconds or not hasattr(run.counts,
                                                  "mamba2_step_bytes"):
        return None
    need = run.counts.mamba2_step_bytes(run.cfg, rows)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
