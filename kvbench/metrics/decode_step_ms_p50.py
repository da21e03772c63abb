"""Device time of one execution of the decode program, median over the
traced slice (the trace's modules line). PROGRAM is the jitted function's
name as the trace gives it today; the program sets no stable name yet."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "decode_step_ms_p50"
UNIT = "ms"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


PROGRAM = r"forward_decode_pallas"


def compute(run):
    return percentile([e.dur * 1e-6
                       for e in _read.module_events(run, PROGRAM)], 50)
