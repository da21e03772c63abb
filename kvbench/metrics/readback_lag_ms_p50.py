"""How long after the device was done the engine had its tokens, median over
the traced slice's fetched programs (``_launches.py``): the end of the
``step.fetch`` that read a program's tokens less the program's end on the
device (the copy back, the wake of the thread that waits, ``np.asarray``)."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _launches

NAME = "readback_lag_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    found = _launches.of(run)
    if found is None:
        return None
    return percentile([(p.fetch.end - p.program.end) * _launches.MS
                       for p in found.timed() if p.fetch is not None], 50)
