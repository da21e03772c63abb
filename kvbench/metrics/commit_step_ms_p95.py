"""Host wall of the steps in which a prefill finished, 95th percentile: the
commit of its blocks (and with a storage tier the write-through gather) is
inside that step, and every running decode waits for it."""

from kvbench.metrics import _read
from kvbench.harness.stats import percentile

NAME = "commit_step_ms_p95"
UNIT = "ms"
LAYER = "block manager + offload"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    return percentile([(s.t1 - s.t0) * 1e3
                       for s in _read.sampled_steps(run)
                       if s.prefill_done], 95)
