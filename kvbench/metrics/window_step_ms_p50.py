"""The host time a two-pool engine spends on its window pool in one
``step()``, median over the steps of the traced slice that spent any: the
``step.window`` phases of a (``pod``, ``step``) pair summed (pages ensured
under what a program writes, in ``step.inputs``; pages reclaimed behind the
window once a chunk is launched or a decode program read). Nothing where
the slice holds no such phase (a model with one pool)."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _read

NAME = "window_step_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    steps: dict = {}
    for e in _read.phase_events(run, "step.window"):
        key = (e.stats.get("pod"), e.stats.get("step"))
        steps[key] = steps.get(key, 0.0) + e.dur * 1e-6
    return percentile(list(steps.values()), 50) if steps else None
