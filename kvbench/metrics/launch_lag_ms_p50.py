"""The idle time in front of a step program that its launch accounts for,
median over the traced slice's paired programs (``_launches.py``): from the
start of its ``step.dispatch`` (the transfer of its packed inputs, the
jitted call, the launch), or from the end of what the chip ran before it if
that is later, to its start on the device. About 0 where the program queued
behind the other replica's."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _launches

NAME = "launch_lag_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    found = _launches.of(run)
    if found is None:
        return None
    return percentile([(p.program.start - p.waited_from) * _launches.MS
                       for p in found.timed()], 50)
