"""Of the time the slice's replicas spent inside ``step.fetch``, the share
during which the device ran a program that another ``pod`` launched
(``_launches.py`` names each paired program's): how much of a replica's wait
is the other replica's step, which is why a step's gain shows more than once
in the gap between two tokens."""

from kvbench.metrics import _launches, _read
from kvbench.trace.reduce import overlap

NAME = "fetch_other_pod_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    found = _launches.of(run)
    pairs = found.timed() if found is not None else []
    fetches = _read.phase_events(run, _launches.FETCH)
    waited = sum(f.dur for f in fetches)
    if not pairs or not waited:
        return None
    # A chip runs one program at a time: in start order they are disjoint.
    others = {pod: [(p.program.start, p.program.end) for p in pairs
                    if p.pod != pod]
              for pod in {f.stats.get("pod") for f in fetches}}
    return 100.0 * sum(overlap((f.start, f.end), others[f.stats.get("pod")])
                       for f in fetches) / waited
