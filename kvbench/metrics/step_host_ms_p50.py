"""The engine's own host time in one ``step()``, median over the steps of
the traced slice: what the engine's phases inside the step cover, all but
``step.fetch`` (the blocking read of the tokens: the wait for the device),
as PR 25's overlay defined it. Phases nest (``step.emit`` inside
``step.commit``) and one step opens several of a name (inputs, dispatch and
sample once per program), so a step's time is the length of the union of
its phases' intervals between the start of its ``step.offload_poll`` and
the end of its ``step.finish`` (a ``step.emit`` opened by an eviction inside
``enqueue()`` carries the last step's ordinal and lies outside). A step is
one (``pod``, ``step``) pair; one the slice cut at either end is left out.
The phases are on in a traced run only (``harness/fleet.py``)."""

from kvbench.harness.stats import percentile
from kvbench.metrics import _read
from kvbench.trace.reduce import total, union

NAME = "step_host_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"

FIRST, LAST, WAIT = "step.offload_poll", "step.finish", "step.fetch"


def step_host_ms(run) -> list:
    """Host milliseconds of every whole step in the traced slice."""
    if run.trace is None:
        return []

    def key(e):
        return (e.stats.get("pod"), e.stats.get("step"))

    begin = {key(e): e.start for e in _read.phase_events(run, FIRST)}
    extent = {key(e): (begin[key(e)], e.end)
              for e in _read.phase_events(run, LAST) if key(e) in begin}
    inside: dict = {k: [] for k in extent}
    for name in run.trace.events:
        if not name.startswith("step.") or name == WAIT:
            continue
        for e in _read.phase_events(run, name):
            if key(e) in extent:
                inside[key(e)].append((e.start, e.end))
    return [total(union(ivs, clip=extent[k])) * 1e-6
            for k, ivs in inside.items()]


def compute(run):
    return percentile(step_host_ms(run), 50)
