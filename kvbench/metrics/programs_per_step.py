"""Programs the device ran per engine step in the traced slice: every
execution on the trace's modules line over the harness's ``step.work``
markers. A step of the padded scheduler runs one or two step programs; what
is above that are the small programs the host dispatches between them
(``logits[0, 0]``, ``jnp.argmax(logits[:, 0])`` outside the jit: slices, a
squeeze, an argmax), each a dispatch inside ``step()`` and a turn on the
device behind whatever the other replica queued."""

NAME = "programs_per_step"
UNIT = "count"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def compute(run):
    if run.trace is None or not run.trace.work:
        return None
    lo, hi = run.trace.window
    executions = sum(1 for p in run.trace.planes
                     for e in run.trace.modules[p] if lo <= e.start <= hi)
    # Replicas on several chips each mark their own steps: the markers
    # are the cell's, so the executions are summed over its chips too.
    return executions / len(run.trace.work)
