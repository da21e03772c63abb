"""Share of the traced slice in which no operation ran on the chip: 1 -
busy / window, both from the trace's own timestamps, mean over the cell's
chips."""


NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
