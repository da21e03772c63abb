"""Process start to window start: build, load, compile, probe, warm-up,
set-up traffic."""


NAME = "setup_s"
UNIT = "s"
LAYER = "end to end"
MOVES = ""
SOURCE = "host_clock"


def compute(run):
    return run.setup_seconds
