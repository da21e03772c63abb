"""Output tokens per second: every token any request produced in the sampled
part of the window, over its length. All the work and all the time."""

from kvbench.metrics import _read

NAME = "out_tok_s"
UNIT = "tokens/s"
LAYER = "end to end"
MOVES = ""
SOURCE = "host_clock"


def compute(run):
    n = sum(1 for r in run.requests for t in r.token_times
            if run.t_sample <= t <= run.t_end)
    return n / _read.sampled_seconds(run)
