"""Decoding requests per step, mean over the steps that decoded, from the
harness's per-step record."""

from kvbench.metrics import _read
from kvbench.harness.stats import mean

NAME = "decode_rows_mean"
UNIT = "rows"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    return mean(s.decode_rows for s in _read.sampled_steps(run)
                if s.decode_rows)
