"""Median wait from a request's start (due or send) to the start of the
first step that advanced its prefill: the harness's waiting queue, admission,
and the prefills ahead of it."""

from kvbench.harness.stats import percentile

NAME = "queue_wait_ms_p50"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    return percentile([(r.first_sched - r.start) * 1e3
                       for r in run.sampled()
                       if r.first_sched is not None], 50)
