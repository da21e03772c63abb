"""Median of the router's own ``route.decide`` phase in the traced slice
(``scoring/router.py``: all of ``KVAwareRouter.route``). Its parts
(``route.expire`` / ``.hash`` / ``.lookup`` / ``.score`` / ``.speculate``)
are in ``breakdown.idle_gaps`` where they cover idle time, and their medians
on an earlier line of the run (``[kvbench] route.decide: ...``). A program
that opens no such phase (the parent's) is read through the harness's
``route`` annotation around the same call."""

from kvbench.harness.fleet import log
from kvbench.harness.stats import percentile
from kvbench.metrics import _read

NAME = "route_decide_ms_p50"
UNIT = "ms"
LAYER = "router"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"

PARTS = ("expire", "hash", "lookup", "score", "speculate")
SIZES = ("keys", "speculative", "expired", "best")


def compute(run):
    own = _read.phase_events(run, "route.decide")
    if own:
        parts = {p: percentile([e.dur * 1e-6 for e in _read.phase_events(
            run, f"route.{p}")], 50) for p in PARTS}
        sizes = {k: percentile([float(e.stats[k]) for e in own
                                if k in e.stats], 50) for k in SIZES}
        log(f"route.decide: {len(own)} decisions; ms p50 of each part: "
            f"{parts}; p50 of what it carries: {sizes}")
    events = own or _read.phase_events(run, "route")
    return percentile([e.dur * 1e-6 for e in events], 50)
