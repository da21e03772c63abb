"""Of the decode step programs the traced slice's replicas launched, the
share launched before the host had read the tokens of the decode program
before them: such a program takes those tokens on the device, and the way
back, the step's bookkeeping and the next step's inputs run beside a busy
chip (ROADMAP S4). The engine's padded decode ``step.dispatch`` says so in
``ahead`` (1 or 0); a decode dispatch is one that names no ``prefill_pos``.
0 where no dispatch carries the attribute: a program older than it, or a
path that reads every program before it builds the next."""

from kvbench.metrics import _read

NAME = "launched_ahead_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def compute(run):
    if run.trace is None:
        return None
    decodes = [d for d in _read.phase_events(run, "step.dispatch")
               if "prefill_pos" not in d.stats]
    if not decodes:
        return 0.0
    ahead = sum(1 for d in decodes if int(d.stats.get("ahead", 0)) == 1)
    return 100.0 * ahead / len(decodes)
