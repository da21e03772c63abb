"""Of the drafts the traced slice's speculative decode programs verified
(one a row a step), the share the main model agreed with: rows that emitted
two tokens. The engine counts both where they are first known, as it reads
a program's result: ``spec_drafted`` and ``spec_accepted`` on that program's
``step.fetch``. 0 when none was accepted; nothing where no draft was
verified (a model without a prediction module, a parent commit without the
mechanism). With seeded weights the module does not agree with the main
model and this reads about 0: the cell then shows the mechanism's cost and
its rollback, not its gain."""

from kvbench.metrics import _read

NAME = "spec_accept_share"
UNIT = "%"
LAYER = "model step"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    fetched = [e.stats for e in _read.phase_events(run, "step.fetch")
               if "spec_accepted" in e.stats]
    drafted = sum(int(s.get("spec_drafted", 0)) for s in fetched)
    if not drafted:
        return None
    return 100.0 * sum(int(s["spec_accepted"]) for s in fetched) / drafted
