"""The prediction module's share of the speculative decode program's device
time over the traced slice. A trace keeps an op's name and not the scope it
was written in (``mtp_draft``), so the module's ops are told by where they
stand: everything the module does takes its tokens from the acceptance
kernel (``ops/draft_accept.py``: ``mtp_accept``, one call a program), so the
ops of a program's run that start behind that call are the drafter's:
``W_eh``, its block with its attention kernel and experts, the second pass
of the head. Summed op time behind the marker over summed op time of the
same runs. Nothing where no program holds the marker (a model without a
module, a parent commit without the mechanism). In this configuration the
module is 1 block of 6 where the published model has 1 of 62: the share is
overstated about tenfold against a deployment."""

import bisect
import re

NAME = "mtp_draft_share"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

PROGRAM = r"forward_decode_pallas"
MARKER = r"^mtp_accept"


def compute(run):
    if run.trace is None:
        return None
    program, marker = re.compile(PROGRAM), re.compile(MARKER)
    behind = whole = 0.0
    for plane in run.trace.planes:
        ops = sorted((e for e in run.trace.ops[plane]
                      if program.search(e.stats.get("program", ""))),
                     key=lambda e: e.start)
        starts = [e.start for e in ops]
        for run_ in run.trace.modules[plane]:
            if not program.search(run_.name):
                continue
            inside = ops[bisect.bisect_left(starts, run_.start):
                         bisect.bisect_left(starts, run_.end)]
            mark = next((e.start for e in inside if marker.search(e.name)),
                        None)
            if mark is None:
                continue
            whole += sum(e.dur for e in inside)
            behind += sum(e.dur for e in inside if e.start > mark)
    if not whole:
        return None
    return 100.0 * behind / whole
