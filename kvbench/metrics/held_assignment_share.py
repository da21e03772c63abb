"""Of the assignments the router made (real tokens x experts a token x
routed layers), the share that fell to the experts this chip holds: the
engine's ``assignments_held`` counter, counted on the device and read back
with the sampled tokens (``step.fetch``), over ``counted_tokens`` x
``num_experts_per_token`` x routed layers. 100 x held / experts where the
router is as wide as published and routes evenly (6.25 at 16 of 256): a
reading near 100 would mean the router had been cut to the experts held."""

from kvbench.metrics import _dsa

NAME = "held_assignment_share"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def compute(run):
    rows = _dsa.fetched(run)
    tokens = sum(int(r.get("counted_tokens", 0)) for r in rows)
    if not tokens:
        return None
    made = tokens * run.cfg.num_experts_per_token * len(run.cfg.moe_layers)
    return 100.0 * sum(int(r["assignments_held"]) for r in rows) / made
