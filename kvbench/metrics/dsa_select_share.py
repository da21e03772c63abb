"""Selection's share of the decode program's device time over the traced
slice: what lies between the indexer's scores and attention over the chosen
keys, which is XLA's work and no kernel of ours: laying a row's pages of
index keys side by side for the scoring kernel (a gather), the exact top-k
of a row's scores (a sort), and the gather of the chosen latents into a
pool of their own. A trace names an XLA op by what it is, not by the scope
it was written in, so the ops are told by name: OPS, read off the first
traced run of the cell on a v5e (PERF.md, PR 34). Elementwise work that the
compiler fused into a ``fusion`` is not told apart from the model's own and
is left out: the share is a floor."""

from kvbench.metrics import _dsa

NAME = "dsa_select_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

OPS = r"^(sort|gather|topk|top_k|custom-call|scatter)"


def compute(run):
    if _dsa.dispatch_sum(run, "selected_keys") is None:
        return None  # a program without the mechanism selects nothing
    whole = _dsa.decode_seconds(run)
    if not whole:
        return None
    return 100.0 * _dsa.decode_op_seconds(run, OPS) / whole
