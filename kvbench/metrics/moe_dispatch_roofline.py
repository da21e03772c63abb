"""The routed experts' grouped matmuls' share of their roofline over the
traced slice's decode steps: the larger of their FLOPs over the chip's bf16
peak and the bytes of the touched experts' weights over its HBM bandwidth,
over the device time of the kernels a trace calls ``gmm`` inside the decode
program. FLOPs: ``assignments_held`` x 3 matrices x 2 x hidden x expert
width; bytes: ``experts_touched`` x 3 matrices x hidden x expert width x 2
(both counters are sums over the routed layers, counted on the device and
read on ``step.fetch``). A decode step sends a fraction of a token to each
expert held, so it is the weights' bytes that bound it."""

from kvbench.metrics import _dsa

NAME = "moe_dispatch_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^gmm"


def compute(run):
    rows = _dsa.fetched(run, "decode")
    seconds = _dsa.decode_op_seconds(run, KERNEL)
    if not rows or not seconds:
        return None
    cfg = run.cfg
    matrix = cfg.hidden_size * cfg.moe_intermediate_size
    flops = 6.0 * matrix * sum(int(r["assignments_held"]) for r in rows)
    weights = 6.0 * matrix * sum(int(r["experts_touched"]) for r in rows)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                weights / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
