"""The indexer's scoring kernel's share of its roofline over the traced
slice's decode steps: the bytes of index keys it must read (the engine's
``index_keys`` counter on ``step.dispatch``: a layer's, the keys of the rows
that hold more than ``index_topk``; times the layers and 2 bytes a value,
``run.counts.index_bytes``) over the chip's HBM bandwidth, over the device
time of the kernels a trace calls ``dsa_index_scores`` inside the decode
program. Memory-bound: 64 x 128 multiply-adds a key against 256 bytes. The
gather that lays a row's pages side by side for the kernel is not in its
time; ``dsa_select_share`` holds it."""

from kvbench.metrics import _dsa

NAME = "dsa_index_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^dsa_index_scores"


def compute(run):
    keys = _dsa.dispatch_sum(run, "index_keys")
    seconds = _dsa.decode_op_seconds(run, KERNEL)
    if keys is None or not seconds:
        return None
    need = run.counts.index_bytes(run.cfg, keys)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
