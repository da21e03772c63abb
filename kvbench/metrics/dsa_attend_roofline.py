"""Sparse attention's share of its roofline over the traced slice's decode
steps: the bytes of latent the selected keys hold (the engine's
``selected_keys`` counter on ``step.dispatch``: a layer's sum of min(a
row's keys, ``index_topk``); times the layers and the latent's padded
width, ``run.counts.latent_bytes``) over the chip's HBM bandwidth, over the
device time of the decode attention kernels in the decode program. Where
``attn_decode_roofline`` has only the rows' keys in all and counts a floor,
this counts what the kernels were given to read."""

from kvbench.metrics import _dsa

NAME = "dsa_attend_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

KERNEL = r"^pallas_paged_decode_attention"


def compute(run):
    keys = _dsa.dispatch_sum(run, "selected_keys")
    seconds = _dsa.decode_op_seconds(run, KERNEL)
    if keys is None or not seconds:
        return None
    need = run.counts.latent_bytes(run.cfg, keys)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
