"""The paged decode attention kernel's share of its roofline over the
traced slice: the bytes of cache its calls must read (context lengths of the
decoding rows, window-capped, from the harness's work markers, through the
configuration's counts, ``run.counts``: K and V of a GQA pool, the one
stream of a latent pool) over the chip's HBM bandwidth, over the kernel's
device time. Memory-bound: one query row per sequence. KERNEL is the
Mosaic custom call's name as the trace gives it today (one call a layer,
``pallas_paged_decode_attention.<n>``), PROGRAM the jitted function's."""

from kvbench.metrics import _read

NAME = "attn_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


PROGRAM = r"forward_decode_pallas"
KERNEL = r"^pallas_paged_decode_attention"


def compute(run):
    events = _read.op_events(run, KERNEL, PROGRAM)
    seconds = sum(e.dur for e in events) * 1e-9
    if not seconds or not run.trace.work:
        return None  # no kernel time, or no step's counts to divide by
    keys = sum(int(w.get("decode_ctx", 0)) for w in run.trace.work)
    need = run.counts.decode_attention_bytes(run.cfg, keys)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
