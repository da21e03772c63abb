"""The paged decode attention kernel's share of its roofline over the
traced slice's decode steps of a model that keeps a window pool beside a
global pool: the bytes its calls must read from BOTH pools
(``run.counts.pools_decode_attention_bytes`` of the engine's own
``full_keys`` and ``window_keys`` on the decode steps' ``step.dispatch``:
the live rows' contexts in the full layers, capped at the window in the
window layers) over the chip's HBM bandwidth, over the kernel's device time
in the decode program. Memory-bound: one query row a sequence. LIVE rows
are counted, not the ``max_batch`` rows a step is padded to. The list-less
``attn_decode_roofline`` divides the same seconds into the harness's
window-capped keys and is a floor here. Nothing where the program carries
no such counter or the counts no such function."""

from kvbench.metrics import _pools

NAME = "attn_pools_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def compute(run):
    keys = _pools.decode_keys(run)
    seconds = _pools.kernel_seconds(run)
    if (keys is None or not seconds
            or not hasattr(run.counts, "pools_decode_attention_bytes")):
        return None
    need = run.counts.pools_decode_attention_bytes(run.cfg, *keys)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
