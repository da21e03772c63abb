"""The paged decode attention kernel's share of the decode programs' device
time over the traced slice, in a model that keeps a window pool beside a
global pool: the kernel's calls over both pools (one a layer) over the
whole of the decode program's executions. What is left is the weights'
read (the projections, the held experts' grouped matmuls, the head), the
norms, RoPE and the page writes. Nothing where the engine carries no
two-pool counter (one pool: ``attn_decode_roofline`` reads that kernel)."""

from kvbench.metrics import _gdn, _pools, _read

NAME = "attn_pools_step_share"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def compute(run):
    seconds = _pools.kernel_seconds(run)
    whole = sum(e.dur for e in _read.module_events(run, _gdn.DECODE)) * 1e-9
    if _pools.decode_keys(run) is None or not seconds or not whole:
        return None
    return 100.0 * seconds / whole
