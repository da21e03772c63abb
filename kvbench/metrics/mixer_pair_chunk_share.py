"""The two mixers' prefill kernels' share of the prefill programs' device
time over the traced slice, in a model that runs a state-space mixer and
attention side by side in every layer: the device time of the kernels a
trace calls ``mamba2_scan`` and ``pallas_paged_prefill_attention`` inside
the prefill program over the whole of that program's executions. What is
left is the matmuls (the projections, the MLP, the head), the conv and the
page writes. Nothing where the program lacks either kernel."""

from kvbench.metrics import _gdn, _mixer_pair

NAME = "mixer_pair_chunk_share"
UNIT = "%"
LAYER = "model step"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def compute(run):
    return _mixer_pair.pair_share(run, _gdn.PREFILL)
