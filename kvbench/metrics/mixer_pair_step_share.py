"""The two mixers' decode kernels' share of the decode programs' device
time over the traced slice, in a model that runs a state-space mixer and
attention side by side in every layer: the device time of the kernels a
trace calls ``mamba2_step`` and ``pallas_paged_decode_attention`` inside the
decode program over the whole of that program's executions. What is left
is the weights' read (the projections, the MLP, the head), the conv and the
page writes. Nothing where the program lacks either kernel."""

from kvbench.metrics import _gdn, _mixer_pair

NAME = "mixer_pair_step_share"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def compute(run):
    return _mixer_pair.pair_share(run, _gdn.DECODE)
