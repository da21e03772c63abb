"""The Mamba-2 scan's share of its roofline over the traced slice's prefill
chunks: what the recurrence needs for the chunks' real tokens (the engine's
``scan_tokens`` counter on a prefill's ``step.dispatch``;
``run.counts.mamba2_scan_flops`` and ``mamba2_scan_bytes``, all Mamba-2
layers) at the chip's peaks, the larger of operations over bf16 FLOP/s and
bytes over HBM bandwidth, over the device time of the kernels a trace calls
``mamba2_scan`` inside the prefill program. The count is the definition's
(a token at a time, five operations a state element) and the kernel folds a
block into matrix products in float32 at six bfloat16 passes each, so a
reading far under 100 is the form's price, not idleness."""

from kvbench.metrics import _gdn

NAME = "mamba2_scan_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"

KERNEL = r"^mamba2_scan"


def compute(run):
    tokens = _gdn.phase_sum(run, "step.dispatch", "scan_tokens")
    seconds = _gdn.kernel_seconds(run, KERNEL, _gdn.PREFILL)
    if tokens is None or not seconds or not hasattr(run.counts,
                                                    "mamba2_scan_flops"):
        return None
    least = max(run.counts.mamba2_scan_flops(run.cfg, tokens)
                / run.peaks["bf16_flops_per_s"],
                run.counts.mamba2_scan_bytes(run.cfg, tokens)
                / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
