"""FLOPs the real (unpadded) prompt tokens of the traced slice's prefill
chunks need (from the harness's work markers, through the configuration's
counts, ``run.counts``) over the chip's bf16 peak, over the prefill
program's device time. Padding to the power-of-two bucket lowers it."""

from kvbench.metrics import _read

NAME = "prefill_mfu"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


PROGRAM = r"forward_prefill_pallas"


def compute(run):
    seconds = sum(e.dur for e in _read.module_events(run, PROGRAM)) * 1e-9
    if not seconds or not run.trace.work:
        return None  # no kernel time, or no step's counts to divide by
    flops = sum(run.counts.prefill_flops(
        run.cfg, int(w.get("prefill_pos", 0)),
        int(w.get("prefill_tokens", 0))) for w in run.trace.work)
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / seconds
