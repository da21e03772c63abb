"""The decode attention kernel's share of its roofline in a program that
verifies a draft: two query positions a row fold in beside the 128 heads
(256 query rows over one shared latent head), so the kernel that is
bandwidth-bound at one position a row is compute-bound here (435 FLOP a
byte against the chip's ridge of 240). The larger of what its calls must
read (``decode_attention_bytes``: the rows' latents once a layer) over the
chip's HBM bandwidth and what they must multiply
(``verify_attention_flops``) over its bf16 peak, over the kernel's device
time in the traced slice. What the algorithm needs and no more: it can only
be understated. Nothing where the configuration's counts have no
``verify_attention_flops`` or the program verified nothing
(``spec_drafted`` on no ``step.dispatch``)."""

from kvbench.metrics import _read

NAME = "mla_verify_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"

PROGRAM = r"forward_decode_pallas"
KERNEL = r"^pallas_paged_decode_attention"


def compute(run):
    flops_of = getattr(run.counts, "verify_attention_flops", None)
    if flops_of is None or not any(
            "spec_drafted" in e.stats
            for e in _read.phase_events(run, "step.dispatch")):
        return None
    seconds = sum(e.dur for e in _read.op_events(run, KERNEL, PROGRAM)) * 1e-9
    if not seconds or not run.trace.work:
        return None
    keys = sum(int(w.get("decode_ctx", 0)) for w in run.trace.work)
    need = max(
        run.counts.decode_attention_bytes(run.cfg, keys)
        / run.peaks["hbm_bytes_per_s"],
        flops_of(run.cfg, keys, 2) / run.peaks["bf16_flops_per_s"])
    return 100.0 * need / seconds
