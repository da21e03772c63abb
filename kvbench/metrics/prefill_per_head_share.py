"""Of the prefill chunks the traced slice's replicas launched, the share
whose latent-attention layers attended per head, on keys and values
expanded from the latents inside the kernel
(``ops/pallas_latent_prefill.py``), and not in the absorbed form: a chunk
padded to enough queries (256 and 512 at the published widths; ROADMAP
S10). The engine's ``step.dispatch`` of a chunk (one that names a
``prefill_pos``) says so in ``expanded_keys``: the keys a head expanded a
layer, 0 where the absorbed kernel ran. 0 where no chunk carries the
attribute (a program older than it); nothing where the slice holds no
chunk."""

from kvbench.metrics import _read

NAME = "prefill_per_head_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    chunks = [d for d in _read.phase_events(run, "step.dispatch")
              if "prefill_pos" in d.stats]
    if not chunks:
        return None
    per_head = sum(1 for d in chunks
                   if int(d.stats.get("expanded_keys", 0)) > 0)
    return 100.0 * per_head / len(chunks)
