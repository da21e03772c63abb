"""The two mixers' decode kernels' share of their roofline TOGETHER, over
the traced slice's decode steps of a model that runs a state-space mixer
and attention side by side in every layer: the bytes both must move (the
state and conv tail of every row that decodes, read and written,
``run.counts.mamba2_step_bytes`` of the engine's ``state_rows`` counter on a
decode step's ``step.dispatch``; plus the keys and values of those rows'
contexts, ``run.counts.decode_attention_bytes`` of the harness's
``decode_ctx`` work markers) over the chip's HBM bandwidth, over the device
time of the kernels a trace calls ``mamba2_step`` and
``pallas_paged_decode_attention`` inside the decode program. Memory-bound,
both.

LIVE rows are counted (the rows that decode), not the rows the kernels
walk: a decode step's shape is ``max_batch`` rows, so what a kernel spends
on a row that decodes nothing is its waste and lowers the number. Nothing
where the program has no state kernel, no such counter or no such count."""

from kvbench.metrics import _gdn, _mixer_pair

NAME = "mixer_pair_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def compute(run):
    rows = _gdn.phase_sum(run, "step.dispatch", "state_rows")
    seconds = _mixer_pair.pair_seconds(run, _gdn.DECODE)
    if (rows is None or not seconds or not run.trace.work
            or not hasattr(run.counts, "mamba2_step_bytes")):
        return None
    keys = sum(int(w.get("decode_ctx", 0)) for w in run.trace.work)
    need = (run.counts.mamba2_step_bytes(run.cfg, rows)
            + run.counts.decode_attention_bytes(run.cfg, keys))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
