"""Of the prompt tokens whose pages matched in the global pool at admission,
the share that was served from cache, over the traced slice's admissions of
a two-pool engine: a hit at depth ``d`` needs the global pool's whole chain
AND the window pool's trailing window below ``d``, so the engine walks back
to the deepest ``d`` whose window still stands (0 where none does). The
engine's ``window_hit_tokens`` over ``page_hit_tokens`` on
``enqueue.lookup``. 100 where every matched chain found its trailing
window; what is missing was evicted from the window pool under other
sessions and is computed again. Nothing where no admission carries the
counters (one pool), 0 where nothing matched at all."""

from kvbench.metrics import _gdn

NAME = "window_hit_share"
UNIT = "%"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    kept = _gdn.phase_sum(run, "enqueue.lookup", "window_hit_tokens")
    if kept is None:
        return None
    pages = _gdn.phase_sum(run, "enqueue.lookup", "page_hit_tokens")
    return 100.0 * kept / pages if pages else 0.0
