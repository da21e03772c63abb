"""Of the prompt tokens whose pages matched at admission, the share that
was served from cache: a hit needs a snapshot of the sequence state
standing inside the matched pages, and is cut back to the deepest one. The
engine's ``state_hit_tokens`` over ``page_hit_tokens`` on ``enqueue.lookup``,
over the traced slice's admissions. 100 where every matched page had a
state to resume from; what is missing was computed again."""

from kvbench.metrics import _gdn

NAME = "state_hit_share"
UNIT = "%"
LAYER = "block manager + offload"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    pages = _gdn.phase_sum(run, "enqueue.lookup", "page_hit_tokens")
    if not pages:
        return None
    return 100.0 * _gdn.phase_sum(run, "enqueue.lookup",
                                  "state_hit_tokens") / pages
