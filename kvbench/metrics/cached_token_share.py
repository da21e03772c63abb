"""Prompt tokens the sampled requests did not have to compute (prefix hits
in HBM at admission plus blocks restored from the store), over their prompt
tokens."""


NAME = "cached_token_share"
UNIT = "%"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def compute(run):
    reqs = [r for r in run.sampled() if r.enqueued]
    tokens = sum(r.prompt_len for r in reqs)
    if not tokens:
        return None
    return 100.0 * sum(r.cached_len for r in reqs) / tokens
