"""Short unshared prompts from a fixed number of clients (closed loop).

Nothing is shared: every prompt is fresh random tokens, so a prefix cache
can win nothing and a KV-aware router has nothing to score.

Parameters: ``prompt_len`` [lo, hi] (log-uniform), ``max_new`` [lo, hi]
(uniform), ``requests_per_client`` (enough to outlast the window; a client
that runs out stops sending). ``traffic["clients"]`` clients.

Clients of a running service are not in step with each other, so each
client's first request is cut to a different share of its output length
(client k of n: (k + 1/2) / n): completions are spread over the first
requests' lifetime and the loop is in its steady state from the start,
instead of all clients prefilling, decoding and finishing in waves.
"""

from __future__ import annotations

import numpy as np

from kvbench.generators.common import (Arrival, Schedule, quantile_set, rngs, shuffled,
                    tokens)


def schedule(seed: int, traffic: dict, vocab: int,
             seconds: float) -> Schedule:
    p = traffic["params"]
    rng, trng = rngs(seed, traffic, 3)
    clients = int(traffic["clients"])
    n = clients * int(p["requests_per_client"])
    lens = shuffled(rng, quantile_set(n, *p["prompt_len"], "loguniform"))
    # First requests: the same set of lengths, the k-th cut to its share.
    cut = quantile_set(clients, *p["max_new"], "uniform") * (
        (np.arange(clients) + 0.5) / clients)
    new = [*shuffled(rng, np.maximum(1, cut.astype(np.int64))),
           *shuffled(rng, quantile_set(n - clients, *p["max_new"],
                                       "uniform"))]
    # Arrival i is a request of client i % clients, in its order.
    return Schedule(arrivals=[
        Arrival(prompt=tokens(trng, lens[i], vocab), max_new=int(new[i]),
                client=i % clients, kind="short")
        for i in range(n)])
