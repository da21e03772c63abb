"""Long documents, each asked several times.

``setup_docs`` documents are prefilled (and, where the deployment has a
storage tier, written through) during set-up. In the window one arrival in
``asks_per_doc`` is the first ask of a new document and the others re-ask a
known one. Re-asks walk the live documents in a seeded round, so between two
asks of one document lie all the others: with ``setup_docs`` of them live,
the reuse distance exceeds what the HBM pools hold, and a re-ask finds its
document evicted from HBM and present in the store. A document asked
``asks_per_doc`` times retires. Each arrival is the document + a new
question.

Parameters: ``doc_len`` [lo, hi] (uniform), ``setup_docs``,
``asks_per_doc``, ``question_len`` [lo, hi] (uniform), ``max_new`` [lo, hi]
(uniform).
"""

from __future__ import annotations

from collections import deque

from kvbench.generators.common import (Arrival, Schedule, arrival_offsets, quantile_set,
                    rngs, shuffled, tokens)


def schedule(seed: int, traffic: dict, vocab: int,
             seconds: float) -> Schedule:
    p = traffic["params"]
    rng, trng = rngs(seed, traffic, 2)
    due = arrival_offsets(rng, traffic, seconds)
    n = len(due)
    asks = int(p["asks_per_doc"])
    n_setup = int(p["setup_docs"])
    # Arrival i is a first ask when i % asks == phase; the rest re-ask.
    phase = int(rng.integers(asks))
    n_first = len(range(phase, n, asks))

    setup_lens = shuffled(rng, quantile_set(n_setup, *p["doc_len"],
                                            "uniform"))
    first_lens = shuffled(rng, quantile_set(n_first, *p["doc_len"],
                                            "uniform"))
    # Window arrivals first, set-up asks after them: each its own set.
    q_lens = [*shuffled(rng, quantile_set(n, *p["question_len"], "uniform")),
              *shuffled(rng, quantile_set(n_setup, *p["question_len"],
                                          "uniform"))]
    new = [*shuffled(rng, quantile_set(n, *p["max_new"], "uniform")),
           *shuffled(rng, quantile_set(n_setup, *p["max_new"], "uniform"))]

    def ask(doc, k, **kw):
        return Arrival(prompt=doc + tokens(trng, q_lens[k], vocab),
                       max_new=int(new[k]), **kw)

    live: deque = deque()  # [document, asks so far], in round order
    setup = []
    for j in range(n_setup):
        doc = tokens(trng, setup_lens[j], vocab)
        setup.append(ask(doc, n + j, kind="setup"))
        live.append([doc, 1])
    out = []
    firsts = iter(first_lens)

    def first_len() -> int:
        # Only a mix whose documents retire at once runs past the set.
        return int(next(firsts, None) or rng.integers(p["doc_len"][0],
                                                      p["doc_len"][1] + 1))

    for i in range(n):
        if i % asks == phase or not live:
            doc = tokens(trng, first_len(), vocab)
            out.append(ask(doc, i, due=float(due[i]), kind="first"))
            if asks > 1:
                live.append([doc, 1])
            continue
        entry = live.popleft()
        out.append(ask(entry[0], i, due=float(due[i]), extends=True,
                       kind="reask"))
        entry[1] += 1
        if entry[1] < asks:
            live.append(entry)
    return Schedule(arrivals=out, setup=setup)
