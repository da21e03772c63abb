"""Prefix-sharing chat sessions.

A few system prompts, many sessions under them, a few of the sessions heavy.
Turn k+1 of a session is turn k's prompt + a pre-generated "assistant" span
+ a new user span: the schedule is fixed before the window and the program
receives only generated inputs (never its own output). A session that would
pass ``max_context`` ends, and a new one takes its place under the same
system prompt.

A deployment has been serving for hours when a window starts, so every
session slot begins with a history: the system prompt and ``history_turns``
[lo, hi] earlier turns (uniform over the slots), served once during set-up
(one token each) so that the pools hold what a long-running replica would
and eviction is live from the window's first second.

Parameters (``traffic["params"]``): ``system_prompts``, ``system_len``
[lo, hi] (uniform), ``sessions``, ``zipf_system`` (sessions over system
prompts), ``zipf_session`` (arrivals over sessions), ``assistant_len`` and
``user_len`` [lo, hi] (log-uniform), ``max_new`` [lo, hi] (uniform),
``max_context``, ``history_turns`` [lo, hi].
"""

from __future__ import annotations

from kvbench.generators.common import (Arrival, Schedule, apportion, arrival_offsets,
                    quantile_set, rngs, shuffled, tokens, zipf_weights)


def schedule(seed: int, traffic: dict, vocab: int,
             seconds: float) -> Schedule:
    p = traffic["params"]
    rng, trng = rngs(seed, traffic, 1)
    due = arrival_offsets(rng, traffic, seconds)
    n = len(due)

    n_sys, n_sess = int(p["system_prompts"]), int(p["sessions"])
    sys_lens = shuffled(rng, quantile_set(n_sys, *p["system_len"],
                                          "uniform"))
    systems = [tokens(trng, ln, vocab) for ln in sys_lens]
    # Which system prompt each session slot sits under, and which slot each
    # arrival belongs to: fixed counts, seeded order.
    per_sys = apportion(n_sess, zipf_weights(n_sys, p["zipf_system"]))
    slot_system = shuffled(rng, [s for s, c in enumerate(per_sys)
                                 for _ in range(c)])
    per_slot = apportion(n, zipf_weights(n_sess, p["zipf_session"]))
    picks = shuffled(rng, [s for s, c in enumerate(per_slot)
                           for _ in range(c)])

    user = shuffled(rng, quantile_set(n, *p["user_len"], "loguniform"))
    asst = shuffled(rng, quantile_set(n, *p["assistant_len"], "loguniform"))
    new = shuffled(rng, quantile_set(n, *p["max_new"], "uniform"))
    max_context = int(p["max_context"])

    history: dict[int, list] = {}
    seen_system: set[int] = set()
    setup = []
    i_served: dict[int, bool] = {}
    turns = shuffled(rng, quantile_set(n_sess, *p.get("history_turns",
                                                      [0, 0]), "uniform"))
    h_user = shuffled(rng, quantile_set(int(turns.sum()), *p["user_len"],
                                        "loguniform"))
    h_asst = shuffled(rng, quantile_set(int(turns.sum()),
                                        *p["assistant_len"], "loguniform"))
    k = 0
    for slot in range(n_sess):
        if not turns[slot]:
            continue
        prompt = list(systems[int(slot_system[slot])])
        for _ in range(int(turns[slot])):
            more = (tokens(trng, h_user[k], vocab)
                    + tokens(trng, h_asst[k], vocab))
            k += 1
            if len(prompt) + len(more) > 0.75 * max_context:
                break
            prompt += more
        history[slot] = prompt
        seen_system.add(int(slot_system[slot]))
        setup.append(Arrival(prompt=prompt, max_new=1, kind="history"))
    out = []
    for i in range(n):
        slot = int(picks[i])
        sys_id = int(slot_system[slot])
        prev = history.get(slot)
        prompt = None
        if prev is not None:
            # A history ends with the assistant's last answer; a served
            # turn is followed by one.
            answered = prev is not None and i_served.get(slot, False)
            prompt = (prev + (tokens(trng, asst[i], vocab) if answered
                              else []) + tokens(trng, user[i], vocab))
            if len(prompt) + int(new[i]) > max_context:
                prompt = None  # the session ends; a new one takes the slot
        kind = "turn"
        if prompt is None:
            prompt = systems[sys_id] + tokens(trng, user[i], vocab)
            kind = "first"
        history[slot] = prompt
        i_served[slot] = True
        out.append(Arrival(prompt=prompt, max_new=int(new[i]),
                           due=float(due[i]),
                           extends=sys_id in seen_system, kind=kind))
        seen_system.add(sys_id)
    return Schedule(arrivals=out, setup=setup)
