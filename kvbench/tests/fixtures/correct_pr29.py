"""``harness/correct.py`` as it stood at PR 29 (commit 5ef9041), kept word for
word but for its two imports: the probe that compares ONE answer of the
reference. ``test_routed_probe.py`` holds today's probe to it: equal to the
last bit where a reference admits one answer, and failing a built near-tie
that today's passes.

The probe that decides ``correct``, run in set-up outside the window.

(a) One seeded prompt: the engine's last-position logits after prefill must
agree with the plain reference; then the engine decodes further tokens
through the paged cache, the reference is run once over prompt + those
tokens, and at every decoded position the token the engine chose must be
the reference's best or within the tolerance of it, in logit space (the
engine keeps no decode logits to compare, and with random weights the
largest logit changes on rounding, so tokens are not compared for
equality). (b) The same prompt again on the first replica, which now serves
it as a prefix hit, and on every other replica, where it is cold or, with a
shared storage tier, restored from what the first wrote through: the same
bound.
"""

from __future__ import annotations

import numpy as np

from kvbench.harness.fleet import Fleet, log
from kvbench.harness.loop import now


def _run(eng, rid: str, prompt, max_new: int):
    req = eng.enqueue(rid, prompt, max_new_tokens=max_new)
    logits = None
    deadline = now() + 600.0
    while not req.done:
        eng.step()
        if logits is None and req.last_logits is not None:
            logits = np.asarray(req.last_logits, np.float32)
        if now() > deadline:
            raise TimeoutError(f"probe {rid} is stuck")
    return req, logits


def probe(fleet: Fleet, params, reference, seed: int, prompt_len: int,
          decode_tokens: int) -> dict:
    """Returns ``{"ok": bool, ...measurements}``; every failed comparison
    is named in ``faults``."""
    cfg = fleet.cfg
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 99])
    prompt = rng.integers(1, cfg.vocab_size, prompt_len).tolist()
    pods = list(fleet.engines)
    first = fleet.engines[pods[0]]
    tol = reference.TOLERANCE
    faults = []

    req, logits = _run(first, "probe-cold", prompt, decode_tokens + 1)
    if req.cached_len:
        faults.append(f"the cold probe was admitted with cached_len "
                      f"{req.cached_len}")
    out = list(req.output)
    positions = [prompt_len - 1 + i for i in range(decode_tokens + 1)]
    ref = reference.logits_at(params, cfg, prompt + out[:decode_tokens],
                              positions)
    scale = float(np.abs(ref[0]).max())

    def rel(got) -> float:
        return float(np.abs(got - ref[0]).max() / scale)

    report = {"tolerance": tol, "prefill_rel_err": rel(logits)}
    if not np.isfinite(logits).all() or report["prefill_rel_err"] > tol:
        faults.append(f"prefill logits differ from the reference by "
                      f"{report['prefill_rel_err']:.3e} of its largest")
    # Token i of the output was chosen from the logits at positions[i].
    short = [float((ref[i].max() - ref[i][out[i]]) / np.abs(ref[i]).max())
             for i in range(decode_tokens + 1)]
    report["decode_worst_shortfall"] = max(short[1:], default=0.0)
    report["decode_tokens_equal"] = sum(
        int(np.argmax(ref[i])) == out[i] for i in range(1, len(out)))
    if max(short) > tol:
        faults.append(f"a token decoded through the cache is {max(short):.3e}"
                      f" of the reference's largest logit below its best")

    req, logits = _run(first, "probe-hit", prompt, 1)
    report["hit_cached_len"] = req.cached_len
    report["hit_rel_err"] = rel(logits)
    if req.cached_len < prompt_len - cfg.page_size:
        faults.append(f"the repeated probe was admitted with cached_len "
                      f"{req.cached_len}, not as a prefix hit")
    if report["hit_rel_err"] > tol:
        faults.append(f"prefix-hit logits differ by "
                      f"{report['hit_rel_err']:.3e}")
    worst = 0.0
    if first.offload_handlers is not None:
        first.flush_offload(timeout_s=60.0)
    report["other_replicas_cached_len"] = []
    for pod in pods[1:]:
        req, logits = _run(fleet.engines[pod], f"probe-{pod}", prompt, 1)
        worst = max(worst, rel(logits))
        report["other_replicas_cached_len"].append(req.cached_len)
    report["other_replicas_rel_err"] = worst
    if worst > tol:
        faults.append(f"another replica's cold logits differ by {worst:.3e}")
    report["faults"] = faults
    report["ok"] = not faults
    log(f"probe vs float32 reference: {report}")
    return report
