#!/usr/bin/env python3
"""The accept path of a drafting model at the benchmark's widths, which the
cell's seeded weights hardly ever walk (a draft is right about once in
``vocab_size`` steps) and the probe behind ``correct`` therefore never sees.
Not part of a run of the benchmark; a builder's tool, for the chip (``chiprun
-- python3 hack/mtp_accept_path.py``) or, with ``--rehearse``, the CPU at toy
widths.

One replica of ``openpangu-ultra-ep32-l5`` as the cell builds it decodes a
prompt three times:

1. with the prediction module's own drafts;
2. with every draft replaced by the next token of run 1's own continuation
   (every draft right: every step emits two);
3. with every draft replaced by a token that is wrong.

All three must give the same tokens; after each, the pool's committed blocks
must hash to the prompt's tokens alone and every ``BlockStored`` name them
alone (no position a rejected draft was written at is ever committed). Then
the step's own functions are run once more over the row's last token and a
RIGHT draft, keeping the logits a step program never hands out: both
verified positions against the reference's ``logits_at`` (the nearest of
its ``alternatives_at``) and the module's logits at both against
``draft_logits_at``, each within the reference's ``TOLERANCE``.

Writes ``chiprun_out/pr53/accept_path.json`` and prints ``ACCEPT-PATH ok`` or
what failed; exits 1 on a failure.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench.harness import correct, fleet as F, names  # noqa: E402

CONFIG = "openpangu-ultra-ep32-l5"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000000053)
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.events.model import BlockStoredEvent
    from llmd_kv_cache_tpu.models import llama
    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

    conf = names.config_for_run(names.benchmark(), CONFIG, args.rehearse)
    sizes = conf["kvbench"]["engine"]
    if args.rehearse:
        args.prompt, args.new = min(args.prompt, 96), min(args.new, 12)
    ref = names.reference(conf)
    cfg, params = F.build_model(conf, args.seed)
    page = cfg.page_size
    rng = np.random.default_rng([args.seed & 0xFFFFFFFFFFFF, 53])
    prompt = rng.integers(1, cfg.vocab_size, args.prompt).tolist()
    faults, report = [], {"seed": args.seed, "prompt": args.prompt,
                          "new": args.new,
                          "device": jax.devices()[0].device_kind}

    def engine(events):
        return MiniEngine(EngineConfig(
            model=cfg, model_name="m", pod_identifier="pod-0",
            num_pages=int(sizes["num_pages"]),
            max_pages_per_seq=int(sizes["max_pages_per_seq"]),
            max_batch=int(sizes["max_batch"]),
            max_prefill_tokens=int(sizes["max_prefill_tokens"]),
            use_pallas_decode=True if args.rehearse else None),
            params=params, event_sink=events.extend)

    def committed(eng, events, what):
        hashes = eng.processor.tokens_to_kv_block_keys(0, prompt, "m")
        known = {h: tuple(prompt[i * page:(i + 1) * page])
                 for i, h in enumerate(hashes)}
        blocks = eng.block_manager.blocks
        if set(blocks) != set(known) or any(
                tuple(info.tokens) != known[h] for h, info in blocks.items()):
            faults.append(f"{what}: committed blocks are not the prompt's")
        for batch in events:
            for ev in getattr(batch, "events", [batch]):
                if isinstance(ev, BlockStoredEvent) and ev.tokens and any(
                        known.get(h) != tuple(ev.tokens[i * page:(i + 1)
                                                        * page])
                        for i, h in enumerate(ev.block_hashes)):
                    faults.append(f"{what}: a BlockStored names tokens "
                                  f"that are not the prompt's")
        stats = eng.block_manager.pool_stats()
        if stats["orphan_pages"]:
            faults.append(f"{what}: {stats['orphan_pages']} pages stayed "
                          f"allocated")

    def run(what, want=None):
        events: list = []
        eng = engine(events)
        if want is not None:
            eng._defers = False  # a replaced draft is the host's
        t0 = time.time()
        req = eng.enqueue(what, prompt, max_new_tokens=args.new)
        steps = 0
        while not req.done:
            decoding = req.prefill_pos is None and bool(req.output)
            if decoding and want is not None:
                nxt = want[len(req.output)] if len(req.output) < len(
                    want) else 0
                req.draft = nxt if what == "right" else (
                    nxt + 1) % (cfg.vocab_size - 1) + 1
            eng.step()
            steps += decoding
        eng.step()
        committed(eng, events, what)
        report[what] = {"decode_steps": steps, "seconds": time.time() - t0,
                        "backend": eng.attention_backends["decode"]}
        return list(req.output), steps, eng

    own, _, eng = run("own")
    right, right_steps, _ = run("right", own)
    wrong, wrong_steps, _ = run("wrong", own)
    if not own == right == wrong:
        faults.append("the three runs' tokens differ")
    if right_steps != args.new // 2:
        faults.append(f"every draft right took {right_steps} steps, not "
                      f"{args.new // 2}")
    if wrong_steps != args.new - 1:
        faults.append(f"every draft wrong took {wrong_steps} steps, not "
                      f"{args.new - 1}")
    report["tokens_equal"] = own == right == wrong

    # Both verified positions and the module's logits at both, from the
    # step's own functions over the pools a prefill left.
    events = []
    eng = engine(events)
    req = eng.enqueue("logits", prompt, max_new_tokens=4)
    while req.prefill_pos is not None or not req.output:
        eng.step()
    eng._drain("logits")
    n = req.computed_len
    tokens = prompt + own
    backend = eng.attention_backends["decode"]
    kind = "decode" if backend["backend"] == "pallas" else "xla"

    @jax.jit
    def both(params, pair, after, k, v, table, ctx):
        new = jnp.full((1,), 2, jnp.int32)
        logits, hidden, k, v = llama._main_forward(
            params, cfg, pair, k, v, table, ctx, new, kind,
            backend["interpret"], None)
        draft, _, _ = llama.draft_logits(
            params, cfg, hidden, after, k, v, table, ctx, new, kind,
            backend["interpret"], last_only=False)
        return logits[0], draft[0]

    logits, draft = both(
        eng.params, jnp.asarray([tokens[n:n + 2]], jnp.int32),
        jnp.asarray([tokens[n + 1:n + 3]], jnp.int32), eng.k_cache,
        eng.v_cache, jnp.asarray(eng._page_table_for(req)[None, :]),
        jnp.asarray([n], jnp.int32))
    logits, draft = np.asarray(logits), np.asarray(draft)
    alts = correct.alternatives(ref, params, cfg, tokens[:n + 3], [n, n + 1])
    want_draft = ref.draft_logits_at(params, cfg, tokens[:n + 3], [n, n + 1])
    tol = ref.TOLERANCE
    for j in range(2):
        _, err = correct.nearest(alts[j], logits[j])
        d_err = float(np.abs(draft[j] - want_draft[j]).max()
                      / np.abs(want_draft[j]).max())
        report[f"verified_position_{j}_rel_err"] = err
        report[f"module_position_{j}_rel_err"] = d_err
        if not err <= tol:
            faults.append(f"verified position {j} differs from the "
                          f"reference by {err:.3e}")
        if not d_err <= tol:
            faults.append(f"the module's logits at position {j} differ "
                          f"from the reference by {d_err:.3e}")
    report["tolerance"] = tol
    report["alternatives"] = [len(a) for a in alts]
    stats = jax.devices()[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use", 0)
    report["faults"] = faults
    out = Path("chiprun_out/pr53")
    out.mkdir(parents=True, exist_ok=True)
    (out / ("accept_path_rehearsal.json" if args.rehearse
            else "accept_path.json")).write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    print("ACCEPT-PATH ok" if not faults else f"ACCEPT-PATH faults: {faults}")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
