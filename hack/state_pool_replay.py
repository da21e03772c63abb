#!/usr/bin/env python3
"""A cell's schedule replayed on the host, no model and no chip: which
replica each request goes to, how many tokens of its pages match there,
where its prefill resumes from, and what the pools evicted, for a cell
whose model keeps a sequence state beside its pages (cells 5 and 6). A
question about eviction's order, the snapshot rules or the pools' sizes is
sized here in seconds before a chip is asked.

The set-up's histories and the window's arrivals go one after another, in
the order they are due, each to its prefill's end before the next is
routed, through the tree's own ``BlockManager`` and ``StatePool`` and the
engine's own admission, snapshot rules, commit and release (``HostReplica``
is a ``MiniEngine`` without weights, pools or programs). The router's rule
is written out as ``KVAwareRouter._pick`` applies it to the `kv` scorer:
the replica with the longest resident prefix of pages, else round robin.
Left out: concurrency (a request due behind a miss of its own replica is
admitted here after that miss has committed, on the chip before), the
rows' decoding, the probe's and the warm-up's few snapshots, and the
router's speculative entries. Against the chip's request dump
(``hack/kvbench_requests.py``) of cell 6 it gives the replica of all 14
sampled turns and the cached length of 13 to the token (PERF.md §6, PR 51).

  python3 hack/state_pool_replay.py solar-open2-ep16-l8.sessions-64k
  python3 hack/state_pool_replay.py gigachat3.5-ep16-l5.sessions-32k --rate 0.6
"""

from __future__ import annotations

import argparse
import itertools
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench.harness import fleet, names  # noqa: E402
from llmd_kv_cache_tpu.core.keys import EMPTY_BLOCK_HASH  # noqa: E402
from llmd_kv_cache_tpu.core.token_processor import (  # noqa: E402
    ChunkedTokenDatabase, TokenProcessorConfig)
from llmd_kv_cache_tpu.models.engine import (  # noqa: E402
    BlockManager, EngineConfig, MiniEngine)
from llmd_kv_cache_tpu.models.state_pool import StatePool  # noqa: E402

# ``pool_stats()``'s lifetime counts, read as deltas around a request.
COUNTERS = ("evictions", "state_evictions", "state_orphaned",
            "state_replaced")


class HostReplica(MiniEngine):
    """The host's side of one replica: pages, states and the rules that
    move them, with nothing on a device."""

    def __init__(self, cfg: EngineConfig):
        mcfg = cfg.model
        self.cfg = cfg
        self.processor = ChunkedTokenDatabase(TokenProcessorConfig(
            block_size_tokens=mcfg.page_size, hash_seed=cfg.hash_seed))
        self.state_pool = StatePool(mcfg.state_slots, mcfg.page_size)
        self.block_manager = BlockManager(cfg, self.processor)
        self.block_manager.state_pool = self.state_pool
        self.requests, self._running, self.state = {}, [], ()
        self.hybrid = False
        self._phases = self.telemetry = self.workingset = None
        self.offload_manager = self.offload_handlers = None
        self._unread = self.handoff = None

    def _to_dev(self, x, dtype=None):
        return np.asarray(x, dtype)

    def hashes(self, prompt) -> list:
        return self.processor.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, prompt, self.cfg.model_name)

    def serve(self, rid: str, prompt, max_new: int) -> SimpleNamespace:
        """Admit, prefill chunk by chunk, commit and release: what
        ``enqueue`` and ``step`` do to the pools for one request."""
        page = self.cfg.model.page_size
        cap = max(page, self.cfg.max_prefill_tokens // page * page)
        before = self.block_manager.pool_stats()
        req = self._admit(rid, prompt, max_new)
        pos, chunks = req.prefill_pos, 0
        while pos < len(prompt):
            n = min(cap, len(prompt) - pos)
            _, taken = self._plan_snapshots(req, pos, n)
            for boundary, slot in taken:  # as ``_prefill_chunk`` stores them
                blocks = boundary // page
                self.state_pool.store(
                    req.block_hashes[blocks - 1], slot,
                    req.block_hashes[:blocks],
                    req.block_hashes[blocks - 2] if blocks > 1
                    else EMPTY_BLOCK_HASH,
                    prompt[boundary - page:boundary])
                req.snapshots.append(req.block_hashes[blocks - 1])
            pos, chunks = pos + n, chunks + 1
        self._commit_full_blocks(req)   # as ``_finish_prefill`` does
        self.state_pool.announce(req.snapshots)
        req.snapshots = []
        self._emit_state_events()
        self._finish(req)
        after = self.block_manager.pool_stats()
        return SimpleNamespace(
            prompt=len(prompt), matched=req.page_hit_blocks * page,
            cached=req.cached_len, chunks=chunks,
            **{k: after[k] - before[k] for k in COUNTERS})


def route(replicas: dict, prompt, turn) -> str:
    """The replica with the longest resident prefix of at least a block,
    the first on a tie; else the next in ``turn`` (a count)."""
    hashes = next(iter(replicas.values())).hashes(prompt)
    depth = {pod: len(r.block_manager.match_prefix(hashes))
             for pod, r in replicas.items()}
    pod = max(depth, key=depth.get)
    if depth[pod] < 1:
        pod = list(replicas)[next(turn) % len(replicas)]
    return pod


def replay(cell: str, seconds: float = 50.0, seed: int = 0,
           rate: float | None = None, rehearse: bool = False,
           out=sys.stdout) -> dict:
    """Replay ``cell``: a line a request (``*`` marks a sampled turn), then
    the totals, which are returned too: the sampled turns' ``share`` of
    cached tokens, their ``misses`` (turns that prefill more than a turn can
    add), the window's ``chunks``, every replica's counters in
    ``setup`` and ``window``, and the ``replicas`` themselves."""
    bench = names.benchmark()
    entry = names.workload(bench, cell)
    conf = names.config_for_run(bench, entry["config"], rehearse)
    traffic = names.with_rehearsal(names.traffic(entry["traffic"]), rehearse)
    if rate is not None:
        traffic = {**traffic, "rate": rate}
    mcfg = fleet.model_config(conf)
    if not mcfg.linear_layers:
        raise SystemExit(f"{cell}: its model keeps no sequence state")
    kv = conf["kvbench"]
    ecfg = {k: int(v) for k, v in kv["engine"].items() if k != "page_size"}
    replicas = {f"pod-{i}": HostReplica(EngineConfig(
        model=mcfg, model_name=kv["model_name"], pod_identifier=f"pod-{i}",
        **ecfg)) for i in range(int(kv["replicas"]))}
    schedule = names.generator(traffic["generator"]).schedule(
        seed, traffic, mcfg.vocab_size, seconds)
    lo = seconds * float(traffic["warm_fraction"])
    hi = seconds * (1.0 - float(traffic.get("tail_fraction", 0.0)))

    turn = itertools.count()
    totals = {part: {pod: dict.fromkeys(COUNTERS, 0) for pod in replicas}
              for part in ("setup", "window")}
    sampled, chunks = [], 0
    print("   due s  pod    prompt  matched   cached chunks  pages evicted",
          file=out)
    arrivals = sorted(schedule.arrivals, key=lambda a: a.due)
    for i, a in enumerate([*schedule.setup, *arrivals]):
        pod = route(replicas, a.prompt, turn)
        got = replicas[pod].serve(f"r{i}", a.prompt, a.max_new)
        counts = totals["setup" if a.due is None else "window"][pod]
        for k in COUNTERS:
            counts[k] += getattr(got, k)
        mark = " "
        if a.due is not None:
            chunks += got.chunks
            if lo <= a.due < hi:
                sampled.append(got)
                mark = "*"
        print(f"{'set-up' if a.due is None else f'{a.due:7.2f}'}{mark} {pod}"
              f" {got.prompt:8d} {got.matched:8d} {got.cached:8d}"
              f" {got.chunks:6d} {got.evictions:14d}", file=out)
    # A turn adds a reply and a message to what its session sent before:
    # one that prefills more than the longest of both found less than that.
    p = traffic["params"]
    turn_most = p["assistant_len"][1] + p["user_len"][1] + mcfg.page_size
    misses = sum(g.prompt - g.cached > turn_most for g in sampled)
    prefilled = sum(g.prompt - g.cached for g in sampled)
    share = (100.0 * sum(g.cached for g in sampled)
             / max(1, sum(g.prompt for g in sampled)))
    print(f"sampled (*): {len(sampled)} of {len(arrivals)}, {misses} of them "
          f"misses; cached_token_share {share:.1f}%; they prefill "
          f"{prefilled} tokens; chunks in the window {chunks}", file=out)
    for part, pods in totals.items():
        for pod, c in pods.items():
            print(f"{part} {pod}: pages evicted {c['evictions']}; snapshots "
                  f"evicted for room "
                  f"{c['state_evictions'] - c['state_orphaned']}, orphaned "
                  f"by a page eviction {c['state_orphaned']}, replaced by "
                  f"their own prefill's next {c['state_replaced']}", file=out)
    for pod, r in replicas.items():
        stats = r.state_pool.stats()
        print(f"{pod} holds {stats['state_snapshots']} snapshots in "
              f"{stats['state_slots']} slots", file=out)
    return {"share": share, "misses": misses, "sampled": len(sampled),
            "prefilled": prefilled, "chunks": chunks, "replicas": replicas,
            **totals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", help="a workload of BENCHMARK.json whose model "
                                 "has linear layers")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="the tokens' values; the structure is the file's")
    ap.add_argument("--rate", type=float,
                    help="another rate than the traffic file's: another "
                         "structure of arrivals")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's toy sizes, as tier-1 walks it")
    args = ap.parse_args(argv)
    replay(args.cell, args.seconds, args.seed, args.rate, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
