#!/usr/bin/env python
"""Score/ingest hot-path microbenchmark (`make bench-hotpath`).

Three workloads, each run with the optimizations disabled (baseline: no
prefix cache, full lookups, one-message-at-a-time ingestion) and enabled
(defaults), emitting one JSON line of p50/p99 latencies and speedups:

- repeat_prefix: a multi-turn session re-sending a long, mostly-unchanged
  prompt. Each turn appends one block-sized delta and the prompt is scored
  ``--scores-per-turn`` times — llm-d disaggregated scheduling scores the
  prefill and decode pools separately, and retries/rebalances re-score the
  same request, so the scheduler sees each prompt more than once. This is
  the case the prefix cache + early-exit chunked lookup target
  (O(prompt-rehash) → O(fingerprint + delta))
- cold_prefix: every call a fresh prompt (worst case for the cache; the
  guardrail that the optimizations don't regress cold traffic)
- event_ingest: BlockStored/BlockRemoved digest throughput through the
  drain path, batch + coalescing vs per-message, in the per-pod shard
  order the pool's workers actually see (events shard by pod, so one
  worker drains runs of same-pod messages)

``--fleet`` switches to the fleet-scale data-plane arm (ISSUE 17): a
4-shard in-process fleet (real IndexerService handler methods behind
loopback clients that msgpack round-trip every frame and sleep a
configurable simulated RTT per RPC) scored through ShardRouter with the
batched LookupBlocksBatch fan-out vs the per-chunk wire
(``fanoutBatchChunks=0``), while packed zero-copy event frames ingest
concurrently through each shard's pool. Emits sustained GetPodScores/s
for both wires, ingest lag percentiles, and the sampled hot-function
shares; the JSON ``value`` is the batched/per-chunk throughput ratio
(the ≥5x acceptance gate of ISSUE 17, hard-asserted here too).

``--evict`` times the engine's other host hook on the way to a first
token: the pages an admission takes from a full pool (45 pages from 2,559
cached, unreferenced blocks: what a `sessions` admission evicts), through
``BlockManager.allocate_pages`` (order kept in a heap, one event batch)
and through the walk it replaced (every victim found by a scan of the
pool, one event a victim), into a sink that does nothing. The JSON
``value`` is the scan's p50 over the kept order's. A host time on this
sandbox's CPU: no device runs and no device metric is named.

``--phases`` times what the router's and the pool's phases
(``telemetry/tracing.py``: ``route.*``, ``ingest``) cost a call: one site
bare, and a whole ``KVAwareRouter.route`` of a `sessions`-sized prompt, off
(no engine of the process has phases: the shared no-op) and on (a
``TraceAnnotation`` a site, no capture running). The JSON ``value`` is what
a route gains on, in microseconds. Host times on this sandbox's CPU.

Pure CPU scheduling-path work; run it pinned (`taskset`) for stable
numbers. The ≥5x acceptance gate of ISSUE 2 applies to repeat_prefix.
"""

import argparse
import json
import random
import statistics
import time

from llmd_kv_cache_tpu.core import PodEntry
from llmd_kv_cache_tpu.core.token_processor import (
    ChunkedTokenDatabase,
    TokenProcessorConfig,
)
from llmd_kv_cache_tpu.events import (
    BlockRemovedEvent,
    BlockStoredEvent,
    EventBatch,
    Pool,
    PoolConfig,
)
from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
from llmd_kv_cache_tpu.scoring.indexer import Indexer, IndexerConfig

MODEL = "meta/bench-model"
PODS = [f"pod-{i}" for i in range(4)]
BLOCK = 16


def make_indexer(optimized: bool) -> Indexer:
    return Indexer(IndexerConfig(
        token_processor_config=TokenProcessorConfig(
            block_size_tokens=BLOCK,
            prefix_cache_tokens=0 if not optimized else 4 * 2**20,
        ),
        lookup_chunk_size=128 if optimized else 0,
    ))


def pcts(samples):
    qs = statistics.quantiles(samples, n=100)
    return {
        "p50_us": round(statistics.median(samples) * 1e6, 1),
        "p99_us": round(qs[98] * 1e6, 1),
        "mean_us": round(statistics.fmean(samples) * 1e6, 1),
    }


def bench_score(optimized: bool, *, prompt_tokens: int, resident_blocks: int,
                turns: int, scores_per_turn: int, repeat_prefix: bool,
                rng: random.Random):
    """Time score_tokens over a session; returns latency stats."""
    indexer = make_indexer(optimized)
    base = [rng.randrange(32_000) for _ in range(prompt_tokens)]
    keys = indexer.compute_block_keys(base, MODEL)
    entries = [PodEntry(p, "tpu-hbm") for p in PODS]
    if resident_blocks:
        indexer.kv_block_index.add(None, keys[:resident_blocks], entries)

    samples = []
    tokens = list(base)
    for turn in range(turns):
        if repeat_prefix:
            tokens = tokens + [rng.randrange(32_000) for _ in range(BLOCK)]
        else:  # cold: a brand-new prompt every call
            tokens = [rng.randrange(32_000) for _ in range(prompt_tokens)]
        for _ in range(scores_per_turn if repeat_prefix else 1):
            t0 = time.perf_counter()
            scores = indexer.score_tokens(tokens, MODEL)
            samples.append(time.perf_counter() - t0)
            if repeat_prefix:
                assert len(scores) == len(PODS) or resident_blocks == 0
    stats = pcts(samples)
    pc = indexer.prefix_cache_stats()
    if pc is not None:
        stats["prefix_cache_hit_rate"] = round(pc["block_hit_rate"], 4)
    return stats


def bench_ingest(batch_max: int, *, n_msgs: int, keys_per_msg: int,
                 rng: random.Random):
    """Messages/s through the sharded pool at the given drain budget."""
    proc = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=BLOCK))
    index = InMemoryIndex(InMemoryIndexConfig(size=10**6))
    pool = Pool(PoolConfig(concurrency=4, ingest_batch_max=batch_max),
                index, proc)
    batches = []
    for i in range(n_msgs):
        # Per-pod runs: the pool shards queues by pod, so each worker
        # drains consecutive messages from the same engine.
        pod = PODS[(i * len(PODS)) // n_msgs]
        if i % 5 == 4:
            ev = BlockRemovedEvent(
                block_hashes=[i * keys_per_msg + j for j in range(keys_per_msg)])
        else:
            tokens = [rng.randrange(32_000) for _ in range(keys_per_msg * BLOCK)]
            ev = BlockStoredEvent(
                block_hashes=[i * keys_per_msg + j for j in range(keys_per_msg)],
                tokens=tokens, parent_hash=0, block_size=BLOCK)
        batches.append((pod, EventBatch(timestamp=1.0, events=[ev])))

    t0 = time.perf_counter()
    # Drive the drain path directly (single-threaded timing keeps numbers
    # comparable across machines; the thread pool adds only queue overhead).
    from llmd_kv_cache_tpu.events.pool import _IngestCoalescer

    i = 0
    while i < len(batches):
        chunk = batches[i:i + max(1, batch_max)]
        sink = _IngestCoalescer(index) if len(chunk) > 1 else None
        for pod, b in chunk:
            pool.process_event_batch(b, pod, MODEL, sink=sink)
        if sink is not None:
            sink.flush()
        i += len(chunk)
    dt = time.perf_counter() - t0
    return {"messages_per_s": round(n_msgs / dt, 1), "wall_s": round(dt, 4)}


class LoopbackShardClient:
    """ShardClient stand-in that calls the real service handler methods
    through a full msgpack round trip (both directions, exactly the
    bytes the gRPC wire would carry) plus a simulated per-RPC network
    RTT. No sockets: the bench isolates the *fan-out protocol* cost —
    frames serialized, RPCs issued, windows walked — from kernel/socket
    noise, which is the part this PR's batched wire changes."""

    def __init__(self, service, rtt_s: float = 0.0):
        self._svc = service
        self._rtt = rtt_s

    def _call(self, handler, frame: dict) -> dict:
        import msgpack

        if self._rtt:
            time.sleep(self._rtt)
        req = msgpack.unpackb(
            msgpack.packb(frame, use_bin_type=True),
            raw=False, strict_map_key=False,
        )
        resp = handler(req, None)
        return msgpack.unpackb(
            msgpack.packb(resp, use_bin_type=True),
            raw=False, strict_map_key=False,
        )

    def lookup_blocks(self, keys, pods=None, timeout=None, deadline=None,
                      hedge=False):
        from llmd_kv_cache_tpu.cluster.remote import entry_from_row

        frame = {"keys": [int(k) for k in keys], "pods": list(pods or [])}
        resp = self._call(self._svc.lookup_blocks_rpc, frame)
        hits = {
            int(k): [entry_from_row(r) for r in rows]
            for k, rows in resp.get("hits", [])
        }
        return {"hits": hits, "degraded": bool(resp.get("degraded", False)),
                "shard": resp.get("shard", "") or ""}

    def lookup_blocks_batch(self, chunks, pods=None, timeout=None,
                            deadline=None, hedge=False):
        from llmd_kv_cache_tpu.cluster.remote import entry_from_row

        frame = {
            "chunks": [[int(k) for k in c] for c in chunks],
            "pods": list(pods or []),
        }
        resp = self._call(self._svc.lookup_blocks_batch_rpc, frame)
        hits = {}
        for chunk_hits in resp.get("chunks", []):
            for k, rows in chunk_hits:
                hits[int(k)] = [entry_from_row(r) for r in rows]
        return {
            "hits": hits,
            "cont": [bool(f) for f in resp.get("cont", []) or []],
            "degraded": bool(resp.get("degraded", False)),
            "shard": resp.get("shard", "") or "",
        }

    def close(self):
        pass


def bench_fleet(args) -> dict:
    """Fleet-scale score/ingest data-plane arm (``--fleet``)."""
    import threading

    from llmd_kv_cache_tpu.cluster.config import ClusterConfig
    from llmd_kv_cache_tpu.cluster.router import ShardRouter
    from llmd_kv_cache_tpu.events.model import RawMessage
    from llmd_kv_cache_tpu.events.packed import encode_packed_batch
    from llmd_kv_cache_tpu.services.indexer_service import IndexerService
    from llmd_kv_cache_tpu.telemetry import (
        InMemorySpanExporter,
        SamplingProfiler,
        SamplingProfilerConfig,
        install_span_exporter,
        merge_folded,
        set_process_identity,
        span_function_shares,
        uninstall_span_exporter,
    )

    rng = random.Random(7)
    shards = [f"shard-{i}" for i in range(4)]
    rtt_s = args.fleet_rtt_us / 1e6
    services = {
        sid: IndexerService(IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK, prefix_cache_tokens=4 * 2**20,
            ),
            lookup_chunk_size=128,
        ), pool_config=PoolConfig(concurrency=2))
        for sid in shards
    }
    clients = {sid: LoopbackShardClient(svc, rtt_s=rtt_s)
               for sid, svc in services.items()}

    def make_router(batch_chunks: int) -> ShardRouter:
        return ShardRouter(
            ClusterConfig(
                shard_addresses=shards,
                fanout_chunk_blocks=args.fleet_chunk,
                fanout_batch_chunks=batch_chunks,
                # Uniform simulated RTT would arm the latency-quantile
                # hedge trigger on every RPC; this arm measures wire
                # shape, not tail tolerance (bench-graytail owns that).
                hedge_enabled=False,
            ),
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK, prefix_cache_tokens=4 * 2**20,
            ),
            clients=clients,
        )

    router_b = make_router(args.fleet_batch_chunks)
    router_p = make_router(0)  # the pre-batch per-chunk Python fan-out

    # Seed every shard with the keys it owns so the full prompt scans
    # without early exit (worst case for fan-out volume).
    base = [rng.randrange(32_000) for _ in range(args.fleet_prompt_tokens)]
    keys = router_b.token_processor.tokens_to_kv_block_keys(0, base, MODEL)
    plan = router_b.plan(keys)
    by_owner: dict = {}
    for k, owner in zip(keys, plan):
        by_owner.setdefault(owner, []).append(k)
    # Each block resident on ONE pod (a warm fleet holds a prefix on the
    # pod that served it, not on every pod) — keeps the per-key row work
    # realistic instead of 4x-inflated.
    for owner, okeys in by_owner.items():
        for k in okeys:
            services[owner].indexer.kv_block_index.add(
                None, [k], [PodEntry(PODS[int(k) % len(PODS)], "tpu-hbm")])

    # Byte-equivalence gate: the batched wire must produce the identical
    # RouterScore the per-chunk wire does, down to float bits.
    res_b = router_b.score(base, MODEL)
    res_p = router_p.score(base, MODEL)
    assert res_b.scores == res_p.scores, (res_b.scores, res_p.scores)
    assert res_b.hit_blocks == res_p.hit_blocks == len(keys)
    assert router_b.batch_rpcs > 0 and router_b.batch_fallbacks == 0

    # Concurrent zero-copy ingest: packed KZC1 frames through each
    # shard's live pool while the routers score.
    for svc in services.values():
        svc.pool.start()
    stop = threading.Event()
    sent = {"n": 0}

    def ingest_loop() -> None:
        seq = 0
        while not stop.is_set():
            for i, sid in enumerate(shards):
                seq += 1
                tokens = [rng.randrange(32_000)
                          for _ in range(4 * BLOCK)]
                frame = encode_packed_batch(
                    f"ingest-pod-{i}", MODEL,
                    [seq * 8 + j for j in range(4)], tokens,
                    timestamp=time.time(), block_size=BLOCK,
                )
                services[sid].pool.add_task(RawMessage(
                    topic=f"kv@ingest-pod-{i}@{MODEL}",
                    sequence=seq, payload=frame,
                ))
                sent["n"] += 1
            stop.wait(0.002)

    ingester = threading.Thread(target=ingest_loop, name="fleet-ingest",
                                daemon=True)

    def sustained(router, seconds: float):
        t_end = time.perf_counter() + seconds
        iters = 0
        rpcs = 0
        t0 = time.perf_counter()
        while time.perf_counter() < t_end:
            rpcs += router.score(base, MODEL).rpcs
            iters += 1
        dt = time.perf_counter() - t0
        return {
            "scores_per_s": round(iters / dt, 2),
            "rpcs_per_score": round(rpcs / max(iters, 1), 1),
            "iters": iters,
        }

    set_process_identity("bench-router")
    install_span_exporter(InMemorySpanExporter(max_spans=50_000))
    profiler = SamplingProfiler(
        SamplingProfilerConfig(enabled=True, hz=67.0, window_s=3600.0))
    profiler.start()
    ingester.start()
    try:
        per_chunk = sustained(router_p, args.fleet_seconds)
        batched = sustained(router_b, args.fleet_seconds)
    finally:
        stop.set()
        ingester.join(timeout=5.0)
        profiler.stop()
        uninstall_span_exporter()
        set_process_identity(None)

    # Let the pools drain the ingest backlog, then read lag.
    deadline = time.perf_counter() + 5.0
    while time.perf_counter() < deadline and any(
            sum(s.pool.lag_stats()["queue_depths"]) for s in services.values()):
        time.sleep(0.01)
    lag_p99 = 0.0
    lag_p50 = 0.0
    zerocopy = 0
    for svc in services.values():
        st = svc.pool.lag_stats()
        lag_p99 = max(lag_p99, st.get("lag_p99_s", 0.0))
        lag_p50 = max(lag_p50, st.get("lag_p50_s", 0.0))
        zerocopy += svc.pool.data_plane_debug()["zerocopy_batches"]
        svc.pool.shutdown()

    profiler.rotate(force=True)
    windows = profiler.export_since(-1)["windows"]
    shares = span_function_shares(
        merge_folded([w["folded"] for w in windows]))
    hot = {
        span: {
            "samples": entry["samples"],
            "functions": dict(list(entry["functions"].items())[:5]),
        }
        for span, entry in shares.items()
        if span in ("llm_d.kv_cache.cluster.fanout",
                    "llm_d.kv_cache.events.ingest")
    }

    ratio = batched["scores_per_s"] / max(per_chunk["scores_per_s"], 1e-9)
    # ISSUE 17 acceptance: the batched data plane must sustain >=5x the
    # per-chunk wire, and concurrent ingest must stay inside the
    # staleness bound. Hard-asserted so `make bench-hotpath -- --fleet`
    # fails loudly, not just the perf sentinel.
    assert ratio >= args.fleet_min_speedup, (
        f"batched fan-out sustained only {ratio:.2f}x the per-chunk wire "
        f"(need >={args.fleet_min_speedup}x): {batched} vs {per_chunk}")
    assert lag_p99 <= args.fleet_lag_bound_s, (
        f"ingest lag p99 {lag_p99:.3f}s breaches the "
        f"{args.fleet_lag_bound_s}s staleness bound under score load")
    assert zerocopy > 0, "no packed frame took the zero-copy ingest path"

    return {
        "bench": "hotpath-fleet",
        "shards": len(shards),
        "prompt_tokens": args.fleet_prompt_tokens,
        "blocks": len(keys),
        "chunk_blocks": args.fleet_chunk,
        "batch_chunks": args.fleet_batch_chunks,
        "rtt_us": args.fleet_rtt_us,
        "per_chunk": per_chunk,
        "batched": batched,
        "batch_rpcs": router_b.batch_rpcs,
        "batch_fallbacks": router_b.batch_fallbacks,
        "ingest": {
            "messages": sent["n"],
            "zerocopy_batches": zerocopy,
            "lag_p50_s": round(lag_p50, 4),
            "lag_p99_s": round(lag_p99, 4),
        },
        "value": round(ratio, 2),
        "unit": "batched/per-chunk sustained GetPodScores/s ratio",
        "hot_functions": hot,
    }


def _scan_allocate(blocks: dict, free_pages: list, n: int, sink) -> list:
    """``n`` pages the way ``BlockManager`` found them before its order was
    kept (``blocks``: hash -> [page, ref_count, last_used]): a walk of the
    whole pool for each victim, one BlockRemoved a victim."""
    pages = []
    for _ in range(n):
        if not free_pages:
            victim, victim_time = None, float("inf")
            for h, info in blocks.items():
                if info[1] == 0 and info[2] < victim_time:
                    victim, victim_time = h, info[2]
            if victim is None:
                break
            free_pages.append(blocks.pop(victim)[0])
            sink([BlockRemovedEvent(block_hashes=[victim], group_idx=0)])
        pages.append(free_pages.pop())
    return pages


def bench_evict(*, pool_pages: int, pages: int, rounds: int) -> dict:
    """An admission's pages from a pool full of idle cached blocks."""
    from llmd_kv_cache_tpu.models.engine import BlockManager, EngineConfig
    from llmd_kv_cache_tpu.models.llama import LlamaConfig

    proc = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=BLOCK))

    def sink(events):       # the manager alone: nothing ingests
        pass

    bm = BlockManager(
        EngineConfig(model=LlamaConfig.tiny(), num_pages=pool_pages),
        proc, event_sink=sink)
    toks = [[0] * BLOCK]
    next_hash = iter(range(1, 10**9))

    def commit_and_release(free):
        # Sessions of 20 blocks: one last_used a session, as in serving.
        for lo in range(0, len(free), 20):
            run = free[lo:lo + 20]
            hashes = [next(next_hash) for _ in run]
            bm.commit_blocks(hashes, run, toks * len(run), 0)
            bm.release(hashes, [])

    commit_and_release(bm.allocate_pages(pool_pages - 1))
    scan_blocks = {h: [i.page, 0, i.last_used] for h, i in bm.blocks.items()}
    scan_free: list = []
    kept, scan = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        got = bm.allocate_pages(pages)
        kept.append(time.perf_counter() - t0)
        assert len(got) == pages
        commit_and_release(got)

        t0 = time.perf_counter()
        got = _scan_allocate(scan_blocks, scan_free, pages, sink)
        scan.append(time.perf_counter() - t0)
        assert len(got) == pages
        now = time.monotonic()
        for page in got:
            scan_blocks[next(next_hash)] = [page, 0, now]
    assert bm.evictions == rounds * pages
    return {
        "bench": "hotpath-evict",
        "cached_blocks": pool_pages - 1, "pages": pages, "rounds": rounds,
        "platform": "cpu-host",
        "scan": pcts(scan), "kept_order": pcts(kept),
        "value": round(statistics.median(scan) / statistics.median(kept), 1),
        "unit": "scan p50 / kept-order p50, host time of one admission's "
                "pages",
    }


def bench_phases(*, routes: int, prompt_tokens: int, sites: int) -> dict:
    """The control plane's phase sites off and on: two routers over two
    indexes that see the same prompts in turn, one under an owner of its
    own (on), one under the process's (off: no engine here has phases)."""
    from llmd_kv_cache_tpu.scoring.router import KVAwareRouter
    from llmd_kv_cache_tpu.telemetry import tracing

    rng = random.Random(7)
    assert tracing.process_phases() is None
    owner = tracing.Phases()
    routers = {"off": KVAwareRouter(make_indexer(True), PODS),
               "on": KVAwareRouter(make_indexer(True), PODS, phases=owner)}
    prompts = [[rng.randrange(1, 30000) for _ in range(prompt_tokens)]
               for _ in range(16)]

    def site_us(phases) -> float:
        t0 = time.perf_counter()
        for _ in range(sites):
            with tracing.phase(phases, tracing.PHASE_ROUTE_HASH):
                pass
        return round((time.perf_counter() - t0) / sites * 1e6, 3)

    took = {"off": [], "on": []}
    for i in range(-50, routes):                # 50 to warm the caches
        for side in (("off", "on") if i % 2 else ("on", "off")):
            t0 = time.perf_counter()
            routers[side].route(prompts[i % len(prompts)], MODEL)
            if i >= 0:
                took[side].append(time.perf_counter() - t0)
    out = {side: {"site_us": site_us(owner if side == "on" else None),
                  "route": pcts(took[side])} for side in took}
    return {
        "bench": "hotpath-phases", "platform": "cpu-host",
        "routes": routes, "prompt_tokens": prompt_tokens, **out,
        "value": round(out["on"]["route"]["p50_us"]
                       - out["off"]["route"]["p50_us"], 2),
        "unit": "us a route() gains with its six phases on, no capture",
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    # 100k tokens is the ISSUE's motivating scenario: a multi-turn session
    # re-sending a ~100k-token prefix on every scheduling decision.
    ap.add_argument("--prompt-tokens", type=int, default=100 * 1024)
    ap.add_argument("--resident-blocks", type=int, default=32)
    ap.add_argument("--turns", type=int, default=30)
    ap.add_argument("--scores-per-turn", type=int, default=4,
                    help="score_tokens calls per appended delta (P/D "
                         "disaggregated pool picks + retries/rebalances)")
    ap.add_argument("--ingest-msgs", type=int, default=3000)
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet-scale data-plane arm instead "
                         "(4 shards, batched vs per-chunk fan-out, "
                         "concurrent zero-copy ingest)")
    ap.add_argument("--evict", action="store_true",
                    help="run the eviction arm instead: one admission's "
                         "pages from a full pool, kept order vs the scan")
    ap.add_argument("--phases", action="store_true",
                    help="run the phases arm instead: the router's phase "
                         "sites and a whole route(), off and on")
    ap.add_argument("--fleet-prompt-tokens", type=int, default=32 * 1024)
    ap.add_argument("--fleet-chunk", type=int, default=16,
                    help="fanoutChunkBlocks for both wires (fine-grained "
                         "early exit: the regime batching targets)")
    ap.add_argument("--fleet-batch-chunks", type=int, default=16,
                    help="fanoutBatchChunks for the batched wire")
    ap.add_argument("--fleet-rtt-us", type=float, default=2500.0,
                    help="simulated per-RPC network RTT (cross-host "
                         "datacenter gRPC: ~0.5ms same-rack to ~3ms "
                         "cross-zone; loopback would hide the fan-out "
                         "cost the batched wire removes)")
    ap.add_argument("--fleet-seconds", type=float, default=2.0,
                    help="sustained-measurement window per wire")
    ap.add_argument("--fleet-lag-bound-s", type=float, default=1.0,
                    help="ingest lag p99 staleness bound (hard gate)")
    ap.add_argument("--fleet-min-speedup", type=float, default=5.0,
                    help="batched/per-chunk throughput ratio hard gate")
    args = ap.parse_args()
    rng = random.Random(7)

    if args.fleet:
        print(json.dumps(bench_fleet(args)))
        return
    if args.phases:
        # a `sessions` prompt: 2 k tokens, 128 keys
        print(json.dumps(bench_phases(routes=2000, prompt_tokens=2048,
                                      sites=200_000)))
        return
    if args.evict:
        # cell 1's pool (2,560 pages a replica) and its 45 victims a request
        print(json.dumps(bench_evict(pool_pages=2560, pages=45, rounds=200)))
        return

    result = {"bench": "hotpath", "prompt_tokens": args.prompt_tokens,
              "resident_blocks": args.resident_blocks,
              "scores_per_turn": args.scores_per_turn}

    for name, repeat in (("repeat_prefix", True), ("cold_prefix", False)):
        base = bench_score(False, prompt_tokens=args.prompt_tokens,
                           resident_blocks=args.resident_blocks,
                           turns=args.turns,
                           scores_per_turn=args.scores_per_turn,
                           repeat_prefix=repeat, rng=random.Random(7))
        opt = bench_score(True, prompt_tokens=args.prompt_tokens,
                          resident_blocks=args.resident_blocks,
                          turns=args.turns,
                          scores_per_turn=args.scores_per_turn,
                          repeat_prefix=repeat, rng=random.Random(7))
        result[name] = {
            "baseline": base, "optimized": opt,
            "speedup_p50": round(base["p50_us"] / max(opt["p50_us"], 1e-9), 2),
        }

    seq = bench_ingest(1, n_msgs=args.ingest_msgs, keys_per_msg=4, rng=rng)
    bat = bench_ingest(64, n_msgs=args.ingest_msgs, keys_per_msg=4, rng=rng)
    result["event_ingest"] = {
        "baseline": seq, "optimized": bat,
        "speedup": round(bat["messages_per_s"] / max(seq["messages_per_s"], 1e-9), 2),
    }

    print(json.dumps(result))


if __name__ == "__main__":
    main()
