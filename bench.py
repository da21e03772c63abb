#!/usr/bin/env python
"""Headline benchmark: KV-cache-aware routing vs round-robin TTFT.

Mirrors the reference's benchmark design (``benchmarking/*/README.md``:
"precise" scheduling = Indexer-routed vs random/load baselines) scaled to
one host: N in-process engine pods share a workload with heavy shared-prefix
reuse; requests are routed either round-robin or by
``Indexer.score_tokens``, and TTFT (admission+prefill wall time) is
compared. Prefix-cache hits skip prefill compute, so routing quality shows
up directly as p50 TTFT.

Prints ONE JSON line:
  {"metric": "p50 TTFT reduction, KV-aware routing vs round-robin",
   "value": <percent>, "unit": "%", "vs_baseline": <value/40>}

vs_baseline is measured against the north-star target of a >=40% p50 TTFT
reduction (BASELINE.md). The routing benchmark (the default mode and
``--ttft``) runs in this process on the TPU JAX finds and refuses to run
without one: a TTFT from another backend is not a device number. The other
modes are host-side overhead checks and say so in their metric strings.
"""

from __future__ import annotations

import json
import os
import statistics
import time

import numpy as np

# Capacity-constrained per-pod default (the regime where routing matters;
# see make_pods) — one constant so variant arms (fp8 2x-page pools)
# derive from the same baseline budget.
DEFAULT_POD_KW = {"num_pages": 72, "max_pages_per_seq": 64}


def build_workload(rng, n_requests=64, n_prefixes=8, prefix_len=256, suffix_len=32,
                   vocab=8000):
    """Shared-prefix replay: most requests reuse one of a few system prompts."""
    prefixes = [
        rng.integers(1, vocab, prefix_len).tolist() for _ in range(n_prefixes)
    ]
    workload = []
    for i in range(n_requests):
        prefix = prefixes[rng.integers(0, n_prefixes)]
        suffix = rng.integers(1, vocab, suffix_len).tolist()
        workload.append(prefix + suffix)
    return workload


def make_pods(n_pods, model_cfg, engine_mod, indexer, params=None,
              pod_kw=None, offload_spec_factory=None):
    """Fresh engine pods wired to feed the indexer's index via events.

    All pods share one parameter tree (same seed anyway — the engines
    never donate params), so the chip holds one copy of the weights.
    """
    import jax

    from llmd_kv_cache_tpu.events.model import EventBatch
    from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
    from llmd_kv_cache_tpu.models.llama import init_params, maybe_fuse_params

    if params is None:
        params = init_params(jax.random.PRNGKey(0), model_cfg)
    # Fuse ONCE before sharing — but only when the shape profits
    # (fuse_profitable: the 0.9B bench model's hidden 2048 measured ~8%
    # SLOWER fused on a v5e in July 2026, ROADMAP aim 1). Fusing a shared
    # unfused tree per pod would materialize n_pods private weight
    # copies (~1 GiB each at the TPU bench shape); fuse_params is a
    # no-op on an already-fused tree, so the engines just adopt it.
    params = maybe_fuse_params(params, model_cfg)
    # Capacity-constrained page pool (the regime where routing matters:
    # each pod can hold a few of the workload's shared prefixes, like the
    # reference's 73%-capacity setup). Round-robin thrashes the prefix
    # cache; KV-aware routing lets each pod own a prefix subset.
    pod_kw = dict(pod_kw) if pod_kw is not None else dict(DEFAULT_POD_KW)
    pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                indexer.token_processor)
    pods = {}
    for i in range(n_pods):
        name = f"pod-{i}"

        def sink(events, pod_name=name):
            pool.process_event_batch(
                EventBatch(timestamp=time.time(), events=list(events)),
                pod_name, MODEL_NAME,
            )

        pods[name] = engine_mod.MiniEngine(
            engine_mod.EngineConfig(
                model=model_cfg,
                model_name=MODEL_NAME,
                pod_identifier=name,
                **pod_kw,
            ),
            event_sink=sink,
            params=params,
            seed=0,
            offload_spec=(offload_spec_factory()
                          if offload_spec_factory is not None else None),
        )
    return pods


MODEL_NAME = "bench-llama"


def run_replay(pods, workload, router, tag=""):
    """Admit each request on the routed pod, measuring real service times.

    Returns ``(services, chosen, hit_rate)``: per-request measured prefill
    wall time, the routed pod per request, and the prefix-cache hit-rate
    (cached prompt tokens / total prompt tokens — the metric the
    reference's EPP tables track alongside TTFT,
    `benchmarking/73-capacity/README.md` "KV Cache Metrics Summary").

    Coarse progress goes to stderr (the stdout contract is one JSON line).
    """
    import sys

    services, chosen, cached_lens = [], [], []
    hit_tokens = total_tokens = 0
    pod_names = list(pods.keys())
    arm_start = time.perf_counter()
    for i, prompt in enumerate(workload):
        pod_name = router(i, prompt, pod_names)
        engine = pods[pod_name]
        start = time.perf_counter()
        req = engine.add_request(f"r{i}", prompt, max_new_tokens=1)
        services.append(time.perf_counter() - start)
        chosen.append(pod_name)
        # cached_len at admission = tokens served from cache (HBM prefix
        # hits and, on offload-enabled pods, storage-tier restores).
        cached_lens.append(min(req.cached_len, len(prompt)))
        hit_tokens += cached_lens[-1]
        total_tokens += len(prompt)
        if i % 16 == 15:
            print(f"[bench {tag}] {i + 1}/{len(workload)} requests, "
                  f"{time.perf_counter() - arm_start:.1f}s elapsed",
                  file=sys.stderr, flush=True)
    return services, chosen, hit_tokens / max(total_tokens, 1), cached_lens


def run_concurrent(pods, workload, router, arrivals, max_new_tokens=8,
                   tag=""):
    """Arrival-timed CONCURRENT replay through ``enqueue()``/``step()``.

    The virtual-time FIFO model (``queueing_ttfts``) composes serially
    measured service times, so they never interact with concurrency. This
    arm serves the workload through each pod's continuous-batching
    scheduler instead: requests are admitted when they arrive (in virtual
    time), prefill chunks interleave with running decodes, and decode
    steps batch every live request — so a measured TTFT includes queue
    wait, chunked-prefill stalls, batching interference, and decode load
    (reference analog: the real inference-perf runs behind
    ``benchmarking/73-capacity/README.md``).

    Virtual-time accounting over real compute: each pod has a clock;
    every ``enqueue``/``step`` call's wall time advances it. A pod picks
    up work when its clock is the fleet minimum, admissions happen at
    ``max(arrival, pod clock)``, and a request's TTFT is the clock at the
    end of the step that emitted its first token minus its arrival. Wall
    clock on one host would serialize the pods against each other (they
    share the machine), so virtual time is what makes an N-pod fleet
    honest here — the same reasoning as ``queueing_ttfts``, but with the
    service process real.

    Returns ``(ttfts, hit_rate, out_tok_s, decode)`` — one TTFT per
    request, the prefix hit rate, the fleet's sustained output throughput
    (decoded tokens / virtual makespan — the reference capacity tables'
    headline unit, 73-capacity README "Summary across QPS"), and decode
    latency samples: ``decode["itl"]`` is every inter-token gap in
    virtual time (the reference tables' "ITL mean" unit) and
    ``decode["tpot"]`` one per-request mean time-per-output-token
    (requests with ≥2 tokens).
    """
    import math
    import sys
    from collections import deque

    names = list(pods.keys())
    queues: dict = {p: deque() for p in names}
    clocks: dict = {p: 0.0 for p in names}
    arr_of: dict = {}
    ttfts: dict = {}
    emitted_once: set = set()
    # Decode latency accounting: last emission clock and token count per
    # request; gaps between consecutive emissions are the ITL samples.
    last_emit: dict = {}
    first_emit: dict = {}
    n_emitted: dict = {}
    itls: list = []
    hit_tokens = total_tokens = out_tokens = 0
    n = len(workload)
    i = 0
    arm_start = time.perf_counter()

    def inflight(p):
        return len(pods[p]._running)

    def busy(p):
        return bool(queues[p]) or inflight(p) > 0

    while i < n or any(busy(p) for p in names):
        t_arr = arrivals[i] if i < n else math.inf
        t_pod, pick = math.inf, None
        for p in names:
            if busy(p) and clocks[p] < t_pod:
                t_pod, pick = clocks[p], p
        if t_arr <= t_pod:
            # Next event is an arrival: route it with the index as of the
            # work already performed (events publish inside step()); load
            # routers also see each pod's outstanding work (queued +
            # in-flight) as of now.
            # Lazy: only the load router pays for the fleet scan.
            p = router(i, workload[i], names,
                       lambda: {q: len(queues[q]) + inflight(q)
                                for q in names})
            queues[p].append(i)
            arr_of[i] = t_arr
            if inflight(p) == 0 and len(queues[p]) == 1:
                clocks[p] = max(clocks[p], t_arr)  # idle pod fast-forwards
            i += 1
            continue

        p, eng = pick, pods[pick]
        # Admit everything that has arrived by this pod's clock (pool
        # permitting; an out-of-pages admission retries after steps free
        # pages as requests finish).
        while queues[p]:
            j = queues[p][0]
            t0 = time.perf_counter()
            try:
                req = eng.enqueue(f"r{j}", workload[j],
                                  max_new_tokens=max_new_tokens)
            except RuntimeError:
                clocks[p] += time.perf_counter() - t0
                if inflight(p) == 0:
                    raise  # nothing running will ever free pages
                break
            clocks[p] += time.perf_counter() - t0
            queues[p].popleft()
            hit_tokens += min(req.cached_len, len(workload[j]))
            total_tokens += len(workload[j])
        t0 = time.perf_counter()
        emitted = eng.step()
        clocks[p] += time.perf_counter() - t0
        out_tokens += len(emitted)
        new_first = False
        for rid in emitted:
            if rid not in emitted_once:
                emitted_once.add(rid)
                new_first = True
                j = int(rid[1:])
                ttfts[j] = clocks[p] - arr_of[j]
                first_emit[rid] = clocks[p]
                n_emitted[rid] = 1
            else:
                itls.append(clocks[p] - last_emit[rid])
                n_emitted[rid] += 1
            last_emit[rid] = clocks[p]
        if new_first and len(emitted_once) % 16 == 0:
            print(f"[bench {tag}] {len(emitted_once)}/{n} first tokens, "
                  f"{time.perf_counter() - arm_start:.1f}s elapsed",
                  file=sys.stderr, flush=True)

    assert len(ttfts) == n, f"served {len(ttfts)} of {n}"
    makespan = max(clocks.values())
    tpots = [
        (last_emit[rid] - first_emit[rid]) / (n_emitted[rid] - 1)
        for rid in first_emit if n_emitted[rid] > 1
    ]
    return ([ttfts[j] for j in range(n)], hit_tokens / max(total_tokens, 1),
            out_tokens / max(makespan, 1e-9),
            {"itl": itls, "tpot": tpots})


def make_kv_router(indexer):
    """Score-argmax router with round-robin fallback — shared by every
    KV-routed arm so the arms cannot silently diverge in policy.

    This is the reference's "precise scheduling" strategy (the EPP
    scoring from this indexer, benchmarking/37-capacity README); the
    factories below mirror its comparison strategies. Each score_tokens
    call is timed into ``router.score_latencies`` so arms can report
    scheduler overhead (see ``score_path_stats``)."""
    rr_counter = [0]
    latencies: list = []

    def router(_i, prompt, names, loads=None):
        t0 = time.perf_counter()
        scores = indexer.score_tokens(prompt, MODEL_NAME)
        latencies.append(time.perf_counter() - t0)
        if scores:
            return max(scores.items(), key=lambda kv: kv[1])[0]
        pick = names[rr_counter[0] % len(names)]
        rr_counter[0] += 1
        return pick

    router.score_latencies = latencies
    return router


def score_path_stats(router, indexer) -> dict:
    """Scheduler-overhead summary for a KV-routed arm: score_tokens
    latency percentiles plus the token processor's prefix-cache hit
    counters, so BENCH_r*.json tracks score-path cost over time."""
    out = {}
    lat = getattr(router, "score_latencies", None)
    if lat:
        out["score_tokens_p50_us"] = round(statistics.median(lat) * 1e6, 1)
        out["score_tokens_p99_us"] = round(
            float(np.quantile(lat, 0.99)) * 1e6, 1)
        out["score_tokens_calls"] = len(lat)
    pc = indexer.prefix_cache_stats()
    if pc is not None:
        out["prefix_cache_hit_rate"] = round(pc["block_hit_rate"], 4)
        out["prefix_cache_hits"] = pc["hits"]
        out["prefix_cache_misses"] = pc["misses"]
    return out


def make_rr_router(_indexer=None):
    """Round-robin baseline (deterministic uniform spread)."""
    def router(i, _p, names, loads=None):
        return names[i % len(names)]
    return router


def make_random_router(_indexer=None, seed=11):
    """Uniform-random scheduling — the reference's "random" strategy."""
    r = np.random.default_rng(seed)

    def router(_i, _p, names, loads=None):
        return names[int(r.integers(len(names)))]
    return router


def make_load_router(_indexer=None):
    """Least-outstanding-work scheduling — the reference's "load-aware"
    strategy: route to the pod with the fewest queued + in-flight
    requests at arrival (name order breaks ties)."""
    def router(_i, _p, names, loads=None):
        loads = (loads() if callable(loads) else loads) or {}
        return min(names, key=lambda p: (loads.get(p, 0), p))
    return router


def queueing_ttfts(services, chosen, arrivals):
    """Open-loop TTFTs from measured service times, in virtual time.

    Each pod serves FIFO; TTFT = queue wait + service. This is the regime
    behind the reference's headline tables — at saturation, routing
    quality compounds through queue depth, not just prefill skip
    (`benchmarking/73-capacity/README.md`: precise 0.542 s vs 92.5 s p90
    is queue-dominated). ``arrivals=None`` → bare service times. Because
    service times are fixed measurements, one replay supports a whole
    arrival-rate sweep (the reference's "Summary across QPS").
    """
    if arrivals is None:
        return list(services)
    pod_free: dict = {}
    ttfts = []
    for i, (svc, pod) in enumerate(zip(services, chosen)):
        begin = max(arrivals[i], pod_free.get(pod, 0.0))
        pod_free[pod] = begin + svc
        ttfts.append(begin + svc - arrivals[i])
    return ttfts


def bench_index_add(native: bool = True) -> dict:
    """Fallback metric: index Add throughput vs the reference's documented
    Go micro-benchmark (BenchmarkInMemory_Add: 6,086,106 ns/op on the same
    fixed-seed 10k-key workload, tests/profiling/kv_cache_index/README.md)."""
    import time

    from llmd_kv_cache_tpu.core import PodEntry

    if native:
        from llmd_kv_cache_tpu.index.native import NativeIndex as IndexImpl
        from llmd_kv_cache_tpu.index.native import NativeIndexConfig as ConfigImpl
        backend = "native C++ index"
    else:
        from llmd_kv_cache_tpu.index import InMemoryIndex as IndexImpl
        from llmd_kv_cache_tpu.index import InMemoryIndexConfig as ConfigImpl
        backend = "python in-memory index"

    rng = np.random.default_rng(42)
    keys = [int(x) for x in rng.integers(0, 2**63, 10_000, dtype=np.int64)]
    entries = [PodEntry("pod1", "gpu")]
    times = []
    for _ in range(30):
        idx = IndexImpl(ConfigImpl())
        start = time.perf_counter()
        idx.add(keys, keys, entries)
        times.append(time.perf_counter() - start)
    ns_op = min(times) * 1e9
    go_baseline_ns = 6_086_106
    return {
        "metric": f"index Add ns/op (10k-key workload, {backend}; "
                  "reference Go BenchmarkInMemory_Add = 6086106)",
        "value": round(ns_op),
        "unit": "ns/op",
        "vs_baseline": round(go_baseline_ns / ns_op, 3),
    }


def bench_offload_throughput() -> dict:
    """Secondary metric: offload store+load throughput through the full
    stack (device page gather → host slab → native file write, and back).
    Printed by ``--offload``; informational (the reference publishes no
    comparable figure)."""
    import shutil
    import tempfile
    import time

    import jax.numpy as jnp

    from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

    root = tempfile.mkdtemp(prefix="kvtpu-bench-offload-")
    try:
        layers, pages, page_size, kvh, hd = 16, 256, 16, 8, 128
        spec = SharedStorageOffloadSpec(
            root=root, model_name="bench", page_size=page_size,
            num_layers=layers, kv_heads=kvh, head_dim=hd, io_threads=4,
            parallel_agnostic=True,
        )
        rng = np.random.default_rng(0)
        shape = (layers, pages, kvh, page_size, hd)
        k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        handlers = spec.get_handlers(k, v)

        # 64 blocks of 2 pages each
        transfers = [(0x1000 + i, [1 + 2 * i, 2 + 2 * i]) for i in range(64)]
        start = time.perf_counter()
        job = handlers.async_store_blocks(transfers)
        result = None
        while result is None:
            for res in handlers.get_finished():
                if res.job_id == job:
                    result = res
            time.sleep(0.001)
        store_s = time.perf_counter() - start
        if not result.success or result.shed_hashes:
            raise RuntimeError(
                f"store leg degraded (success={result.success}, "
                f"shed={len(result.shed_hashes)}): throughput not measurable"
            )
        store_bytes = result.bytes_transferred

        start = time.perf_counter()
        job = handlers.async_load_blocks(transfers)
        result = None
        while result is None:
            for res in handlers.get_finished():
                if res.job_id == job:
                    result = res
            time.sleep(0.001)
        load_s = time.perf_counter() - start
        if not result.success:
            raise RuntimeError("load leg failed: throughput not measurable")
        load_bytes = result.bytes_transferred
        handlers.shutdown()

        return {
            "metric": "offload store/load throughput (64 blocks, "
                      f"{store_bytes / 1e6:.0f} MB, device↔host↔disk)",
            "value": round(store_bytes / store_s / 1e9, 3),
            "unit": "GB/s store "
                    f"({load_bytes / load_s / 1e9:.2f} GB/s load)",
            "vs_baseline": 1.0,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_decode_throughput(hybrid: bool = False) -> dict:
    """Secondary metric: steady-state greedy decode tokens/s through the
    engine, single-token stepping vs fused 32-token bursts
    (``forward_decode_steps``). The burst factor is the dispatch-overhead
    amortization — the figure that matters on real deployments where
    per-launch latency competes with per-token compute.

    ``hybrid=True`` runs a mixed full/SWA model instead: the burst rides
    the two-pool scan with freeze-and-reclaim window paging
    (``forward_decode_steps_hybrid``) — the arm VERDICT r2 #4 asked for,
    proving SWA families keep the dispatch-amortization win."""
    import time

    from llmd_kv_cache_tpu.models import engine as engine_mod
    from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params

    import jax

    hybrid_kw = dict(
        sliding_window=128, swa_layers=(1, 3),
    ) if hybrid else {}
    cfg = LlamaConfig(
        # head_dim 128: the Mosaic lane-tiling unit, so the real-TPU run
        # exercises the Pallas kernels (sub-128 head dims fall back to XLA)
        # — and the shape real model families (Llama/Qwen) actually use.
        vocab_size=8192, hidden_size=512, num_layers=4, num_heads=8,
        num_kv_heads=4, head_dim=128, intermediate_size=1408, page_size=16,
        **hybrid_kw,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 8000, 64).tolist() for _ in range(8)]
    max_new = 128
    rates = {}
    bursts = (1, 32)
    for burst in bursts:
        eng = engine_mod.MiniEngine(
            engine_mod.EngineConfig(
                model=cfg, num_pages=256, max_pages_per_seq=16,
                model_name="bench-decode", pod_identifier="p",
                decode_burst=burst,
            ),
            params=params, seed=0,
        )
        reqs = [eng.add_request(f"r{i}", p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        # one warm step so the decode program is compiled before timing
        eng.step()
        start = time.perf_counter()
        tokens_before = sum(len(r.output) for r in reqs)
        while not all(r.done for r in reqs):
            eng.step()
        elapsed = time.perf_counter() - start
        rates[burst] = (sum(len(r.output) for r in reqs) - tokens_before) / elapsed
    kind = "hybrid full/SWA" if hybrid else "dense"
    return {
        "metric": f"greedy decode tok/s, batch 8, {kind} (burst "
                  f"{bursts[-1]} vs single-step {rates[1]:.0f} tok/s)",
        "value": round(rates[bursts[-1]], 1),
        "unit": f"tok/s (x{rates[bursts[-1]] / rates[1]:.2f} vs single-step)",
        "vs_baseline": 1.0,
    }


def bench_ragged() -> dict:
    """Ragged single-kernel mixed prefill+decode dispatch vs the padded
    two-kernel path (``EngineConfig.ragged_attention``).

    Three replay mixes (prefill-heavy / decode-heavy / 50-50) run through
    engine pairs differing only in the ``ragged_attention`` knob. Padding
    waste is read from the engines' dispatch-token telemetry (the
    ``kvtpu_engine_ragged_*_tokens_total`` pair) — the padded path
    dispatches ``max_batch`` decode rows and full prefill chunks, the
    ragged path dispatches one flat token axis bucketed to the next power
    of two.

    On CPU the Pallas kernels run in interpret mode, so this is a
    correctness smoke: token streams must match the padded path exactly
    (greedy fp32) and only the waste ratios are meaningful. On a real TPU
    the workload scales up and the gate asserts >=1.5x decode throughput
    on the decode-heavy mix.
    """
    import time

    import jax

    from llmd_kv_cache_tpu.models import engine as engine_mod
    from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetryConfig,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=8192, hidden_size=512, num_layers=4, num_heads=8,
            num_kv_heads=4, head_dim=128, intermediate_size=1408,
            page_size=16,
        )
        # (prompt_len, max_new_tokens, n_requests) per replay mix
        mixes = {"prefill_heavy": (384, 8, 8), "decode_heavy": (32, 96, 8),
                 "mixed": (128, 32, 8)}
        num_pages, max_pps, max_batch = 1024, 64, 8
    else:
        import dataclasses

        import jax.numpy as jnp

        # fp32: the equivalence gate compares greedy argmax streams between
        # two differently-compiled programs — at bf16 resolution random tiny
        # models hit top-2 logit ties (~2^-9 gaps) that flip on benign
        # accumulation-order differences.
        cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
        mixes = {"prefill_heavy": (20, 2, 4), "decode_heavy": (5, 8, 4),
                 "mixed": (12, 4, 4)}
        num_pages, max_pps, max_batch = 128, 16, 4
    params = init_params(jax.random.PRNGKey(0), cfg)

    arms = {}
    for mix, (plen, max_new, nreq) in mixes.items():
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(1, cfg.vocab_size - 1,
                         plen + int(rng.integers(0, max(plen // 2, 2)))
                         ).tolist()
            for _ in range(nreq)
        ]
        per_path = {}
        for ragged in (False, True):
            eng = engine_mod.MiniEngine(
                engine_mod.EngineConfig(
                    model=cfg, num_pages=num_pages,
                    max_pages_per_seq=max_pps, max_batch=max_batch,
                    model_name="bench-ragged",
                    pod_identifier="ragged" if ragged else "padded",
                    ragged_attention=ragged,
                    telemetry=EngineTelemetryConfig(),
                ),
                params=params, seed=0,
            )
            if ragged:
                assert eng._ragged, "ragged path did not engage"
            reqs = [eng.enqueue(f"r{i}", p, max_new_tokens=max_new)
                    for i, p in enumerate(prompts)]
            eng.step()  # compile the dispatch before timing
            start = time.perf_counter()
            steps = 0
            while not all(r.done for r in reqs):
                eng.step()
                steps += 1
                assert steps < 10_000, f"{mix}: engine did not converge"
            elapsed = time.perf_counter() - start
            waste = eng.telemetry.debug_vars()["ragged"]
            real = waste["real_tokens_total"]
            padded = waste["padded_tokens_total"]
            per_path[ragged] = {
                "tok_s": sum(len(r.output) for r in reqs) / elapsed,
                "tokens": [list(r.output) for r in reqs],
                "waste_ratio": 1.0 - real / max(padded, 1),
            }
        if not on_tpu:
            # Interpret-mode equivalence gate: same greedy streams as the
            # padded two-kernel path, token for token (fp32 tiny model).
            assert per_path[True]["tokens"] == per_path[False]["tokens"], (
                f"{mix}: ragged token streams diverge from the padded path")
        arms[mix] = {
            "ragged_tok_s": round(per_path[True]["tok_s"], 2),
            "padded_tok_s": round(per_path[False]["tok_s"], 2),
            "speedup": round(per_path[True]["tok_s"]
                             / per_path[False]["tok_s"], 3),
            "ragged_waste": round(per_path[True]["waste_ratio"], 4),
            "padded_waste": round(per_path[False]["waste_ratio"], 4),
        }
    if on_tpu:
        # The on-chip gate: ragged dispatch must beat the padded two-kernel
        # path by >=1.5x on the decode-heavy replay (padding-FLOP + launch
        # elimination is the whole point of the single-kernel path).
        speed = arms["decode_heavy"]["speedup"]
        assert speed >= 1.5, (
            f"ragged decode-heavy speedup {speed:.2f}x < 1.5x gate")
        value = arms["decode_heavy"]["speedup"]
        unit = "x decode-heavy tok/s vs padded two-kernel path"
    else:
        # CPU smoke: the gate is token-stream equivalence (asserted above
        # for every mix) — throughput in interpret mode is meaningless.
        value = float(len(arms))
        unit = "replay mixes token-equivalent to the padded path (smoke)"
    return {
        "metric": "ragged single-kernel vs padded two-kernel dispatch "
                  "(prefill-heavy / decode-heavy / 50-50 replays)",
        "value": value,
        "unit": unit,
        "vs_baseline": 1.0,
        "arms": arms,
        "platform": "tpu" if on_tpu else "cpu-interpret",
    }


def bench_fp8_bandwidth() -> dict:
    """fp8 vs bf16 decode KV bandwidth at real batch shapes (the VERDICT
    r5 item-1 closeout: the fp8 arm's justification is halved attention
    HBM traffic, and it had zero measured perf).

    Times ``pallas_paged_decode_attention`` over identical page tables
    with a bf16 cache and its fp8 (e4m3) cast at the bandwidth-bound
    shape ROADMAP S1 names (b32 / ctx2048 / 8 kv heads / hd128),
    and reports ms/step next to the analytic KV bytes/step each dtype
    must stream. On CPU the kernel runs in interpret mode — timing is
    meaningless, so the probe degrades to a correctness smoke (fp8 kernel
    vs the XLA upcast-on-gather reference) plus the analytic byte counts;
    the decision rule (flip the default only if fp8's measured ms/step
    wins) is encoded in the output either way. The roofline argument
    lives in benchmarking/fp8-roofline/README.md.
    """
    import time

    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
    from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        batch, ctx, kv_heads, q_heads, head_dim, page_size = (
            32, 2048, 8, 16, 128, 16)
        iters, compute_dtype = 30, jnp.bfloat16
    else:
        batch, ctx, kv_heads, q_heads, head_dim, page_size = (
            2, 64, 2, 4, 128, 8)
        iters, compute_dtype = 1, jnp.float32
    pages_per_seq = ctx // page_size
    num_pages = batch * pages_per_seq + 1
    key = jax.random.PRNGKey(0)
    kk, kv, kq = jax.random.split(key, 3)
    k16 = jax.random.normal(
        kk, (num_pages, kv_heads, page_size, head_dim), compute_dtype)
    v16 = jax.random.normal(
        kv, (num_pages, kv_heads, page_size, head_dim), compute_dtype)
    k8 = k16.astype(jnp.float8_e4m3fn)
    v8 = v16.astype(jnp.float8_e4m3fn)
    q = jax.random.normal(kq, (batch, q_heads, head_dim), compute_dtype)
    page_table = (np.arange(batch * pages_per_seq, dtype=np.int32)
                  .reshape(batch, pages_per_seq) + 1)
    page_table = jnp.asarray(page_table)
    ctx_lens = jnp.full((batch,), ctx, jnp.int32)

    def run(k_cache, v_cache):
        return pallas_paged_decode_attention(
            q, k_cache, v_cache, page_table, ctx_lens,
            interpret=not on_tpu)

    wide = "bf16" if on_tpu else "f32"  # interpret smoke runs fp32
    results = {}
    kv_bytes = {}
    for name, (kc, vc) in {wide: (k16, v16), "fp8": (k8, v8)}.items():
        out = run(kc, vc)
        out.block_until_ready()
        start = time.perf_counter()
        for _ in range(iters):
            out = run(kc, vc)
        out.block_until_ready()
        results[name] = (time.perf_counter() - start) / iters * 1e3
        # Analytic KV stream per decode step: every live key+value page.
        kv_bytes[name] = int(
            2 * batch * ctx * kv_heads * head_dim * kc.dtype.itemsize)
    if not on_tpu:
        # Interpret smoke: the fp8 quant arm must match the XLA
        # upcast-on-gather reference on the same 1-byte cache.
        q_pos = jnp.full((batch, 1), ctx, jnp.int32)
        ref = paged_attention(
            q[:, None].transpose(0, 1, 2, 3).reshape(batch, 1, q_heads,
                                                     head_dim),
            k8, v8, page_table, q_pos, ctx_lens)[:, 0]
        np.testing.assert_allclose(
            np.asarray(run(k8, v8), np.float32),
            np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2)
    fp8_wins = on_tpu and results["fp8"] < results[wide] * 0.8
    return {
        "metric": f"fp8 vs {wide} decode ms/step, b{batch}/ctx{ctx}/"
                  f"kvh{kv_heads}/hd{head_dim} "
                  f"(KV stream {kv_bytes[wide] >> 10} KiB -> "
                  f"{kv_bytes['fp8'] >> 10} KiB per step)",
        "value": round(results["fp8"], 3),
        "unit": f"ms/step fp8 ({wide} {results[wide]:.3f} ms/step)",
        "vs_baseline": round(results[wide] / max(results["fp8"], 1e-9), 3),
        "kv_bytes_per_step": kv_bytes,
        "fp8_wins": bool(fp8_wins),
        "decision": ("flip kv_cache_dtype default to f8_e4m3"
                     if fp8_wins else
                     "keep bf16 default; see benchmarking/fp8-roofline"),
        "platform": "tpu" if on_tpu else "cpu-interpret",
    }


def bench_event_ingestion() -> dict:
    """Write-path capacity: raw ZMQ-shaped messages through the sharded
    pool into the (native) index, end to end (msgpack parse → request-key
    recompute → index add). Events/sec across 8 simulated pods."""
    import time

    import msgpack

    from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
    from llmd_kv_cache_tpu.events import Pool, PoolConfig, RawMessage
    from llmd_kv_cache_tpu.index.base import create_index

    block = 16
    processor = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=block))
    index = create_index(None)
    pool = Pool(PoolConfig(concurrency=4), index, processor)
    pool.start()

    rng = np.random.default_rng(0)
    n_msgs = 4000
    msgs = []
    for i in range(n_msgs):
        pod = f"pod-{i % 8}"
        tokens = rng.integers(1, 30000, 4 * block).tolist()  # 4 blocks/event
        ev = ["BlockStored", [int(h) for h in rng.integers(1, 2**62, 4)],
              None, tokens, block]
        msgs.append(RawMessage(
            topic=f"kv@{pod}@m", sequence=i,
            payload=msgpack.packb([float(i), [ev]], use_bin_type=True),
        ))

    start = time.perf_counter()
    for m in msgs:
        pool.add_task(m)
    pool.join()
    elapsed = time.perf_counter() - start
    pool.shutdown()

    return {
        "metric": "KV-event ingestion (BlockStored, 4 blocks/event, "
                  "parse+hash+index, 8 pods, 4 shards)",
        "value": round(n_msgs / elapsed),
        "unit": "events/s",
        "vs_baseline": 1.0,
        # Batched-drain effectiveness (events/pool.py): messages per
        # worker wakeup and index calls saved by digest coalescing.
        "ingest_batches": pool.ingest_batches,
        "ingest_messages": pool.ingest_messages,
        "ingest_coalesced_ops": pool.coalesced_ops,
    }


def bench_flight_recorder() -> dict:
    """Observability overhead: flight-recorder cost per record, its share
    of the Python-path score hot path (<1% asserted — the recorder rides
    every ``score_tokens`` call), and event-ingest lag p50/p99 through the
    sharded pool."""
    import time

    import msgpack

    from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.events import Pool, PoolConfig, RawMessage
    from llmd_kv_cache_tpu.index.base import create_index
    from llmd_kv_cache_tpu.scoring import Indexer
    from llmd_kv_cache_tpu.telemetry.flight_recorder import KIND_SCORE, FlightRecorder

    # -- ns/record: the exact hot-path shape (dict literal + ring store) --
    recorder = FlightRecorder()
    scores = {f"pod-{i}": float(i) for i in range(4)}
    n_records = 200_000
    start = time.perf_counter_ns()
    for _ in range(n_records):
        recorder.record(
            KIND_SCORE,
            {"model": "bench", "blocks": 64, "hits": 32, "scores": scores},
        )
    ns_per_record = (time.perf_counter_ns() - start) / n_records

    # -- score-path baseline (Python path: lookup + prefix scorer) --------
    indexer = Indexer()
    block = indexer.token_processor.block_size
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)
    n_scores = 2_000
    samples = []
    for _ in range(n_scores):
        t0 = time.perf_counter_ns()
        indexer.score_tokens(tokens, "bench")
        samples.append(time.perf_counter_ns() - t0)
    samples.sort()
    score_p50_ns = samples[len(samples) // 2]
    overhead_pct = 100.0 * ns_per_record / score_p50_ns
    # The recorder must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"flight recorder {ns_per_record:.0f} ns/record is "
        f"{overhead_pct:.2f}% of the {score_p50_ns} ns score p50"
    )

    # -- event-ingest lag through the sharded pool ------------------------
    processor = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=block))
    pool = Pool(PoolConfig(concurrency=4), create_index(None), processor)
    pool.start()
    n_msgs = 2000
    for i in range(n_msgs):
        pod = f"pod-{i % 8}"
        ev_tokens = rng.integers(1, 30000, 4 * block).tolist()
        ev = ["BlockStored", [int(h) for h in rng.integers(1, 2**62, 4)],
              None, ev_tokens, block]
        pool.add_task(RawMessage(
            topic=f"kv@{pod}@m", sequence=i,
            payload=msgpack.packb([time.time(), [ev]], use_bin_type=True),
        ))
    pool.join()
    lag = pool.lag_stats()
    pool.shutdown()

    return {
        "metric": "flight-recorder overhead on the score hot path "
                  "(Python path, 16-block prompt, 4 pods)",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "flight_recorder_ns_per_record": round(ns_per_record, 1),
        "score_p50_us": round(score_p50_ns / 1e3, 1),
        # Same-process publish→ingest, so skew-free: pure queueing+parse.
        "ingest_lag_p50_ms": round(lag.get("lag_p50_s", 0.0) * 1e3, 3),
        "ingest_lag_p99_ms": round(lag.get("lag_p99_s", 0.0) * 1e3, 3),
        "index_staleness_s": round(lag.get("staleness_s", 0.0), 3),
    }


def bench_snapshot_overhead() -> dict:
    """Crash-recovery overhead: score-path p50 with the periodic
    snapshotter running hot vs without it (<1% regression asserted —
    snapshots ride a background thread, never the score path), plus the
    cost of one snapshot of a populated index."""
    import tempfile
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.recovery import RecoveryConfig, RecoveryManager
    from llmd_kv_cache_tpu.scoring import Indexer

    indexer = Indexer()
    block = indexer.token_processor.block_size
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)
    # Realistic index population so dump_state moves real bytes.
    for i in range(2000):
        extra = rng.integers(1, 30000, 4 * block).tolist()
        indexer.kv_block_index.add(
            None, indexer.compute_block_keys(extra, "bench"),
            [entries[i % 4]])

    def score_p50_ns(n=20_000):
        samples = []
        for _ in range(n):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    class _SeqPool:
        """Just enough pool surface for the manager's snapshot loop."""

        def lag_stats(self):
            return {"pods": {f"pod-{i}": {"last_seq": 1000} for i in range(4)}}

        def index_staleness_s(self):
            return 0.0

    score_p50_ns(n=2_000)  # warm caches so both arms measure steady state
    baseline_ns = score_p50_ns()

    with tempfile.TemporaryDirectory() as tmp:
        mgr = RecoveryManager(
            RecoveryConfig(snapshot_dir=tmp, snapshot_interval_s=0.5,
                           snapshot_keep=2),
            indexer.kv_block_index, _SeqPool())
        t0 = time.perf_counter_ns()
        mgr.snapshot_now("bench")
        one_snapshot_ms = (time.perf_counter_ns() - t0) / 1e6
        # Hot arm: snapshots every 0.5 s while scoring — 60× the default
        # production cadence (30 s) — over a window spanning several
        # snapshot cycles.
        mgr.start()
        hot_ns = score_p50_ns()
        mgr.stop(final_snapshot=False)
        snapshots = mgr.snapshots_written

    regression_pct = 100.0 * (hot_ns - baseline_ns) / baseline_ns
    # The snapshotter must stay invisible on the score hot path.
    assert regression_pct < 1.0, (
        f"snapshotting regressed score p50 by {regression_pct:.2f}% "
        f"({baseline_ns} -> {hot_ns} ns) with {snapshots} snapshots written"
    )

    return {
        "metric": "score-path p50 regression with 0.5 s periodic snapshots "
                  "(Python path, 16-block prompt, 4 pods, ~10k-entry index)",
        "value": round(regression_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "score_p50_baseline_us": round(baseline_ns / 1e3, 1),
        "score_p50_snapshotting_us": round(hot_ns / 1e3, 1),
        "snapshot_write_ms": round(one_snapshot_ms, 3),
        "snapshots_during_window": snapshots,
    }


def bench_engine_telemetry() -> dict:
    """Engine-telemetry overhead gate: per-step hook cost as a share of the
    decode-step p50 (<1% asserted — the hooks ride every ``step()``), plus
    informational enabled-vs-disabled step p50s from real engine runs.

    The assertion is analytic (hook-ns / step-p50-ns) like the
    flight-recorder gate: two wall-clock arms of a sub-millisecond CPU
    step differ by more than 1% from scheduler noise alone, so a direct
    A/B assert would flap. Both arms still run and are reported."""
    import time

    import jax

    from llmd_kv_cache_tpu.models import engine as engine_mod
    from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetry,
        EngineTelemetryConfig,
    )

    cfg = LlamaConfig(
        vocab_size=8192, hidden_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=64, intermediate_size=704, page_size=16,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 8000, 64).tolist() for _ in range(4)]
    max_new = 96

    def step_p50_us(telemetry) -> float:
        eng = engine_mod.MiniEngine(
            engine_mod.EngineConfig(
                model=cfg, num_pages=128, max_pages_per_seq=16,
                model_name="bench-telemetry", pod_identifier="p",
                decode_burst=8, telemetry=telemetry,
            ),
            params=params, seed=0,
        )
        for i, p in enumerate(prompts):
            eng.enqueue(f"r{i}", p, max_new_tokens=max_new)
        eng.step()  # compile the prefill/decode programs before timing
        samples = []
        while True:
            t0 = time.perf_counter_ns()
            alive = eng.step()
            samples.append(time.perf_counter_ns() - t0)
            if not alive:
                break
        samples.sort()
        return samples[len(samples) // 2] / 1e3

    off_p50_us = step_p50_us(None)
    on_p50_us = step_p50_us(EngineTelemetryConfig())

    # -- analytic hook cost: the exact per-step call shape ----------------
    tel = EngineTelemetry(EngineTelemetryConfig())
    pool_eng = engine_mod.MiniEngine(
        engine_mod.EngineConfig(
            model=cfg, num_pages=128, max_pages_per_seq=16,
            model_name="bench-telemetry-pool", pod_identifier="p",
        ),
        params=params, seed=0,
    )
    pools = [("full", pool_eng.block_manager)]
    n = 100_000
    start = time.perf_counter_ns()
    for _ in range(n):
        tel.on_step(1e-3, True, pools)  # includes the 1-in-16 pool scrape
    ns_on_step = (time.perf_counter_ns() - start) / n

    tel.on_admitted("r0", 0)
    tel.on_first_token("r0")
    now = time.monotonic()
    start = time.perf_counter_ns()
    for i in range(n):
        tel.on_decode_tokens("r0", 1, now + i * 1e-3)
    ns_on_decode = (time.perf_counter_ns() - start) / n

    # Per step the engine pays one on_step plus one on_decode_tokens per
    # running request (batch of 4 here, matching the wall-clock arms).
    hook_ns_per_step = ns_on_step + len(prompts) * ns_on_decode
    overhead_pct = 100.0 * hook_ns_per_step / (off_p50_us * 1e3)
    # Telemetry must stay invisible on the decode-step path.
    assert overhead_pct < 1.0, (
        f"engine telemetry costs {hook_ns_per_step:.0f} ns/step — "
        f"{overhead_pct:.2f}% of the {off_p50_us:.0f} us decode-step p50"
    )

    return {
        "metric": "engine-telemetry overhead on the decode-step path "
                  "(batch 4, burst 8, pool scrape every 16 steps)",
        "value": round(overhead_pct, 4),
        "unit": "% of decode-step p50",
        "vs_baseline": 1.0,
        "hook_ns_per_step": round(hook_ns_per_step, 1),
        "on_step_ns": round(ns_on_step, 1),
        "on_decode_tokens_ns": round(ns_on_decode, 1),
        "step_p50_off_us": round(off_p50_us, 1),
        "step_p50_on_us": round(on_p50_us, 1),
    }


def bench_shard_fanout(shards: int = 4) -> dict:
    """Sharded control-plane overhead gate (``--shards N``, ISSUE 6).

    Two arms over the real gRPC wire on localhost, both scored through
    :class:`~llmd_kv_cache_tpu.cluster.router.ShardRouter` so the only
    variable is the fan-out width:

    - **baseline** — a single indexer replica (N=1 ring: one LookupBlocks
      RPC per score).
    - **sharded** — ``shards`` replicas holding ``shards``× the baseline
      index size in aggregate (ownership-filtered ingest, rf=2), scored by
      consistent-hash scatter-gather.

    Gate: sharded score p99 must stay within **1.15x** of the baseline —
    parallel fan-out, the ring-plan cache, and chunk early exit must hide
    the partitioning rather than tax the score hot path.

    The workload is the long-context regime sharding exists for (256
    blocks = 4096 tokens per prompt): each shard looks up and serializes
    ~1/N of the keys in parallel, so the big single-response tail the
    baseline pays is split across small messages. Fan-out runs as one
    chunk (``fanoutChunkBlocks: 0``) because every query is a full hit —
    chunked early exit only pays off on misses and has its own unit
    tests (tests/test_cluster_sharding.py).
    """
    from llmd_kv_cache_tpu.cluster.config import ClusterConfig
    from llmd_kv_cache_tpu.core import (
        ChunkedTokenDatabase,
        PodEntry,
        TokenProcessorConfig,
    )
    from llmd_kv_cache_tpu.cluster import ShardRouter
    from llmd_kv_cache_tpu.scoring.indexer import IndexerConfig
    from llmd_kv_cache_tpu.services.indexer_service import (
        IndexerService,
        serve,
    )

    BLOCKS, BSZ = 256, 16  # 4096-token prompts: 256 blocks of 16
    BASE_PROMPTS, QUERIES, WARMUP = 300, 200, 30
    rng = np.random.default_rng(7)

    def run_arm(n_shards: int, n_prompts: int, base_port: int) -> dict:
        addrs = [f"127.0.0.1:{base_port + i}" for i in range(n_shards)]
        rf = min(2, n_shards)
        tp = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=BSZ))
        # Unique leading token → every prompt owns a distinct key chain.
        prompts = [
            [base_port + j * 131071] + list(range(1, BLOCKS * BSZ))
            for j in range(n_prompts)
        ]
        services, servers = [], []
        try:
            for addr in addrs:
                cc = None
                if n_shards > 1:
                    cc = ClusterConfig(
                        shard_addresses=addrs, shard_id=addr,
                        replication_factor=rf,
                    )
                svc = IndexerService(IndexerConfig(
                    token_processor_config=TokenProcessorConfig(
                        block_size_tokens=BSZ),
                    cluster_config=cc,
                ))
                services.append(svc)
                servers.append(serve(addr, svc))
            # Broadcast ingest (the event stream every replica sees);
            # ShardFilterIndex keeps each replica at owned keys only.
            total_keys = 0
            for j, prompt in enumerate(prompts):
                keys = tp.tokens_to_kv_block_keys(0, prompt, MODEL_NAME)
                pod = [PodEntry(pod_identifier=f"pod-{j % 8}",
                                device_tier="gpu")]
                for svc in services:
                    (svc.shard_index or svc.indexer.kv_block_index).add(
                        None, keys, pod)
                total_keys += len(keys)
            router = ShardRouter(
                ClusterConfig(shard_addresses=addrs, replication_factor=rf,
                              fanout_chunk_blocks=0),
                token_processor_config=TokenProcessorConfig(
                    block_size_tokens=BSZ),
            )
            try:
                picks = rng.integers(n_prompts, size=QUERIES + WARMUP)
                lat, rpcs = [], 0
                for i, j in enumerate(picks):
                    t0 = time.perf_counter()
                    res = router.score(prompts[int(j)], MODEL_NAME)
                    dt = time.perf_counter() - t0
                    assert res.hit_blocks == BLOCKS and not res.degraded
                    if i >= WARMUP:
                        lat.append(dt)
                        rpcs += res.rpcs
                plan = router.debug_view()["plan_cache"]
            finally:
                router.close()
            return {
                "index_keys_total": total_keys,
                # Owned (post-filter) writes per replica: shows the ring
                # spreading the 4x population, ~rf/N of the keys each.
                "per_replica_owned_keys": [
                    svc.shard_index.owned_writes for svc in services
                ] if n_shards > 1 else [total_keys],
                "score_p50_us": round(
                    statistics.median(lat) * 1e6, 1),
                "score_p99_us": round(
                    float(np.quantile(lat, 0.99)) * 1e6, 1),
                "rpcs_per_score": round(rpcs / QUERIES, 2),
                "plan_cache_hit_rate": round(
                    plan["hits"] / max(plan["hits"] + plan["misses"], 1), 4),
            }
        finally:
            for server in servers:
                server.stop(grace=0)

    baseline = run_arm(1, BASE_PROMPTS, 15930)
    sharded = run_arm(shards, shards * BASE_PROMPTS, 15940)
    ratio = sharded["score_p99_us"] / max(baseline["score_p99_us"], 1e-9)
    return {
        "metric": f"scatter-gather score p99 vs single shard "
                  f"({shards} shards, {shards}x index size, rf=2)",
        "value": round(ratio, 3),
        "unit": "x single-shard p99",
        "vs_baseline": 1.15,
        "gate_ok": bool(ratio <= 1.15),
        "shards": shards,
        "baseline": baseline,
        "sharded": sharded,
    }


def bench_graytail(shards: int = 4) -> dict:
    """Gray-failure tail-tolerance gate (``--graytail``, PR 16).

    One 4-shard gRPC fleet (rf=2) scored through
    :class:`~llmd_kv_cache_tpu.cluster.router.ShardRouter`, three phases:

    - **healthy** — all shards fast; measures the baseline score p50/p99
      and warms every shard's hedge-trigger latency quantile.
    - **graytail** — ONE shard is delayed 10x the healthy score p50 via a
      seeded ``delay`` failpoint (``services.indexer.lookup.<shard>``):
      slow, not dead — every RPC still succeeds, so breakers must stay
      closed and hedged fan-out to the rf=2 replica owner must keep the
      score p99 within **2x** of the healthy baseline.
    - **deadline** — the same slowed fleet queried under a deliberately
      impossible ambient deadline: every response that overruns it must
      be shed (``DeadlineExceeded``) or flagged degraded — never
      silently late.

    The perf-sentinel headline is the *healthy-path* hedging overhead:
    the per-RPC bookkeeping (latency-quantile observe + trigger read +
    budget refill) the hedging machinery adds to every score even when
    nothing is slow. Gate: < 1% of the healthy score p50.
    """
    from llmd_kv_cache_tpu.cluster.config import ClusterConfig
    from llmd_kv_cache_tpu.cluster import ShardRouter
    from llmd_kv_cache_tpu.core import (
        ChunkedTokenDatabase,
        PodEntry,
        TokenProcessorConfig,
    )
    from llmd_kv_cache_tpu.resilience import Deadline, DeadlineExceeded
    from llmd_kv_cache_tpu.resilience.deadline import deadline_scope
    from llmd_kv_cache_tpu.resilience.failpoints import failpoints
    from llmd_kv_cache_tpu.resilience.hedging import (
        HedgeBudget,
        LatencyQuantileTracker,
    )
    from llmd_kv_cache_tpu.scoring.indexer import IndexerConfig
    from llmd_kv_cache_tpu.services.indexer_service import (
        FP_SHARD_LOOKUP,
        IndexerService,
        serve,
    )

    # Long-context regime (4096-token prompts, as bench_shard_fanout):
    # per-RPC service time must dominate localhost scheduling jitter or
    # the p99 ratio gate measures noise, not tail tolerance. For the same
    # reason the two arms are measured as interleaved time segments
    # (paired sampling): a noisy-neighbor burst lands on both arms
    # instead of flipping the ratio's sign.
    BLOCKS, BSZ = 256, 16
    PROMPTS, WARMUP, SEGS, SEG_Q, DEADLINE_Q = 64, 60, 8, 35, 10
    PACE_S = 0.004  # identical open-loop pacing for both arms
    # Demand is ~1 hedge per fleet-wide fan-out when 1/4 shards is slow
    # (~0.25/primary), so the budget is sized above demand; the bench
    # asserts the *measured* hedge rate stays under it.
    HEDGE_RATE, HEDGE_BURST = 0.35, 16.0
    rng = np.random.default_rng(16)
    base_port = 15960
    addrs = [f"127.0.0.1:{base_port + i}" for i in range(shards)]
    rf = 2
    tp = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=BSZ))
    prompts = [
        [base_port + j * 131071] + list(range(1, BLOCKS * BSZ))
        for j in range(PROMPTS)
    ]

    failpoints.reset(seed=1337)
    services, servers = [], []
    try:
        for addr in addrs:
            svc = IndexerService(IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size_tokens=BSZ),
                cluster_config=ClusterConfig(
                    shard_addresses=addrs, shard_id=addr,
                    replication_factor=rf,
                ),
            ))
            services.append(svc)
            servers.append(serve(addr, svc))
        for j, prompt in enumerate(prompts):
            keys = tp.tokens_to_kv_block_keys(0, prompt, MODEL_NAME)
            pod = [PodEntry(pod_identifier=f"pod-{j % 8}",
                            device_tier="gpu")]
            for svc in services:
                svc.shard_index.add(None, keys, pod)
        router = ShardRouter(
            ClusterConfig(
                shard_addresses=addrs, replication_factor=rf,
                fanout_chunk_blocks=0,
                hedge_budget_rate=HEDGE_RATE,
                hedge_budget_burst=HEDGE_BURST,
            ),
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BSZ),
        )
        try:
            def run_phase(n: int, record_from: int = 0, pace_s: float = 0.0):
                lat, hedges, rpcs, flagged = [], 0, 0, 0
                picks = rng.integers(PROMPTS, size=n)
                for i, j in enumerate(picks):
                    t0 = time.perf_counter()
                    res = router.score(prompts[int(j)], MODEL_NAME)
                    dt = time.perf_counter() - t0
                    assert res.hit_blocks == BLOCKS
                    if i >= record_from:
                        lat.append(dt)
                        hedges += res.hedges
                        rpcs += res.rpcs
                        flagged += int(res.degraded)
                    if pace_s:
                        time.sleep(pace_s)
                return lat, hedges, rpcs, flagged

            # Warmup: healthy traffic calibrates the slow delay and warms
            # the per-shard hedge quantiles past min_samples so gray
            # segments hedge from their first query.
            w_lat, _, w_rpcs, _ = run_phase(WARMUP, pace_s=PACE_S)
            slow_s = 10.0 * statistics.median(w_lat)
            fp_slow = f"{FP_SHARD_LOOKUP}.{addrs[1]}"

            # Paired measurement: alternate healthy / gray segments.
            h_lat, g_lat = [], []
            h_hedges = h_rpcs = g_hedges = g_rpcs = 0
            for seg in range(SEGS):
                gray = seg % 2 == 1
                if gray:
                    failpoints.arm(fp_slow, mode="delay", delay_s=slow_s)
                # Unrecorded lead-in so both arms measure steady state,
                # not the first queries after a boundary.
                run_phase(3, record_from=3, pace_s=PACE_S)
                lat, hedges, rpcs, _ = run_phase(SEG_Q, pace_s=PACE_S)
                if gray:
                    failpoints.disarm(fp_slow)
                    g_lat += lat
                    g_hedges += hedges
                    g_rpcs += rpcs
                    # Boundary drain: the slow shard's server is still
                    # working off delayed requests after disarm — settle
                    # it so the next healthy segment doesn't inherit the
                    # backlog.
                    time.sleep(2 * slow_s)
                else:
                    h_lat += lat
                    h_hedges += hedges
                    h_rpcs += rpcs
            h_p50 = statistics.median(h_lat)
            h_p99 = float(np.quantile(h_lat, 0.99))
            g_p99 = float(np.quantile(g_lat, 0.99))
            tail_ratio = g_p99 / max(h_p99, 1e-9)
            breakers = {s: b.state for s, b in router.breakers.items()}

            # Healthy-path hedging overhead: per-RPC bookkeeping cost.
            tracker = LatencyQuantileTracker(quantile=0.95)
            budget = HedgeBudget(rate=HEDGE_RATE, burst=HEDGE_BURST)
            N = 20000
            t0 = time.perf_counter()
            for _ in range(N):
                tracker.observe("s0", 0.001)
                tracker.value("s0")
                budget.on_primary()
            per_rpc = (time.perf_counter() - t0) / N
            rpcs_per_score = h_rpcs / max(len(h_lat), 1)
            overhead_pct = per_rpc * rpcs_per_score / h_p50 * 100.0
            assert overhead_pct < 1.0, (
                f"healthy-path hedging overhead {overhead_pct:.3f}% "
                f">= 1% of score p50")
            # Budget compliance: hedge *decisions* per primary, straight
            # from the token bucket — must stay within rate plus the
            # amortized burst credit.
            bstats = router.hedge_budget.stats()
            hedge_rate = bstats["hedges"] / max(bstats["primaries"], 1)
            hedge_rate_cap = HEDGE_RATE + (
                (HEDGE_BURST + 1.0) / max(bstats["primaries"], 1))

            # Phase 3: impossible deadline against the slowed fleet —
            # shed or flagged, never silently late.
            failpoints.arm(fp_slow, mode="delay", delay_s=slow_s)
            shed = late_flagged = late_unflagged = 0
            for j in rng.integers(PROMPTS, size=DEADLINE_Q):
                budget_s = 0.0005
                t0 = time.perf_counter()
                try:
                    with deadline_scope(Deadline.after(budget_s)):
                        res = router.score(prompts[int(j)], MODEL_NAME)
                except DeadlineExceeded:
                    shed += 1
                    continue
                if time.perf_counter() - t0 > budget_s:
                    if res.degraded or res.deadline_expired:
                        late_flagged += 1
                    else:
                        late_unflagged += 1
        finally:
            router.close()
    finally:
        failpoints.reset(seed=1337)
        for server in servers:
            server.stop(grace=0)

    gates = {
        "tail_ok": bool(tail_ratio <= 2.0),
        # Discriminating bound: an unhedged gather pays the full injected
        # delay on every score (all prompts touch the slow shard), so a
        # hedged p99 at half the delay proves hedges actually carried it.
        "tail_vs_injected_ok": bool(g_p99 <= 0.5 * slow_s),
        "overhead_ok": bool(overhead_pct < 1.0),
        "hedge_budget_ok": bool(hedge_rate <= hedge_rate_cap),
        "breakers_ok": all(s == "closed" for s in breakers.values()),
        "deadline_ok": late_unflagged == 0,
        "hedged_at_all": g_hedges > 0,
    }
    return {
        "metric": "healthy-path hedging bookkeeping overhead "
                  f"({shards} shards, rf=2; gray arm: 1 shard 10x slow)",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "gate_ok": all(gates.values()),
        "gates": gates,
        "healthy": {
            "score_p50_us": round(h_p50 * 1e6, 1),
            "score_p99_us": round(h_p99 * 1e6, 1),
            "rpcs_per_score": round(rpcs_per_score, 2),
            "hedges": h_hedges,
        },
        "graytail": {
            "slow_shard": addrs[1],
            "injected_delay_ms": round(slow_s * 1e3, 2),
            "score_p99_us": round(g_p99 * 1e6, 1),
            "tail_ratio_vs_healthy": round(tail_ratio, 3),
            "tail_gate": 2.0,
            "p99_vs_injected_delay": round(g_p99 / slow_s, 3),
            "hedge_rpcs": g_hedges,
            "hedge_decision_rate": round(hedge_rate, 4),
            "hedge_decision_cap": round(hedge_rate_cap, 4),
            "hedge_budget": bstats,
            "breakers": breakers,
        },
        "deadline": {
            "queries": DEADLINE_Q,
            "shed": shed,
            "late_flagged": late_flagged,
            "late_unflagged": late_unflagged,
        },
    }


def main(queued: bool = True) -> dict:
    """TTFT routing benchmark: service-time replay + open-loop QPS sweep.

    ``queued`` is retained for CLI compatibility; the sweep always runs
    (it reuses the measured service times, so it costs nothing extra).
    """
    import jax

    from llmd_kv_cache_tpu.core import TokenProcessorConfig
    from llmd_kv_cache_tpu.models import engine as engine_mod
    from llmd_kv_cache_tpu.models.llama import LlamaConfig
    from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig

    rng = np.random.default_rng(42)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py: the routing benchmark needs a TPU; JAX found "
            f"platform {dev.platform!r} ({dev.device_kind!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). A TTFT "
            f"from another backend is not a device number: no result.")
    from llmd_kv_cache_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile; see the helper
    # A ~0.9B-param model with 4k-token shared prefixes, so a prefix hit
    # skips real MXU work.
    model_cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=16,
        num_heads=16, num_kv_heads=8, head_dim=128,
        intermediate_size=5632, page_size=16,
    )
    wl_kw = dict(n_requests=48, n_prefixes=8, prefix_len=4096,
                 suffix_len=64, vocab=30000)
    # 768 pages/pod = 12k tokens ≈ 3 resident prefixes of the 8 —
    # capacity-constrained per pod (routing matters) while 8 pods fit
    # HBM: 8 × 768 MiB KV + 1.8 GiB params < 16 GiB v5e.
    pod_kw = dict(num_pages=768, max_pages_per_seq=272,
                  max_prefill_tokens=2048)
    # Every prefill bucket a partial prefix hit can produce: the full
    # prompt covers the 128-page chunk + 4-page tail; the shorter
    # lengths cover 8..64-page buckets (a partially evicted prefix
    # leaves a page-aligned remainder ≥ 4 pages). An unwarmed bucket
    # would compile inside an arm's timed window.
    warm_lens = [4096 + 64, 1024, 512, 256, 128]
    # KVTPU_BENCH_FP8=1: fp8 (e4m3) KV pools at the SAME HBM byte budget
    # — 1-byte elements double num_pages, so each pod holds twice the
    # resident prefixes. This is the fp8 capacity story measured in the
    # benchmark's own unit (hit rate → TTFT), on top of the
    # decode-bandwidth halving the kernel probes measure.
    fp8_pods = os.environ.get("KVTPU_BENCH_FP8") == "1"
    if fp8_pods:
        pod_kw["num_pages"] *= 2
        pod_kw["kv_cache_dtype"] = "f8_e4m3"
    # 8 pods — the reference's headline fleet size (73-capacity README).
    n_pods = 8
    workload = build_workload(rng, **wl_kw)

    def fresh_indexer():
        return Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size_tokens=model_cfg.page_size
                )
            )
        )

    # Warm the jit cache (prefill buckets + decode) so compile time doesn't
    # pollute TTFT for either arm.
    import sys as _sys
    _t0 = time.perf_counter()
    from llmd_kv_cache_tpu.models.llama import init_params as _init_params
    from llmd_kv_cache_tpu.models.llama import (
        maybe_fuse_params as _maybe_fuse_params)
    # Fused once here when the shape profits (fuse_profitable; the 0.9B
    # bench shape measured faster UNFUSED on the v5e); every fleet
    # shares this single tree (make_pods's fuse and the engines' are
    # no-ops on it).
    shared_params = _maybe_fuse_params(
        _init_params(jax.random.PRNGKey(0), model_cfg), model_cfg)
    warm_indexer = fresh_indexer()
    warm = make_pods(1, model_cfg, engine_mod, warm_indexer,
                     params=shared_params, pod_kw=pod_kw)["pod-0"]
    for wl in warm_lens:
        _tb = time.perf_counter()
        prompt = rng.integers(1, 8000, wl).tolist()
        warm.add_request(f"warm{wl}", prompt, max_new_tokens=1)
        print(f"[bench warm] len {wl}: "
              f"{time.perf_counter() - _tb:.1f}s", file=_sys.stderr, flush=True)
    # Warm the continuous-batching step path too (enqueue-side prefill
    # chunk + the padded batched-decode program the concurrent arms use).
    _tb = time.perf_counter()
    warm.enqueue("warmstep", rng.integers(1, 8000, 128).tolist(),
                 max_new_tokens=3)
    while warm.step():
        pass
    print(f"[bench warm] step path: {time.perf_counter() - _tb:.1f}s",
          file=_sys.stderr, flush=True)
    print(f"[bench warm] total {time.perf_counter() - _t0:.1f}s",
          file=_sys.stderr, flush=True)

    # Calibrate the fleet's all-cold capacity from a measured cold prefill
    # on the warmed pod so arrival rates are platform-honest.
    _tb = time.perf_counter()
    warm.add_request(
        "cal", rng.integers(1, 8000, wl_kw.get("prefix_len", 256)
                            + wl_kw.get("suffix_len", 32)).tolist(),
        max_new_tokens=1)
    d_cold = time.perf_counter() - _tb
    fleet_qps = n_pods / d_cold  # all-cold saturation rate
    print(f"[bench load] cold service {d_cold * 1e3:.0f}ms -> fleet "
          f"capacity {fleet_qps:.1f} req/s", file=_sys.stderr, flush=True)
    del warm

    # Arm 1: round-robin routing.
    rr_indexer = fresh_indexer()
    rr_pods = make_pods(n_pods, model_cfg, engine_mod, rr_indexer,
                        params=shared_params, pod_kw=pod_kw)
    rr_svc, rr_chosen, rr_hit, _ = run_replay(
        rr_pods, workload, router=lambda i, _p, names: names[i % len(names)],
        tag="round-robin",
    )
    del rr_pods

    # Arm 2: KV-cache-aware routing via the Indexer.
    kv_indexer = fresh_indexer()
    kv_pods = make_pods(n_pods, model_cfg, engine_mod, kv_indexer,
                        params=shared_params, pod_kw=pod_kw)
    kv_router = make_kv_router(kv_indexer)
    kv_svc, kv_chosen, kv_hit, _ = run_replay(
        kv_pods, workload, router=kv_router, tag="kv-aware")
    score_path = score_path_stats(kv_router, kv_indexer)
    del kv_pods

    # Arm 3 (storage tier): prefixes live on shared storage (served once by
    # a since-retired pod), HBM cold — admission restores instead of
    # recomputing. The end-value of the L7/L9 offload stack: a storage hit
    # must beat cold prefill.
    import os as _os
    st_p50 = None
    st_n = 0
    st_restore_svc, st_hit, st_fleets = _storage_arm(
        model_cfg, engine_mod, fresh_indexer, shared_params,
        pod_kw, n_pods, wl_kw)
    if st_restore_svc:
        st_p50 = statistics.median(st_restore_svc)
        st_n = len(st_restore_svc)

    # QPS sweep (reference "Summary across QPS"): the measured service
    # times are fixed, so one replay per arm supports the whole open-loop
    # sweep in virtual time. Rates are capacity-relative multipliers.
    sweep = []
    for mult in (0.5, 0.75, 1.0, 1.25, 1.5, 2.0):
        qps = mult * fleet_qps
        arr = np.cumsum(
            np.random.default_rng(7).exponential(1.0 / qps, len(workload)))
        rr_t = queueing_ttfts(rr_svc, rr_chosen, arr)
        kv_t = queueing_ttfts(kv_svc, kv_chosen, arr)
        row = {
            "qps": round(qps, 2), "mult": mult,
            "rr_p50": round(statistics.median(rr_t), 4),
            "rr_p90": round(float(np.quantile(rr_t, 0.9)), 4),
            "kv_p50": round(statistics.median(kv_t), 4),
            "kv_p90": round(float(np.quantile(kv_t, 0.9)), 4),
        }
        row["reduction_pct"] = round(
            100.0 * (1.0 - row["kv_p50"] / row["rr_p50"]), 2)
        sweep.append(row)
        print(f"[bench sweep] {mult:4.2f}x capacity ({qps:6.2f} qps): "
              f"p50 rr {row['rr_p50']:.3f}s kv {row['kv_p50']:.3f}s "
              f"(-{row['reduction_pct']:.1f}%), "
              f"p90 rr {row['rr_p90']:.3f}s kv {row['kv_p90']:.3f}s",
              file=_sys.stderr, flush=True)

    # Concurrent open-loop arms (VERDICT r3 #3): re-serve the workload
    # through the continuous-batching scheduler with arrival-timed
    # admission and real decode load, so TTFTs include batching
    # interference — methodology check on the virtual-time FIFO model
    # above (same arrival seeds; fewer points, each re-serves the fleet).
    conc_sweep = []
    # Each concurrent fleet re-serves the workload at real service times:
    # run the headline point plus one light- and one over-load point.
    # KVTPU_BENCH_FULL=1 widens the sweep to 6 QPS points (the reference
    # capacity tables' grid).
    conc_mults = ((0.5, 0.75, 1.0, 1.25, 1.5, 2.0)
                  if _os.environ.get("KVTPU_BENCH_FULL")
                  else (0.75, 1.25, 1.5))
    for mult in conc_mults:
        qps = mult * fleet_qps
        arr = np.cumsum(
            np.random.default_rng(7).exponential(1.0 / qps, len(workload)))
        crr_indexer = fresh_indexer()
        crr_pods = make_pods(n_pods, model_cfg, engine_mod, crr_indexer,
                             params=shared_params, pod_kw=pod_kw)
        crr_t, crr_hit, crr_tps, _ = run_concurrent(
            crr_pods, workload, make_rr_router(), arr,
            tag=f"conc-rr {mult}x")
        del crr_pods
        ckv_indexer = fresh_indexer()
        ckv_pods = make_pods(n_pods, model_cfg, engine_mod, ckv_indexer,
                             params=shared_params, pod_kw=pod_kw)
        ckv_t, ckv_hit, ckv_tps, _ = run_concurrent(
            ckv_pods, workload, make_kv_router(ckv_indexer), arr,
            tag=f"conc-kv {mult}x")
        del ckv_pods
        crow = {
            "qps": round(qps, 2), "mult": mult,
            "rr_p50": round(statistics.median(crr_t), 4),
            "rr_p90": round(float(np.quantile(crr_t, 0.9)), 4),
            "kv_p50": round(statistics.median(ckv_t), 4),
            "kv_p90": round(float(np.quantile(ckv_t, 0.9)), 4),
            "rr_hit": round(crr_hit, 4), "kv_hit": round(ckv_hit, 4),
            # Sustained output throughput (decoded tok / virtual
            # makespan) — the reference capacity tables' headline unit.
            "rr_out_tok_s": round(crr_tps, 1),
            "kv_out_tok_s": round(ckv_tps, 1),
        }
        crow["reduction_pct"] = round(
            100.0 * (1.0 - crow["kv_p50"] / crow["rr_p50"]), 2)
        conc_sweep.append(crow)
        print(f"[bench conc ] {mult:4.2f}x capacity ({qps:6.2f} qps): "
              f"p50 rr {crow['rr_p50']:.3f}s kv {crow['kv_p50']:.3f}s "
              f"(-{crow['reduction_pct']:.1f}%), "
              f"p90 rr {crow['rr_p90']:.3f}s kv {crow['kv_p90']:.3f}s, "
              f"out tok/s rr {crow['rr_out_tok_s']:.0f} "
              f"kv {crow['kv_out_tok_s']:.0f}",
              file=_sys.stderr, flush=True)

    # Strategy matrix at the headline point — the reference's
    # 37-capacity report compares precise (this indexer) / default /
    # load-aware / random scheduling on one workload; rr and kv already
    # ran above, so two more fleets cover the matrix.
    strategy_comparison = {}
    head_conc = next((r for r in conc_sweep if r["mult"] == 1.25), None)
    if head_conc is not None:
        strategy_comparison["round_robin"] = {
            "p50": head_conc["rr_p50"], "p90": head_conc["rr_p90"],
            "hit": head_conc["rr_hit"],
            "out_tok_s": head_conc["rr_out_tok_s"]}
        strategy_comparison["kv_precise"] = {
            "p50": head_conc["kv_p50"], "p90": head_conc["kv_p90"],
            "hit": head_conc["kv_hit"],
            "out_tok_s": head_conc["kv_out_tok_s"]}
        arr = np.cumsum(np.random.default_rng(7).exponential(
            1.0 / (1.25 * fleet_qps), len(workload)))
        for strat, factory in (("random", make_random_router),
                               ("load_aware", make_load_router)):
            s_indexer = fresh_indexer()
            s_pods = make_pods(n_pods, model_cfg, engine_mod, s_indexer,
                               params=shared_params, pod_kw=pod_kw)
            s_t, s_hit, s_tps, _ = run_concurrent(
                s_pods, workload, factory(s_indexer), arr,
                tag=f"conc-{strat}")
            del s_pods
            strategy_comparison[strat] = {
                "p50": round(statistics.median(s_t), 4),
                "p90": round(float(np.quantile(s_t, 0.9)), 4),
                "hit": round(s_hit, 4), "out_tok_s": round(s_tps, 1)}
            print(f"[bench strat] {strat}: p50 "
                  f"{strategy_comparison[strat]['p50']:.3f}s hit "
                  f"{s_hit:.2f} out {s_tps:.0f} tok/s",
                  file=_sys.stderr, flush=True)

    # Decode-heavy arm (VERDICT r4 #6): the 8-token decodes above make
    # "out tok/s" mostly prefill amortization; the reference capacity
    # tables report ITL mean alongside TTFT (73-capacity README "ITL
    # mean 0.026 s"). Re-serve the headline point with long decodes and
    # report ITL (inter-token gap) and TPOT (per-request mean) per
    # strategy. KVTPU_BENCH_DECODE_TOKENS overrides the depth.
    decode_heavy = {}
    decode_tokens = int(_os.environ.get("KVTPU_BENCH_DECODE_TOKENS", 96))
    if decode_tokens > 1:
        arr = np.cumsum(np.random.default_rng(7).exponential(
            1.0 / (1.25 * fleet_qps), len(workload)))
        dh_strategies = (("kv_precise", make_kv_router),
                         ("round_robin", make_rr_router),
                         ("load_aware", make_load_router),
                         ("random", make_random_router))
        for strat, factory in dh_strategies:
            d_indexer = fresh_indexer()
            d_pods = make_pods(n_pods, model_cfg, engine_mod, d_indexer,
                               params=shared_params, pod_kw=pod_kw)
            d_t, d_hit, d_tps, d_dec = run_concurrent(
                d_pods, workload, factory(d_indexer), arr,
                max_new_tokens=decode_tokens, tag=f"decode-{strat}")
            del d_pods
            itl, tpot = d_dec["itl"], d_dec["tpot"]
            decode_heavy[strat] = {
                "ttft_p50": round(statistics.median(d_t), 4),
                "itl_p50": round(statistics.median(itl), 5) if itl else None,
                "itl_p90": round(float(np.quantile(itl, 0.9)), 5)
                           if itl else None,
                "tpot_p50": round(statistics.median(tpot), 5)
                            if tpot else None,
                "tpot_p90": round(float(np.quantile(tpot, 0.9)), 5)
                            if tpot else None,
                "hit": round(d_hit, 4), "out_tok_s": round(d_tps, 1)}
            row = decode_heavy[strat]
            print(f"[bench decode] {strat}: ttft p50 {row['ttft_p50']:.3f}s "
                  f"itl p50 {row['itl_p50']}s p90 {row['itl_p90']}s "
                  f"out {row['out_tok_s']:.0f} tok/s",
                  file=_sys.stderr, flush=True)
        decode_heavy["max_new_tokens"] = decode_tokens

    # Headline: the 1.25×-capacity point, from the CONCURRENT
    # continuous-batching arm when it ran — measured TTFTs under real
    # batching interference and decode load, matching how the
    # reference's headline tables are produced (real inference-perf
    # serving, 73-capacity README). The virtual-time FIFO model stays in
    # the payload as the fast methodology-comparison arm; it
    # under-credits routing once prefill is fast (cold prefills cost
    # little when nothing else is running) and over-credits it at
    # saturation, so the served number is the honest one.
    head = next((r for r in conc_sweep if r["mult"] == 1.25), None)
    if head is not None:
        head_tag = "concurrent continuous batching"
        head_kv_hit, head_rr_hit = head["kv_hit"], head["rr_hit"]
    else:
        head = next(r for r in sweep if r["mult"] == 1.25)
        head_tag = "virtual-time replay"
        head_kv_hit, head_rr_hit = kv_hit, rr_hit
    reduction_pct = head["reduction_pct"]
    p50_rr, p50_kv = head["rr_p50"], head["kv_p50"]

    storage = ""
    if st_p50 is not None:
        cold_p50 = statistics.median(rr_svc)
        storage = (f", storage-restore p50 {st_p50:.3f}s vs cold "
                   f"{cold_p50:.3f}s (N={st_n}, {st_fleets} cold fleets, "
                   f"hit-rate {st_hit:.2f})")
    line = {
        "metric": "p50 TTFT reduction, KV-aware routing vs round-robin "
                  f"({n_pods} pods, shared-prefix {head_tag}, Poisson "
                  f"{head['qps']:.1f} req/s open-loop, p50 rr {p50_rr:.2f}s "
                  f"vs kv {p50_kv:.3f}s, hit-rate kv {head_kv_hit:.2f} vs rr "
                  f"{head_rr_hit:.2f}{storage}, "
                  f"{jax.devices()[0].platform}"
                  f"{', fp8 2x-page pools' if fp8_pods else ''})",
        "value": round(reduction_pct, 2),
        "unit": "%",
        "vs_baseline": round(reduction_pct / 40.0, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        # Headline-arm hit rates (match `value`/`metric`); the serial
        # replay arm's are kept under replay_* so consumers never mix
        # measurement arms.
        "hit_rate_kv": round(head_kv_hit, 4),
        "hit_rate_rr": round(head_rr_hit, 4),
        "replay_hit_rate_kv": round(kv_hit, 4),
        "replay_hit_rate_rr": round(rr_hit, 4),
        "qps_sweep": sweep,
        "concurrent_sweep": conc_sweep,
        "strategy_comparison": strategy_comparison,
        # Scheduler-side overhead of the serial replay's KV arm:
        # score_tokens latency and prefix-cache effectiveness.
        "score_path": score_path,
    }
    if decode_heavy:
        line["decode_heavy"] = decode_heavy
    if st_p50 is not None:
        line["storage_restore_p50_s"] = round(st_p50, 4)
        line["storage_hit_rate"] = round(st_hit, 4)
        line["storage_restore_samples"] = st_n
    return line


def _storage_arm(model_cfg, engine_mod, fresh_indexer, shared_params,
                 pod_kw, n_pods, wl_kw, min_restores=50, max_fleets=4):
    """Measure restore-from-shared-storage service times.

    A 'historic' pod serves every unique prefix once with write-through
    offload, flushes, and retires; fresh KV-routed fleets sharing the
    storage root then replay the workload — admissions hit the storage
    tier (`offload/manager.py` lookup → restore) instead of recomputing.
    Mirrors the reference's medium-tier weights
    (`pkg/kvcache/backend.go:19-33`: storage hits are worth routing to).

    Sample-size hardening (VERDICT r3 weak #3): the arm builds its own
    workload with ≥32 unique prefixes and replays it on repeated COLD
    fleets until at least ``min_restores`` genuine restore admissions are
    collected — a p50 over ≥50 points instead of 8.

    Returns ``(restore_services, hit_rate, fleets)`` where
    restore_services covers ONLY the requests actually served by a
    storage restore — the first touch of each prefix on a cold pod.
    Later requests for the same prefix are ordinary HBM hits and would
    dilute the restore number.
    """
    import shutil
    import sys as _sys
    import tempfile

    from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

    root = tempfile.mkdtemp(prefix="bench-storage-")

    def spec():
        # The spec dtype must match the pods' KV pool dtype (fingerprint
        # field; the engine refuses a mismatch) — fp8 pods under
        # KVTPU_BENCH_FP8 store 1-byte blocks.
        kv_dtype = {"f8_e4m3": "float8_e4m3fn"}.get(
            (pod_kw or {}).get("kv_cache_dtype"), "bfloat16")
        return SharedStorageOffloadSpec(
            root=root, model_name=MODEL_NAME, page_size=model_cfg.page_size,
            num_layers=model_cfg.num_layers, kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim, io_threads=4,
            parallel_agnostic=True, dtype=kv_dtype,
        )

    st_kw = dict(wl_kw)
    st_kw["n_prefixes"] = max(32, st_kw.get("n_prefixes", 8))
    workload = build_workload(np.random.default_rng(1234), **st_kw)

    try:
        indexer = fresh_indexer()
        historic = make_pods(1, model_cfg, engine_mod, indexer,
                             params=shared_params, pod_kw=pod_kw,
                             offload_spec_factory=spec)["pod-0"]
        seen = set()
        for i, prompt in enumerate(workload):
            key = tuple(prompt[:64])
            if key in seen:
                continue
            seen.add(key)
            historic.add_request(f"hist{i}", prompt, max_new_tokens=1)
            historic.flush_offload()
        del historic
        print(f"[bench storage] {len(seen)} prefixes stored to {root}",
              file=_sys.stderr, flush=True)

        restore_services: list = []
        fleet_hits: list = []
        fleets = 0
        while len(restore_services) < min_restores and fleets < max_fleets:
            fleets += 1
            st_indexer = fresh_indexer()
            pods = make_pods(n_pods, model_cfg, engine_mod, st_indexer,
                             params=shared_params, pod_kw=pod_kw,
                             offload_spec_factory=spec)
            services, chosen, fleet_hit, cached = run_replay(
                pods, workload, make_kv_router(st_indexer),
                tag=f"storage-restore fleet {fleets}")
            fleet_hits.append(fleet_hit)
            del pods
            # Restore-serving requests: first touch of a prefix on a pod
            # whose HBM cannot hold it yet, with cached tokens at
            # admission — those tokens can only have come from the
            # storage tier.
            touched: set = set()
            for i, prompt in enumerate(workload):
                pair = (chosen[i], tuple(prompt[:64]))
                if pair not in touched and cached[i] > 0:
                    restore_services.append(services[i])
                touched.add(pair)
            print(f"[bench storage] fleet {fleets}: "
                  f"{len(restore_services)} restore admissions so far",
                  file=_sys.stderr, flush=True)
        # Every fleet replays the same workload, so the mean of per-fleet
        # hit-rates is the token-weighted aggregate across all samples.
        hit = sum(fleet_hits) / max(len(fleet_hits), 1)
        return restore_services, hit, fleets
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_fleet_telemetry() -> dict:
    """Fleet-telemetry overhead gate (``--fleet-telemetry``, ISSUE 10).

    Span export rides every traced hot-path operation once a pod enables
    ``fleetTelemetry.spanExport``: each finished span costs one ring
    append (identity stamp + seq + evict-oldest). This gate asserts that
    cost stays <1% of the Python-path score p50 — the per-span microbench
    against the measured score path, like the flight-recorder gate, so
    the number is stable under scheduler noise.

    Also reported (informational): end-to-end score p50 with the
    recording exporter installed, wire-serialization throughput of a
    ``/debug/spans`` pull, and one collector assemble+critical-path round
    over the pulled spans.
    """
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer
    from llmd_kv_cache_tpu.services.telemetry_collector import TraceAssembler
    from llmd_kv_cache_tpu.telemetry import (
        InMemorySpanExporter,
        RecordedSpan,
        install_span_exporter,
        set_process_identity,
        uninstall_span_exporter,
    )

    # -- ns/span: the exact export shape (lock + ring append; seq/identity
    # stamping is deferred to pull time). Steady state: the collector's
    # pull keeps the ring below capacity, so the gated cost is the
    # non-evicting append. The ring-full path (drop counter) only runs
    # when the collector has been gone long enough to fill the ring;
    # reported informationally below. ``map`` drives the loop at C level
    # so the interpreter's per-iteration bytecode is not billed to export.
    from collections import deque as _deque

    n_spans = 200_000
    exporter = InMemorySpanExporter(max_spans=n_spans)
    set_process_identity("bench-pod")
    spans = []
    for i in range(n_spans):
        s = RecordedSpan("llm_d.kv_cache.score_tokens",
                         trace_id=i + 1, span_id=i + 1, parent_span_id=None,
                         attributes={"model": "bench", "blocks": 64})
        s.end_time = s.start_time
        spans.append(s)
    sink = _deque(maxlen=0)
    start = time.perf_counter_ns()
    sink.extend(map(exporter.export, spans))
    ns_per_span = (time.perf_counter_ns() - start) / n_spans

    # Ring-full arm: every further export evicts the oldest and counts the
    # drop — the degraded regime with no collector pulling.
    start = time.perf_counter_ns()
    sink.extend(map(exporter.export, spans[:20_000]))
    ns_per_span_full = (time.perf_counter_ns() - start) / 20_000

    # -- score-path baseline (Python path: lookup + prefix scorer) --------
    indexer = Indexer()
    block = indexer.token_processor.block_size
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n=2_000):
        samples = []
        for _ in range(n):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n=500)  # warm caches
    baseline_ns = score_p50_ns()
    overhead_pct = 100.0 * ns_per_span / baseline_ns
    # Span export must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"span export {ns_per_span:.0f} ns/span is "
        f"{overhead_pct:.2f}% of the {baseline_ns} ns score p50"
    )

    # -- informational: e2e recording-mode p50 + pull + assemble ----------
    live = install_span_exporter(InMemorySpanExporter(max_spans=10_000))
    try:
        score_p50_ns(n=500)  # warm the recording arm too
        recording_ns = score_p50_ns()
        t0 = time.perf_counter_ns()
        payload = live.export_since(-1)
        pull_ms = (time.perf_counter_ns() - t0) / 1e6
        assembler = TraceAssembler(idle_s=0.0)
        t0 = time.perf_counter_ns()
        assembler.ingest(payload["spans"])
        assembled = assembler.finalize_idle(force=True)
        assemble_ms = (time.perf_counter_ns() - t0) / 1e6
    finally:
        uninstall_span_exporter()
        set_process_identity(None)

    return {
        "metric": "span-export overhead on the score hot path "
                  "(Python path, 16-block prompt, 4 pods)",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "span_export_ns_per_span": round(ns_per_span, 1),
        "span_export_ns_per_span_ring_full": round(ns_per_span_full, 1),
        "score_p50_us": round(baseline_ns / 1e3, 1),
        "score_p50_recording_us": round(recording_ns / 1e3, 1),
        "spans_pulled": len(payload["spans"]),
        "debug_spans_pull_ms": round(pull_ms, 3),
        "traces_assembled": len(assembled),
        "assemble_critical_path_ms": round(assemble_ms, 3),
    }


def bench_pyprof_overhead() -> dict:
    """Sampling-profiler overhead gate (``--pyprof-overhead``, ISSUE 11).

    The continuous profiler steals ``pass_cost × hz`` of wall time from
    the program (one GIL-holding stack walk per period), so the expected
    sampler time inside any operation of duration T is ``T × pass_cost ×
    hz`` — its share of the score p50 *is* its CPU fraction. The gate
    asserts that fraction stays <1% from the measured per-pass cost,
    which is stable under scheduler noise (diffing p50 with/without the
    sampler would drown a sub-1% effect in jitter).

    Also reported: score p50 with the sampler actually running
    (informational cross-check) and the span-attributed hot-function
    shares that ``hack/perf_sentinel.py`` diffs against the committed
    baseline manifest.
    """
    import threading
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer
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

    cfg = SamplingProfilerConfig(enabled=True, hz=67.0, window_s=3600.0)
    profiler = SamplingProfiler(cfg)

    # Score workload: same shape as the fleet-telemetry gate (16-block
    # prompt, 4 candidate pods, Python scoring path).
    indexer = Indexer()
    block = indexer.token_processor.block_size
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n=2_000):
        samples = []
        for _ in range(n):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n=500)  # warm caches
    baseline_ns = score_p50_ns()

    # -- pass cost, measured against a realistically busy process: score
    # traffic runs (traced) in a worker thread while passes are timed
    # here. These samples double as the hot-function profile below.
    set_process_identity("bench-pod")
    install_span_exporter(InMemorySpanExporter(max_spans=50_000))
    stop = threading.Event()

    def drive() -> None:
        while not stop.is_set():
            indexer.score_tokens(tokens, "bench")

    worker = threading.Thread(target=drive, name="bench-score", daemon=True)
    worker.start()
    try:
        costs = sorted(profiler.sample_once() for _ in range(1_000))
    finally:
        stop.set()
        worker.join(timeout=5.0)
    avg_cost_s = sum(costs) / len(costs)
    overhead_pct = avg_cost_s * cfg.hz * 100.0
    # The always-on sampler must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"sampling pass costs {avg_cost_s * 1e6:.0f} us; at {cfg.hz:g} Hz "
        f"that is {overhead_pct:.2f}% of every second (and of the score "
        "p50)"
    )

    # -- informational: score p50 with the sampler thread live ------------
    profiler.start()
    try:
        sampled_ns = score_p50_ns()
    finally:
        profiler.stop()
        uninstall_span_exporter()
        set_process_identity(None)

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
    }

    return {
        "metric": "sampling-profiler overhead on the score hot path "
                  "(pass-cost x hz model, 67 Hz)",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50 (== sampler CPU fraction)",
        "vs_baseline": 1.0,
        "hz": cfg.hz,
        "pass_cost_us_avg": round(avg_cost_s * 1e6, 2),
        "pass_cost_us_p50": round(costs[len(costs) // 2] * 1e6, 2),
        "score_p50_us": round(baseline_ns / 1e3, 1),
        "score_p50_sampled_us": round(sampled_ns / 1e3, 1),
        "profile_samples": sum(w["samples"] for w in windows),
        "hot_functions": hot,
    }


def bench_workingset() -> dict:
    """Working-set sampler gates (``--workingset``, ISSUE 12).

    Two hard gates over ``telemetry/workingset.py``:

    1. **MRC accuracy** — the SHARDS-sampled miss-ratio curve must track
       an exact LRU stack-distance oracle within a bounded error on a
       seeded replay trace (zipf-ish popularity + sequential scan
       segments, the mix that makes naive LRU models lie). The oracle
       replays the same trace through a real most-recent-first stack, so
       the comparison is simulation-vs-estimate, not model-vs-model.
    2. **Overhead** — the hook the indexer runs per score call (a
       single batch enqueue; per-key work drains off the p50) must stay
       <1% of the Python-path score p50, same microbench-vs-p50 model
       as the span-export and pyprof gates.
    """
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer
    from llmd_kv_cache_tpu.telemetry import (
        WorkingSetConfig,
        WorkingSetTracker,
        estimate_hit_ratio,
    )

    # -- replay trace: zipf-ish popularity over a warm universe, with
    # periodic sequential scans through one-touch keys (cold traffic that
    # must depress the curve at every capacity, not just the tail).
    # Skew is kept moderate (zipf 0.5 over 4k keys): SHARDS concentrates
    # when no single key owns a macroscopic share of accesses — with a
    # 0.9-exponent zipf the top key alone is ~9% of traffic and whether
    # it hashes into the sample swings the curve by that much.
    rng = np.random.default_rng(12)
    universe = 4096
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    weights = 1.0 / ranks**0.5
    weights /= weights.sum()
    n_accesses = 40_000
    hot = rng.choice(universe, size=n_accesses, p=weights)
    trace: list = []
    scan_key = 1_000_000  # disjoint from the hot universe
    for i, k in enumerate(hot):
        trace.append(int(k))
        if i % 500 == 499:  # a 64-block one-touch scan every 500 accesses
            trace.extend(range(scan_key, scan_key + 64))
            scan_key += 64

    # -- exact oracle: true LRU stack distances (list.index is C-level,
    # so the O(depth) search stays cheap at this trace size).
    stack: list = []
    distances: list = []
    for k in trace:
        try:
            idx = stack.index(k)
        except ValueError:
            distances.append(None)  # cold: misses at every capacity
        else:
            distances.append(idx + 1)
            del stack[idx]
        stack.insert(0, k)
    capacities = (64, 128, 256, 512, 1024, 2048)
    n = len(trace)

    def oracle_hit_ratio(cap: int) -> float:
        return sum(1 for d in distances if d is not None and d <= cap) / n

    # -- estimator arms: the gated sampled tracker plus a rate-1.0 arm
    # that isolates bucket-quantization error from sampling error.
    def estimate_curve(rate: float) -> dict:
        tracker = WorkingSetTracker(WorkingSetConfig(
            enabled=True, sample_rate=rate, window_s=3600.0,
            max_tracked_blocks=4 * universe))
        for i in range(0, n, 64):
            tracker.record_accesses("hbm", trace[i:i + 64])
        tracker.rotate(force=True)
        window = tracker.export_since(-1)["windows"][-1]
        st = window["scopes"]["hbm"]
        return {cap: estimate_hit_ratio(st["hist"], st["cold"], cap)
                for cap in capacities}

    sample_rate = 0.2
    sampled_curve = estimate_curve(sample_rate)
    exact_rate_curve = estimate_curve(1.0)
    oracle_curve = {cap: oracle_hit_ratio(cap) for cap in capacities}
    mrc_err = max(abs(sampled_curve[c] - oracle_curve[c])
                  for c in capacities)
    quant_err = max(abs(exact_rate_curve[c] - oracle_curve[c])
                    for c in capacities)
    # 2^0.25 buckets bound quantization near 0.05 on this trace; the
    # sampling arm gets one more point of estimation noise on top.
    mrc_bound = 0.06
    assert mrc_err <= mrc_bound, (
        f"sampled MRC (rate {sample_rate:g}) is off by {mrc_err:.4f} "
        f"from the exact-simulation oracle (bound {mrc_bound:g}): "
        f"est {sampled_curve} vs oracle {oracle_curve}"
    )

    # -- score-path baseline (same workload as the other telemetry gates:
    # 16-block prompt, 4 candidate pods, Python scoring path).
    indexer = Indexer()
    block = indexer.token_processor.block_size
    trng = np.random.default_rng(7)
    tokens = trng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n_iter=2_000):
        samples = []
        for _ in range(n_iter):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n_iter=500)  # warm caches
    baseline_ns = score_p50_ns()

    # -- per-score hook cost on the p50 path: the exact call the indexer
    # makes per score_tokens (one record_accesses over the prompt's
    # block keys). The hook is a single deque append; the per-key work
    # drains on every 128th call, which lands in the tail, not the p50 —
    # so the gated number is the steady-state enqueue cost, measured
    # with drains forced outside the timed region. The amortized cost
    # including drains is reported (and self-reported at runtime via
    # kvtpu_workingset_overhead_seconds_total).
    hook_tracker = WorkingSetTracker(WorkingSetConfig(
        enabled=True, sample_rate=0.05, window_s=3600.0))
    hook_tracker.record_accesses("index", block_keys)  # warm filter memo
    hook_tracker._drain()
    rounds, per_round = 200, 100  # per_round < the drain threshold
    steady_ns = 0
    for _ in range(rounds):
        t0 = time.perf_counter_ns()
        for _ in range(per_round):
            hook_tracker.record_accesses("index", block_keys)
        steady_ns += time.perf_counter_ns() - t0
        hook_tracker._drain()
    hook_ns = steady_ns / (rounds * per_round)
    n_calls = 20_000
    t0 = time.perf_counter_ns()
    for _ in range(n_calls):
        hook_tracker.record_accesses("index", block_keys)
    amortized_ns = (time.perf_counter_ns() - t0) / n_calls
    overhead_pct = 100.0 * hook_ns / baseline_ns
    # The always-on sampler must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"workingset hook costs {hook_ns:.0f} ns per {len(block_keys)}-key "
        f"score call — {overhead_pct:.2f}% of the {baseline_ns} ns score "
        "p50"
    )

    # -- informational: e2e score p50 with the tracker actually attached.
    indexer.attach_workingset(hook_tracker)
    try:
        attached_ns = score_p50_ns()
    finally:
        indexer.workingset = None

    return {
        "metric": "working-set sampler: MRC error vs exact oracle + hook "
                  "overhead on the score hot path",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "sample_rate": sample_rate,
        "trace_accesses": n,
        "mrc_max_abs_error": round(mrc_err, 4),
        "mrc_error_bound": mrc_bound,
        "mrc_quantization_error_rate1": round(quant_err, 4),
        "mrc_sampled": {str(c): round(v, 4)
                        for c, v in sampled_curve.items()},
        "mrc_oracle": {str(c): round(v, 4)
                       for c, v in oracle_curve.items()},
        "hook_ns_per_score": round(hook_ns, 1),
        "hook_ns_per_score_amortized": round(amortized_ns, 1),
        "score_p50_us": round(baseline_ns / 1e3, 1),
        "score_p50_tracked_us": round(attached_ns / 1e3, 1),
    }


def bench_audit() -> dict:
    """Ground-truth audit hook overhead gate (``--audit``, ISSUE 18).

    The audit plane adds exactly one hook to the score hot path: when an
    ``AuditLog`` is attached, ``Indexer._record_score_decision`` appends
    one prediction record (dict build + ring append under a small lock)
    per score call. Same microbench-vs-p50 model as the flight-recorder,
    pyprof, and workingset gates: measure the hook in isolation, gate it
    <1% of the Python-path score p50, and report the e2e attached p50 as
    an informational cross-check. The engine-side outcome hook runs once
    per *request* (at prefill completion), not per score, so it is
    reported but not gated against the score p50.
    """
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer
    from llmd_kv_cache_tpu.telemetry.audit import AuditLog

    # -- score-path baseline (same workload as the other telemetry gates:
    # 16-block prompt, 4 candidate pods, Python scoring path).
    indexer = Indexer()
    block = indexer.token_processor.block_size
    trng = np.random.default_rng(7)
    tokens = trng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n_iter=2_000):
        samples = []
        for _ in range(n_iter):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n_iter=500)  # warm caches
    baseline_ns = score_p50_ns()

    # -- the per-score hook in isolation: the exact record_prediction
    # call _record_score_decision makes, with a service-realistic
    # staleness_fn wired (it runs on every append). The ring is sized at
    # the default capacity so steady state exercises eviction, the
    # worst case (append + del of the evicted slice).
    log = AuditLog(staleness_fn=lambda: 0.25)
    scores = {f"pod-{i}": float(4 - i) for i in range(4)}
    traceparent = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    n_calls = 20_000
    log.record_prediction(traceparent, "bench", 16, 4.0, scores, None)
    t0 = time.perf_counter_ns()
    for _ in range(n_calls):
        log.record_prediction(traceparent, "bench", 16, 4.0, scores, None)
    hook_ns = (time.perf_counter_ns() - t0) / n_calls
    overhead_pct = 100.0 * hook_ns / baseline_ns
    # The audit plane must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"audit prediction hook costs {hook_ns:.0f} ns per score call — "
        f"{overhead_pct:.2f}% of the {baseline_ns} ns score p50"
    )

    # -- informational: the once-per-request outcome append.
    t0 = time.perf_counter_ns()
    for i in range(n_calls):
        log.record_outcome(traceparent, f"r{i}", "pod-0", 16, 12, 2, 2)
    outcome_ns = (time.perf_counter_ns() - t0) / n_calls

    # -- informational: e2e score p50 with the log actually attached.
    indexer.attach_audit(log)
    try:
        attached_ns = score_p50_ns()
    finally:
        indexer.audit = None

    return {
        "metric": "ground-truth audit hook overhead on the score hot path",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "hook_ns_per_score": round(hook_ns, 1),
        "outcome_ns_per_request": round(outcome_ns, 1),
        "score_p50_us": round(baseline_ns / 1e3, 1),
        "score_p50_audited_us": round(attached_ns / 1e3, 1),
        "ring_dropped": log.debug_view()["dropped"],
    }


def bench_fencing() -> dict:
    """Epoch-fence overhead gate (``--fencing``, ISSUE 19).

    The membership plane adds exactly one check to each serving hot
    path: ``MembershipTable.check_request`` (score/lookup fences — an
    epoch compare under the table lock) and ``check_write`` (event
    ingest — the same plus a lease-validity read). Same
    microbench-vs-p50 model as the audit/flight-recorder/pyprof gates:
    measure the clean-path check in isolation, gate it <1% of the
    Python-path score p50, report the write-fence and the warn-mode
    rejection path as informational.
    """
    import time

    from llmd_kv_cache_tpu.cluster.membership import MembershipTable
    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer

    # -- score-path baseline (same workload as the other telemetry gates:
    # 16-block prompt, 4 candidate pods, Python scoring path).
    indexer = Indexer()
    block = indexer.token_processor.block_size
    trng = np.random.default_rng(7)
    tokens = trng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n_iter=2_000):
        samples = []
        for _ in range(n_iter):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n_iter=500)  # warm caches
    baseline_ns = score_p50_ns()

    # -- the per-request fence in isolation: the exact check the score
    # and lookup RPC handlers make on every request, on the clean path
    # (same-epoch stamp — what every request pays in steady state).
    table = MembershipTable()
    table.grant("pod-0")
    epoch = table.epoch
    n_calls = 20_000
    table.check_request(epoch, "score")
    t0 = time.perf_counter_ns()
    for _ in range(n_calls):
        table.check_request(epoch, "score")
    hook_ns = (time.perf_counter_ns() - t0) / n_calls
    overhead_pct = 100.0 * hook_ns / baseline_ns
    # The fence must stay invisible on the score hot path.
    assert overhead_pct < 1.0, (
        f"epoch fence check costs {hook_ns:.0f} ns per score call — "
        f"{overhead_pct:.2f}% of the {baseline_ns} ns score p50"
    )

    # -- informational: the ingest write fence (lease read + epoch check,
    # once per event batch) and the warn-mode stale-stamp path (metric +
    # flight record + bounded ring — only paid by fenced traffic).
    t0 = time.perf_counter_ns()
    for _ in range(n_calls):
        table.check_write("pod-0", epoch, "events.ingest")
    write_ns = (time.perf_counter_ns() - t0) / n_calls
    table.observe_epoch(epoch + 1, source="bench")
    n_reject = 2_000
    t0 = time.perf_counter_ns()
    for _ in range(n_reject):
        table.check_request(epoch, "score")
    reject_ns = (time.perf_counter_ns() - t0) / n_reject

    return {
        "metric": "epoch-fence check overhead on the score hot path",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "hook_ns_per_score": round(hook_ns, 1),
        "write_fence_ns_per_batch": round(write_ns, 1),
        "stale_reject_ns": round(reject_ns, 1),
        "score_p50_us": round(baseline_ns / 1e3, 1),
    }


def bench_incident() -> dict:
    """Incident black-box trigger-hook overhead gate (``--incident``,
    ISSUE 20).

    The incident plane touches the serving path in exactly one place:
    every alert/anomaly edge calls ``IncidentManager.maybe_open`` — one
    lock, a cooldown-table read, and (on the rare accepted edge) a
    thread handoff; the evidence fan-out and the bundle write run on the
    detached worker. Same microbench-vs-p50 model as the audit/fencing
    gates: measure the steady-state (cooldown-suppressed) trigger hook
    in isolation, gate it <1% of the Python-path score p50, and prove
    the bundle write is off the hot path by comparing the accepted-edge
    return latency against the full synchronous capture duration.
    """
    import json as _json
    import tempfile
    import time

    from llmd_kv_cache_tpu.core.keys import PodEntry
    from llmd_kv_cache_tpu.scoring import Indexer
    from llmd_kv_cache_tpu.telemetry.incident import (
        IncidentConfig,
        IncidentManager,
        load_bundle,
    )

    # -- score-path baseline (same workload as the other telemetry gates:
    # 16-block prompt, 4 candidate pods, Python scoring path).
    indexer = Indexer()
    block = indexer.token_processor.block_size
    trng = np.random.default_rng(7)
    tokens = trng.integers(1, 30000, 16 * block).tolist()
    block_keys = indexer.compute_block_keys(tokens, "bench")
    entries = [PodEntry(f"pod-{i}", "gpu") for i in range(4)]
    indexer.kv_block_index.add(None, block_keys, entries)

    def score_p50_ns(n_iter=2_000):
        samples = []
        for _ in range(n_iter):
            t0 = time.perf_counter_ns()
            indexer.score_tokens(tokens, "bench")
            samples.append(time.perf_counter_ns() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    score_p50_ns(n_iter=500)  # warm caches
    baseline_ns = score_p50_ns()

    # -- a 4-pod fleet behind a canned in-process transport: evidence
    # payloads sized like a busy pod (full default flight tail, a span
    # window) so the fan-out + bundle-write cost is realistic.
    flight = _json.dumps({
        "records": [{"seq": i, "ts": 1000.0 + i * 0.01, "mono": i * 0.01,
                     "kind": "score", "data": {"i": i}}
                    for i in range(512)],
        "next_seq": 511, "dropped": 0,
    }).encode()
    spans = _json.dumps({
        "spans": [{"name": "llm_d.kv_cache.score_tokens",
                   "start_time": 1000.0 + i * 0.01,
                   "end_time": 1000.001 + i * 0.01}
                  for i in range(256)],
        "next_seq": 255, "dropped": 0,
    }).encode()
    timeb = _json.dumps({"wall": 1000.0, "mono": 50.0, "pid": 1}).encode()

    def fetch(url: str) -> bytes:
        if "flight-recorder" in url:
            return flight
        if "/debug/spans" in url:
            return spans
        if "/debug/time" in url:
            return timeb
        raise OSError("404")  # remaining enrichment legs absent

    with tempfile.TemporaryDirectory() as tmp:
        mgr = IncidentManager(
            IncidentConfig(directory=tmp, cooldown_s=3600.0),
            fetch=fetch,
            targets=lambda: [(f"pod-{i}", f"10.0.0.{i}:9400", None)
                             for i in range(4)],
            local_evidence=lambda: {"rounds": 100},
        )

        # -- the accepted edge: maybe_open hands off to a worker thread
        # and returns. Its latency is what the scrape round actually
        # blocks on when an alert fires.
        t0 = time.perf_counter_ns()
        stub = mgr.maybe_open("slo:bench", {"why": "bench"})
        accept_ns = time.perf_counter_ns() - t0
        assert stub is not None and stub.get("state") == "capturing", stub
        mgr.wait()
        assert accept_ns < 50e6, (
            f"accepted-edge return took {accept_ns / 1e6:.1f} ms"
        )

        # -- the steady-state hook: every further edge inside the
        # cooldown window pays one lock + dict lookup. This is the cost
        # the edge stream pays per scrape round, so it is the gated
        # value.
        n_calls = 20_000
        t0 = time.perf_counter_ns()
        for _ in range(n_calls):
            mgr.maybe_open("slo:bench", {"why": "bench"})
        hook_ns = (time.perf_counter_ns() - t0) / n_calls
        overhead_pct = 100.0 * hook_ns / baseline_ns
        # The trigger hook must stay invisible on the serving path.
        assert overhead_pct < 1.0, (
            f"incident trigger hook costs {hook_ns:.0f} ns per edge — "
            f"{overhead_pct:.2f}% of the {baseline_ns} ns score p50"
        )

        # -- informational: the full fan-out + bundle write, run
        # synchronously so it can be timed, then the bundle verified.
        summary = mgr.maybe_open(
            "slo:bench-sync", {"why": "bench"}, force=True,
            synchronous=True)
        assert summary and summary.get("path"), summary
        doc = load_bundle(summary["path"])
        assert len(doc["pods"]) == 4, sorted(doc["pods"])

        # -- proof the bundle write is off the hot path: a transport
        # stalled 20ms per leg (a realistic cross-pod HTTP fan-out) must
        # not delay the accepted edge's return at all.
        stall_s = 0.02

        def slow_fetch(url: str) -> bytes:
            time.sleep(stall_s)
            return fetch(url)

        slow = IncidentManager(
            IncidentConfig(directory=tmp, cooldown_s=3600.0),
            fetch=slow_fetch,
            targets=lambda: [(f"pod-{i}", f"10.0.0.{i}:9400", None)
                             for i in range(4)],
            local_evidence=lambda: {"rounds": 100},
        )
        t0 = time.perf_counter_ns()
        stub = slow.maybe_open("slo:bench-slow", {"why": "bench"})
        slow_accept_ns = time.perf_counter_ns() - t0
        assert stub is not None and stub.get("state") == "capturing", stub
        slow.wait(timeout=30.0)
        slow_summary = slow.debug_view()["recent"][-1]
        slow_capture_ns = slow_summary["capture_seconds"] * 1e9
        assert slow_capture_ns >= 4 * stall_s * 1e9, slow_summary
        assert slow_accept_ns < slow_capture_ns / 4, (
            f"accepted-edge latency {slow_accept_ns / 1e6:.1f} ms is not "
            f"off the hot path (stalled capture takes "
            f"{slow_capture_ns / 1e6:.1f} ms)"
        )

    return {
        "metric": "incident trigger hook overhead on the serving path",
        "value": round(overhead_pct, 4),
        "unit": "% of score p50",
        "vs_baseline": 1.0,
        "hook_ns_per_edge": round(hook_ns, 1),
        "accept_latency_us": round(accept_ns / 1e3, 1),
        "stalled_accept_latency_us": round(slow_accept_ns / 1e3, 1),
        "stalled_capture_ms": round(slow_capture_ns / 1e6, 3),
        "capture_ms": round(summary["capture_seconds"] * 1e3, 3),
        "bundle_bytes": summary["bytes"],
        "pods_captured": summary["pods_captured"],
        "score_p50_us": round(baseline_ns / 1e3, 1),
    }


def bench_disagg() -> dict:
    """Prefill/decode disaggregation vs a monolithic fleet (decode-heavy).

    Two arms over the same decode-heavy replay (short shared-prefix
    prompts, long generations — the regime where decode batching, not
    prefill compute, bounds throughput):

    - **baseline**: two monolithic (``role="both"``) pods behind the KV
      router, served through ``run_concurrent`` — prefill chunks stall
      the decode batch on every admission.
    - **disagg**: one ``role="prefill"`` pod streaming chunk-granular
      KV commits through a shared storage root, one ``role="decode"``
      pod admitting with ``enqueue(handoff=True)`` — the transferred
      prefix restores while earlier decodes keep batching, and the
      decode pod never runs a full local prefill. Routing goes through
      a real ``IndexerService.get_pod_scores`` call (``role="decode"``,
      residency-aware), whose traceparent threads through
      ``HandoffCoordinator.begin`` and both engines so one trace spans
      GetPodScores → prefill commit → decode first token.

    CPU = correctness smoke (every handoff completes without fallback,
    transferred blocks actually restore, and the score→commit→decode
    trace is a single trace id); TPU = the perf gate from the issue:
    disagg must beat the monolithic baseline on out_tok/s while holding
    TTFT p50 within 1.25x.
    """
    import math
    import shutil
    import sys as _sys
    import tempfile

    import jax

    from llmd_kv_cache_tpu.core import TokenProcessorConfig
    from llmd_kv_cache_tpu.events.model import EventBatch
    from llmd_kv_cache_tpu.models import engine as engine_mod
    from llmd_kv_cache_tpu.models.llama import (LlamaConfig, init_params,
                                                maybe_fuse_params)
    from llmd_kv_cache_tpu.offload.handoff import HandoffCoordinator
    from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec
    from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig
    from llmd_kv_cache_tpu.scoring.residency import ResidencyTracker
    from llmd_kv_cache_tpu.services.indexer_service import (IndexerService,
                                                            ScoreRequest)
    from llmd_kv_cache_tpu.telemetry.tracing import recording_tracing

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        model_cfg = LlamaConfig(
            vocab_size=8192, hidden_size=512, num_layers=4, num_heads=8,
            num_kv_heads=4, head_dim=128, intermediate_size=1408,
            page_size=16,
        )
        wl_kw = dict(n_requests=24, n_prefixes=6, prefix_len=256,
                     suffix_len=32, vocab=8000)
        max_new = 64
        pod_kw = dict(num_pages=1024, max_pages_per_seq=48,
                      max_prefill_tokens=128)
    else:
        model_cfg = LlamaConfig.tiny()  # page_size 4
        wl_kw = dict(n_requests=8, n_prefixes=4, prefix_len=8,
                     suffix_len=4, vocab=4000)
        max_new = 16
        # Two prefill chunks per 12-token prompt (chunk cap 8) so the
        # handoff actually streams; pool sized for every request decoding
        # concurrently on the single decode pod.
        pod_kw = dict(num_pages=128, max_pages_per_seq=16,
                      max_prefill_tokens=2 * model_cfg.page_size)
    page = model_cfg.page_size
    workload = build_workload(np.random.default_rng(2026), **wl_kw)
    n = len(workload)
    params = maybe_fuse_params(
        init_params(jax.random.PRNGKey(0), model_cfg), model_cfg)

    def fresh_indexer_cfg():
        return IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=page))

    # --- baseline: 2 monolithic pods, KV-routed concurrent replay ---
    base_indexer = Indexer(fresh_indexer_cfg())
    base_pods = make_pods(2, model_cfg, engine_mod, base_indexer,
                          params=params, pod_kw=pod_kw)
    arrivals = [0.0] * n  # burst replay: decode batching under load
    base_t, base_hit, base_tps, _ = run_concurrent(
        base_pods, workload, make_kv_router(base_indexer), arrivals,
        max_new_tokens=max_new, tag="disagg-base")
    del base_pods
    base_p50 = statistics.median(base_t)

    # --- disagg: prefill pod → shared storage root → decode pod ---
    root = tempfile.mkdtemp(prefix="bench-disagg-")

    def spec():
        return SharedStorageOffloadSpec(
            root=root, model_name=MODEL_NAME, page_size=page,
            num_layers=model_cfg.num_layers,
            kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim, io_threads=4,
            parallel_agnostic=True, dtype="bfloat16",
        )

    try:
        svc = IndexerService(fresh_indexer_cfg())
        tracker = ResidencyTracker()
        svc.indexer.attach_residency(tracker)
        coord = HandoffCoordinator(residency=tracker)

        def pod(name, role):
            def sink(events, pod_name=name):
                svc.pool.process_event_batch(
                    EventBatch(timestamp=time.time(), events=list(events)),
                    pod_name, MODEL_NAME)

            eng = engine_mod.MiniEngine(
                engine_mod.EngineConfig(
                    model=model_cfg, model_name=MODEL_NAME,
                    pod_identifier=name, role=role, handoff_wait_s=60.0,
                    **pod_kw),
                event_sink=sink, params=params, seed=0,
                offload_spec=spec())
            eng.attach_handoff(coord)
            return eng

        prefill, decode = pod("prefill-0", "prefill"), pod("decode-0", "decode")

        # Virtual-time accounting as in run_concurrent: one clock per
        # pod, every enqueue/step's wall time advances it, the pod at
        # the minimum clock acts next. An admission lands on BOTH pods
        # (prefill bootstraps and commits; decode waits on the handoff).
        clocks = {"p": 0.0, "d": 0.0}
        reqs: dict = {}
        arr_of: dict = {}
        ttfts: dict = {}
        first_emit: dict = {}
        last_emit: dict = {}
        n_emitted: dict = {}
        out_tokens = 0
        i = 0
        arm_start = time.perf_counter()

        def p_busy():
            return bool(prefill._running) or bool(prefill._pending_store_jobs)

        def d_busy():
            return bool(decode._running)

        with recording_tracing() as exporter:
            while i < n or p_busy() or d_busy():
                t_arr = arrivals[i] if i < n else math.inf
                t_pod, pick = math.inf, None
                if p_busy():
                    t_pod, pick = clocks["p"], "p"
                if d_busy() and clocks["d"] < t_pod:
                    t_pod, pick = clocks["d"], "d"
                if t_arr <= t_pod:
                    rid, prompt = f"r{i}", workload[i]
                    # Score with the decode role: residency-aware ranks,
                    # and the response traceparent threads the whole
                    # handoff under the GetPodScores span.
                    resp = svc.get_pod_scores(ScoreRequest(
                        tokens=list(prompt), model_name=MODEL_NAME,
                        pod_identifiers=["decode-0"], role="decode"))
                    tp = resp.traceparent or None
                    _, dpod = HandoffCoordinator.pick_pair(
                        ["prefill-0"], ["decode-0"],
                        decode_scores=resp.scores)
                    coord.begin(rid, "prefill-0", dpod,
                                total_blocks=len(prompt) // page,
                                traceparent=tp)
                    if not p_busy():
                        clocks["p"] = max(clocks["p"], t_arr)
                    t0 = time.perf_counter()
                    prefill.enqueue(rid, prompt, max_new_tokens=1,
                                    traceparent=tp)
                    clocks["p"] += time.perf_counter() - t0
                    if not d_busy():
                        clocks["d"] = max(clocks["d"], t_arr)
                    t0 = time.perf_counter()
                    reqs[rid] = decode.enqueue(rid, prompt,
                                               max_new_tokens=max_new,
                                               traceparent=tp, handoff=True)
                    clocks["d"] += time.perf_counter() - t0
                    arr_of[rid] = t_arr
                    i += 1
                    continue
                if pick == "p":
                    t0 = time.perf_counter()
                    if prefill._running:
                        prefill.step()  # bootstrap tokens are discarded
                    prefill.poll_offload()
                    clocks["p"] += time.perf_counter() - t0
                    continue
                t0 = time.perf_counter()
                emitted = decode.step()
                clocks["d"] += time.perf_counter() - t0
                out_tokens += len(emitted)
                for rid in emitted:
                    if rid not in first_emit:
                        ttfts[rid] = clocks["d"] - arr_of[rid]
                        first_emit[rid] = clocks["d"]
                        n_emitted[rid] = 1
                        if len(first_emit) % 8 == 0:
                            print(f"[bench disagg] {len(first_emit)}/{n} "
                                  f"first tokens, "
                                  f"{time.perf_counter() - arm_start:.1f}s",
                                  file=_sys.stderr, flush=True)
                    else:
                        n_emitted[rid] += 1
                    last_emit[rid] = clocks["d"]

        assert len(ttfts) == n, f"decoded {len(ttfts)} of {n}"
        dbg = coord.debug()
        restored = sum(min(r.cached_len, len(workload[int(rid[1:])]))
                       for rid, r in reqs.items())
        # Score→serve trace continuity: one trace id must cover the
        # scorer's span, a prefill commit, and a decode step.
        def trace_ids(name):
            return {sp.trace_id for sp in exporter.find(name)}
        joint = (trace_ids("llm_d.kv_cache.indexer.GetPodScores")
                 & trace_ids("llm_d.kv_cache.handoff.prefill_commit")
                 & trace_ids("llm_d.kv_cache.engine.decode_step"))
        disagg_tps = out_tokens / max(max(clocks.values()), 1e-9)
        disagg_p50 = statistics.median(ttfts.values())
    finally:
        shutil.rmtree(root, ignore_errors=True)

    ratio = disagg_tps / max(base_tps, 1e-9)
    ttft_ratio = disagg_p50 / max(base_p50, 1e-9)
    completed = int(dbg["completed"])
    disagg_detail = {
        "ttft_p50_s": round(disagg_p50, 4),
        "out_tok_s": round(disagg_tps, 1),
        "out_tok_s_ratio": round(ratio, 3),
        "ttft_p50_ratio": round(ttft_ratio, 3),
        "handoffs_completed": completed,
        "handoff_fallbacks": int(dbg["failed"]),
        "restored_tokens": int(restored),
        "trace_continuity": bool(joint),
    }
    baseline_detail = {
        "ttft_p50_s": round(base_p50, 4),
        "out_tok_s": round(base_tps, 1),
        "hit_rate": round(base_hit, 4),
    }
    if on_tpu:
        # The issue's gate: more sustained decode throughput at fixed
        # (within 1.25x) TTFT p50.
        return {
            "metric": "disaggregated handoff out_tok/s vs monolithic "
                      "(decode-heavy, TTFT p50 held within 1.25x)",
            "value": round(ratio, 3),
            "unit": "x monolithic out_tok/s",
            "vs_baseline": 1.0,
            "gate_ok": bool(ratio > 1.0 and ttft_ratio <= 1.25),
            "platform": platform,
            "baseline": baseline_detail,
            "disagg": disagg_detail,
        }
    # CPU smoke: the perf claim is TPU-only; here the gate is the
    # correctness of the handoff plane end to end.
    return {
        "metric": "disaggregated handoff CPU smoke "
                  "(completed handoffs, no fallbacks)",
        "value": completed,
        "unit": "handoffs",
        "vs_baseline": n,
        "gate_ok": bool(completed == n and dbg["failed"] == 0
                        and restored > 0 and joint),
        "platform": platform,
        "baseline": baseline_detail,
        "disagg": disagg_detail,
    }


def bench_controller() -> dict:
    """Fleet-controller chaos arm (``--controller``, ISSUE 13).

    Three deterministic scenarios drive a REAL control stack — SLORegistry
    burn-rate alerting, HandoffCoordinator mix EMA, HashRing membership,
    FleetController with hysteresis/cooldown/budget policy — against a
    modeled fleet (pod service times are analytic functions of topology,
    so the arm is fast and bit-stable):

    1. **re-role chaos**: traffic flips balanced → prefill-heavy mid-run;
       the controller must flip a decode pod to prefill with zero manual
       intervention and bring modeled TTFT p90 back inside the SLO.
    2. **shard ramp**: the index grows 4x; the controller must scale the
       ring up (each join moving < 2/N of partitions) and hold modeled
       score p99 at the threshold.
    3. **flap injection**: the burn rate oscillates around the act band
       every round for 40 rounds; hysteresis must bound executed actions
       (the perf-sentinel value — lower is better, baseline 1).

    Every executed action must carry a ``llm_d.kv_cache.control.action``
    span with the causing signal attached (part of the gate).
    """
    from llmd_kv_cache_tpu.cluster.ring import HashRing, moved_partitions
    from llmd_kv_cache_tpu.control import (
        CollectorSignalSource,
        ControllerConfig,
        FleetController,
        InProcessActuator,
    )
    from llmd_kv_cache_tpu.offload.handoff import HandoffCoordinator
    from llmd_kv_cache_tpu.telemetry.slo import SLOConfig, SLORegistry
    from llmd_kv_cache_tpu.telemetry.tracing import recording_tracing

    clk = [0.0]

    def clock():
        return clk[0]

    def p90(values):
        xs = sorted(values)
        return xs[min(len(xs) - 1, int(0.9 * len(xs)))] if xs else 0.0

    with recording_tracing() as exporter:
        # -- scenario 1: prefill-heavy flip → re-role ----------------------
        roles = {"pod-0": "prefill", "pod-1": "prefill",
                 "pod-2": "decode", "pod-3": "decode"}
        reg = SLORegistry(clock=clock)
        ttft_slo = reg.add(SLOConfig(
            name="ttft", objective=0.99,
            fast_windows=(5.0, 10.0), slow_window=20.0))
        handoff = HandoffCoordinator()
        handoff.mix_alpha = 0.5  # fast EMA so the flip lands in a few rounds
        src = CollectorSignalSource(
            slo_registry=reg, handoff=handoff,
            shards=lambda: ["shard-0"], roles=lambda: dict(roles),
            clock=clock)
        act = InProcessActuator(
            set_role=lambda t, r: roles.__setitem__(t, r),
            drain_pod=lambda t: {"ok": True})
        ctl = FleetController(
            src, act,
            config=ControllerConfig(
                confirm_rounds=2, role_cooldown_s=3.0,
                role_imbalance_act=0.2, role_imbalance_rearm=0.1),
            clock=clock)
        TTFT_BASE, TTFT_SLO_S = 1.4, 2.0
        ttfts = []
        for rnd in range(40):
            mix = 0.5 if rnd < 10 else 0.85  # the chaos flip
            handoff.observe_mix(int(mix * 100), 100 - int(mix * 100))
            prefill_frac = (
                sum(1 for r in roles.values() if r == "prefill")
                / max(len(roles), 1))
            ttft_s = TTFT_BASE * max(1.0, mix / max(prefill_frac, 1e-9))
            ttfts.append(ttft_s)
            ttft_slo.record(*((100, 0) if ttft_s <= TTFT_SLO_S else (0, 100)))
            reg.evaluate_all()
            ctl.reconcile_once()
            clk[0] += 1.0
        reroles = [a for a in act.applied if a[0] == "set_role"]
        ttft_p90_after = p90(ttfts[-10:])
        reroles_ok = (len(reroles) >= 1 and ttft_p90_after <= TTFT_SLO_S
                      and ttft_slo.alert_severity is None)

        # -- scenario 2: 4x index ramp → shard scale-up --------------------
        clk[0] += 100.0
        shards = ["shard-0"]
        reg2 = SLORegistry(clock=clock)
        score_slo = reg2.add(SLOConfig(
            name="score_latency", objective=0.99,
            fast_windows=(5.0, 10.0), slow_window=15.0))
        src2 = CollectorSignalSource(
            slo_registry=reg2, shards=lambda: list(shards),
            roles=lambda: {}, clock=clock)
        move_fracs = []

        def add_shard(target):
            old = HashRing(shards)
            shards.append(target)
            new = HashRing(shards)
            frac = moved_partitions(old, new) / new.partitions
            move_fracs.append(frac)
            return {"joined": target, "moved_fraction": round(frac, 4)}

        act2 = InProcessActuator(
            add_shard=add_shard,
            remove_shard=lambda t: shards.remove(t),
            drain_pod=lambda t: {"ok": True})
        ctl2 = FleetController(
            src2, act2,
            config=ControllerConfig(confirm_rounds=2, shard_cooldown_s=4.0,
                                    max_shards=8),
            clock=clock)
        SCORE_MS_PER_X, SCORE_SLO_MS = 2.0, 4.0
        score_p99 = 0.0
        for rnd in range(50):
            index_x = 1.0 + 3.0 * min(1.0, rnd / 20.0)  # 1x → 4x ramp
            score_p99 = SCORE_MS_PER_X * index_x / max(len(shards), 1)
            score_slo.record(
                *((100, 0) if score_p99 <= SCORE_SLO_MS else (0, 100)))
            reg2.evaluate_all()
            ctl2.reconcile_once()
            clk[0] += 1.0
        scaleup_ok = (len(shards) >= 2 and score_p99 <= SCORE_SLO_MS
                      and all(f <= 2.0 / len(shards) for f in move_fracs))

        # -- scenario 3: flap injection → bounded actions ------------------
        clk[0] += 100.0
        shards3 = ["shard-0"]
        reg3 = SLORegistry(clock=clock)
        flap_slo = reg3.add(SLOConfig(
            name="score_latency", objective=0.99,
            fast_windows=(3.0, 6.0), slow_window=10.0))
        src3 = CollectorSignalSource(
            slo_registry=reg3, shards=lambda: list(shards3),
            roles=lambda: {}, clock=clock)
        act3 = InProcessActuator(
            add_shard=lambda t: shards3.append(t),
            remove_shard=lambda t: shards3.remove(t),
            drain_pod=lambda t: {"ok": True})
        ctl3 = FleetController(
            src3, act3,
            config=ControllerConfig(confirm_rounds=1, shard_cooldown_s=5.0,
                                    max_shards=8),
            clock=clock)
        for rnd in range(40):
            # Oscillate the instantaneous burn around the act band (1.0):
            # 1.5x on even rounds, 0.8x on odd — without hysteresis this
            # would act every other round.
            bad = 15 if rnd % 2 == 0 else 8
            flap_slo.record(1000 - bad, bad)
            reg3.evaluate_all()
            ctl3.reconcile_once()
            clk[0] += 1.0
        flap_actions = len(act3.applied)
        flap_ok = flap_actions <= 2

        executed_total = len(act.applied) + len(act2.applied) + len(act3.applied)
        action_spans = exporter.find("llm_d.kv_cache.control.action")
        spans_ok = (
            len([s for s in action_spans if s.attributes.get("signal")])
            >= executed_total > 0)

    detail = {
        "reroles": {
            "actions": len(reroles),
            "ttft_p90_after_s": round(ttft_p90_after, 3),
            "ttft_slo_s": TTFT_SLO_S,
            "alert_cleared": ttft_slo.alert_severity is None,
            "ok": reroles_ok,
        },
        "scaleup": {
            "final_shards": len(shards),
            "score_p99_ms": round(score_p99, 3),
            "score_slo_ms": SCORE_SLO_MS,
            "max_moved_fraction": round(max(move_fracs), 4) if move_fracs else 0.0,
            "ok": scaleup_ok,
        },
        "flap": {
            "executed_actions": flap_actions,
            "rounds": 40,
            "ok": flap_ok,
        },
        "action_spans_with_signal": spans_ok,
    }
    return {
        "metric": "fleet controller chaos arm "
                  "(flap-injection executed actions; re-role + shard-ramp "
                  "gates)",
        "value": flap_actions,
        "unit": "actions",
        "vs_baseline": 1,
        "gate_ok": bool(reroles_ok and scaleup_ok and flap_ok and spans_ok),
        "detail": detail,
    }


def _dispatch(argv: list) -> object:
    """CLI mode → result dict. No mode names the routing benchmark's
    default: it needs a TPU and fails without one."""
    if "--ttft-load" in argv:
        return main(queued=True)
    if "--ttft" in argv:
        return main()
    if "--index" in argv:
        return bench_index_add()
    if "--offload" in argv:
        return bench_offload_throughput()
    if "--decode-hybrid" in argv:
        return bench_decode_throughput(hybrid=True)
    if "--decode" in argv:
        return bench_decode_throughput()
    if "--ragged" in argv:
        return bench_ragged()
    if "--fp8-bandwidth" in argv:
        return bench_fp8_bandwidth()
    if "--events" in argv:
        return bench_event_ingestion()
    if "--fleet-telemetry" in argv:
        return bench_fleet_telemetry()
    if "--pyprof-overhead" in argv:
        return bench_pyprof_overhead()
    if "--workingset" in argv:
        return bench_workingset()
    if "--audit" in argv:
        return bench_audit()
    if "--fencing" in argv:
        return bench_fencing()
    if "--incident" in argv:
        return bench_incident()
    if "--flight-recorder" in argv:
        return bench_flight_recorder()
    if "--snapshot-overhead" in argv:
        return bench_snapshot_overhead()
    if "--engine-telemetry" in argv:
        return bench_engine_telemetry()
    if "--disagg" in argv:
        return bench_disagg()
    if "--controller" in argv:
        return bench_controller()
    if "--graytail" in argv:
        return bench_graytail()
    if "--shards" in argv:
        i = argv.index("--shards")
        n = 4
        if i + 1 < len(argv):
            try:
                n = int(argv[i + 1])
            except ValueError:
                pass
        return bench_shard_fanout(shards=n)
    return main()


if __name__ == "__main__":
    import contextlib
    import sys

    # The result JSON must be the single LAST stdout line, with nothing
    # after it. Benchmark code and the libraries it imports occasionally
    # write to stdout, so the whole run executes with stdout aliased to
    # stderr; only the final line touches the real stream. The work runs
    # in this process: whoever imports JAX holds the chip, so there are no
    # children.
    _real_stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        _result = _dispatch(sys.argv)
    print(json.dumps(_result), file=_real_stdout, flush=True)
