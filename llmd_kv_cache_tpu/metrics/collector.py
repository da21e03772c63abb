"""Prometheus collectors for the KV-block index.

Counterpart of reference ``pkg/kvcache/metrics/collector.go:29-93``: the same
metric families (``kvcache_index_admissions_total`` etc.) on the default
prometheus_client registry, plus an optional periodic "metrics beat" log line
(``collector.go:97-165``).
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Dict, Iterable, Optional, Sequence, Tuple

from prometheus_client import REGISTRY, Counter, Gauge, Histogram

from ..utils.lockdep import new_lock
from ..utils.logging import get_logger

logger = get_logger("metrics")

_NS = "kvcache_index"

INDEX_ADMISSIONS = Counter(f"{_NS}_admissions_total", "Block keys admitted to the index")
INDEX_EVICTIONS = Counter(f"{_NS}_evictions_total", "Block keys evicted from the index")
INDEX_LOOKUP_REQUESTS = Counter(f"{_NS}_lookup_requests_total", "Index lookups served")
INDEX_LOOKUP_HITS = Counter(f"{_NS}_lookup_hits_total", "Block keys found during lookups")
# Accumulates the best per-pod hit count of each lookup, matching the
# reference's counter semantics (collector.go:43-44). Hits are counted at
# any position, not only the consecutive prefix.
INDEX_MAX_POD_HIT_COUNT = Counter(
    f"{_NS}_max_pod_hit_count",
    "Sum over lookups of the highest per-pod block hit count (any position)",
)
INDEX_LOOKUP_LATENCY = Histogram(
    f"{_NS}_lookup_latency_seconds",
    "Index lookup latency",
    buckets=(1e-5, 1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0),
)

# Score-path hot-loop families (docs/architecture.md "Score-path
# performance"): the prefix-key cache and batched event ingestion are
# invisible in the index families above, so they get their own counters.
PREFIX_CACHE_HIT_BLOCKS = Counter(
    f"{_NS}_prefix_cache_hit_blocks_total",
    "Block keys served from the token-processor prefix cache",
)
PREFIX_CACHE_MISS_BLOCKS = Counter(
    f"{_NS}_prefix_cache_miss_blocks_total",
    "Block keys hashed because the prefix cache had no covering prefix",
)
EVENT_INGEST_BATCHES = Counter(
    "kvcache_event_ingest_batches_total",
    "Worker drain batches processed by the event pool",
)
EVENT_INGEST_MESSAGES = Counter(
    "kvcache_event_ingest_messages_total",
    "Raw event messages ingested by the event pool",
)
EVENT_INGEST_COALESCED_OPS = Counter(
    "kvcache_event_ingest_coalesced_ops_total",
    "Index write calls saved by coalescing consecutive same-pod digests",
)


def record_prefix_cache_delta(hit_blocks: int, miss_blocks: int) -> None:
    if hit_blocks > 0:
        PREFIX_CACHE_HIT_BLOCKS.inc(hit_blocks)
    if miss_blocks > 0:
        PREFIX_CACHE_MISS_BLOCKS.inc(miss_blocks)


def record_ingest_batch(messages: int, coalesced_ops: int) -> None:
    EVENT_INGEST_BATCHES.inc()
    if messages > 0:
        EVENT_INGEST_MESSAGES.inc(messages)
    if coalesced_ops > 0:
        EVENT_INGEST_COALESCED_OPS.inc(coalesced_ops)


# Native data-plane families (docs/architecture.md "Native data plane"):
# zero-copy ingest batches bypassing the per-event Python decode, the
# shared-memory ring that bypasses ZMQ entirely, and the chunk/early-exit
# accounting of the fused native score path.
INGEST_ZEROCOPY_BATCHES = Counter(
    "kvtpu_ingest_zerocopy_batches_total",
    "Packed event batches decoded as memoryview-sliced key arrays "
    "(no per-key Python objects) and fed straight to the index",
)
INGEST_SHM_MESSAGES = Counter(
    "kvtpu_ingest_shm_messages_total",
    "Event messages consumed from the same-host shared-memory ring",
)
NATIVE_SCORE_CHUNKS = Counter(
    "kvtpu_native_score_chunks_total",
    "Chunks scanned by the fused native chunked-score path",
)
NATIVE_SCORE_EARLY_EXITS = Counter(
    "kvtpu_native_score_early_exits_total",
    "Fused native chunked scores that stopped before the last key "
    "(prefix chain broke mid-prompt)",
)
SHARD_BATCH_RPCS = Counter(
    "kvtpu_shard_batch_rpcs_total",
    "Framed multi-chunk LookupBlocks fan-out RPCs by outcome "
    "(batched = native frame, fallback = legacy per-chunk replay)",
    ["outcome"],
)


def record_zerocopy_batch(shm: bool = False) -> None:
    INGEST_ZEROCOPY_BATCHES.inc()
    if shm:
        INGEST_SHM_MESSAGES.inc()


def record_shm_messages(count: int) -> None:
    if count > 0:
        INGEST_SHM_MESSAGES.inc(count)


def record_native_score(chunks: int, early_exited: int) -> None:
    if chunks > 0:
        NATIVE_SCORE_CHUNKS.inc(chunks)
    if early_exited:
        NATIVE_SCORE_EARLY_EXITS.inc()


def record_batch_rpc(outcome: str) -> None:
    SHARD_BATCH_RPCS.labels(outcome).inc()


# Event-pipeline lag & staleness (ISSUE 3): the paper's "near-real-time
# global view" claim is only checkable if the publish→ingest delay and
# per-pod sequence gaps are first-class metrics. Lag is measured as
# ingest-time minus the engine's batch timestamp (clock-skew caveat in
# docs/observability.md); sequence gaps count messages provably lost on
# the PUB/SUB hop (ZMQ drops, not reorders, within one publisher).
EVENT_LAG = Histogram(
    "kvcache_event_lag_seconds",
    "Publish-timestamp to ingest delay of event batches",
    buckets=(1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0, 30.0),
)
EVENT_POD_LAG = Gauge(
    "kvcache_event_pod_lag_seconds",
    "Most recent publish-to-ingest delay per pod",
    ["pod"],
)
EVENT_SEQ_GAPS = Counter(
    "kvcache_event_seq_gaps_total",
    "Event messages lost per pod (holes in the per-topic sequence)",
    ["pod"],
)
EVENT_QUEUE_DEPTH = Gauge(
    "kvcache_event_queue_depth",
    "Queued raw messages per event-pool shard",
    ["shard"],
)
INDEX_STALENESS = Gauge(
    "kvcache_index_staleness_seconds",
    "Upper-bound age of the index's view of the slowest live pod",
)


def record_event_lag(pod: str, lag_s: float, seq_gap: int) -> None:
    EVENT_LAG.observe(lag_s)
    EVENT_POD_LAG.labels(pod).set(lag_s)
    if seq_gap > 0:
        EVENT_SEQ_GAPS.labels(pod).inc(seq_gap)


TOKENIZATION_LATENCY = Histogram(
    "kvcache_tokenization_latency_seconds",
    "Tokenization / render latency",
    buckets=(1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0),
)

# Offload data-plane metrics, labelled by medium and direction — the
# counterpart of the reference's vllm:kv_offload_{total_bytes,total_time}
# per-medium families (llmd_fs_backend/README.md:204-218, metrics.py).
OFFLOAD_BYTES = Counter(
    "kv_offload_total_bytes",
    "Bytes moved by offload transfers",
    ["medium", "direction"],
)
OFFLOAD_SECONDS = Counter(
    "kv_offload_total_time_seconds",
    "Wall time of completed offload jobs",
    ["medium", "direction"],
)
OFFLOAD_JOBS = Counter(
    "kv_offload_jobs_total",
    "Completed offload jobs",
    ["medium", "direction", "outcome"],  # outcome: success|failure
)
OFFLOAD_SHED_BLOCKS = Counter(
    "kv_offload_shed_blocks_total",
    "Store blocks dropped by write shedding",
    ["medium"],
)

# Admission-to-first-schedule delay: a request waits in the queue behind
# the step in flight and older prefills before the scheduler first picks
# it up; the CoDel shedder (resilience.shedding) acts on it. Observed at the
# request's first scheduling visit, BEFORE any deferred storage restore:
# restore time is a storage-tier cost tracked by the kv_offload_* families,
# not a scheduling wait.
ENGINE_ADMISSION_DELAY = Histogram(
    "kvcache_engine_admission_delay_seconds",
    "enqueue() to first scheduler pick (excludes any deferred "
    "storage-restore wait that follows)",
    buckets=(1e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0),
)


def record_admission_delay(seconds: float) -> None:
    ENGINE_ADMISSION_DELAY.observe(max(seconds, 0.0))


# I/O pool placement: operators verify NUMA pinning and the engaged
# transfer path from metrics instead of shelling into the pod.
IO_POOL_NUMA_NODE = Gauge(
    "kv_offload_io_numa_node",
    "Resolved accelerator host NUMA node (-1 = unknown/disabled)",
)
IO_POOL_PINNED_STAGING = Gauge(
    "kv_offload_io_pinned_staging_workers",
    "I/O workers whose staging buffer is mlock'd",
)
IO_POOL_DIRECT_TRANSFERS = Gauge(
    "kv_offload_io_direct_transfers_total",
    "Transfers that took the O_DIRECT staged path",
)


def record_io_pool_placement(engine) -> None:
    """Snapshot a NativeIOEngine's placement/transfer-path gauges."""
    IO_POOL_NUMA_NODE.set(engine.numa_node())
    IO_POOL_PINNED_STAGING.set(engine.pinned_staging_workers())
    IO_POOL_DIRECT_TRANSFERS.set(engine.direct_transfers())


def record_offload_result(medium: str, result) -> None:
    """Record a TransferResult into the offload metric families."""
    direction = "store" if result.is_store else "load"
    outcome = "success" if result.success else "failure"
    OFFLOAD_JOBS.labels(medium, direction, outcome).inc()
    OFFLOAD_BYTES.labels(medium, direction).inc(result.bytes_transferred)
    OFFLOAD_SECONDS.labels(medium, direction).inc(max(result.seconds, 0.0))
    if result.shed_hashes:
        OFFLOAD_SHED_BLOCKS.labels(medium).inc(len(result.shed_hashes))


# Crash-tolerant state (recovery/): snapshot, journal replay, anti-entropy
# and drain outcomes, plus the bounded-queue overflow counter — the signals
# the docs/resilience.md "Crash recovery & drain" runbook keys off.
EVENT_DROPPED = Counter(
    "kvcache_event_dropped_events_total",
    "Raw event messages dropped by the bounded shard queues (drop-oldest)",
    ["shard"],
)
RECOVERY_SNAPSHOTS = Counter(
    "kvcache_recovery_snapshots_total",
    "Index snapshot attempts",
    ["outcome"],  # written|failed
)
RECOVERY_SNAPSHOT_BYTES = Gauge(
    "kvcache_recovery_snapshot_bytes",
    "Size of the most recent index snapshot",
)
RECOVERY_SNAPSHOT_SECONDS = Histogram(
    "kvcache_recovery_snapshot_persist_seconds",
    "Dump + encode + durable-publish time of index snapshots",
    buckets=(1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0),
)
RECOVERY_QUARANTINED = Counter(
    "kvcache_recovery_snapshots_quarantined_total",
    "Snapshots that failed verification and were quarantined",
)
RECOVERY_RESTORED_ENTRIES = Gauge(
    "kvcache_recovery_restored_entries",
    "Index entries restored from the snapshot at the last warm restart",
)
RECOVERY_REPLAYED_RECORDS = Gauge(
    "kvcache_recovery_replayed_records",
    "Journal records replayed at the last warm restart",
)
RECONCILE_RUNS = Counter(
    "kvcache_recovery_reconcile_runs_total",
    "Anti-entropy digest-exchange rounds",
    ["outcome"],  # clean|divergent
)
RECONCILE_REPAIRED = Counter(
    "kvcache_recovery_reconcile_repaired_total",
    "Index entries repaired by anti-entropy reconciliation",
    ["direction"],  # added|removed
)
DRAIN_SECONDS = Gauge(
    "kvcache_recovery_drain_seconds",
    "Wall time of the last graceful drain",
)


def record_dropped_events(shard: int, count: int) -> None:
    if count > 0:
        EVENT_DROPPED.labels(str(shard)).inc(count)


def record_snapshot(outcome: str, size_bytes: int, seconds: float) -> None:
    RECOVERY_SNAPSHOTS.labels(outcome).inc()
    if outcome == "written":
        RECOVERY_SNAPSHOT_BYTES.set(size_bytes)
        RECOVERY_SNAPSHOT_SECONDS.observe(max(seconds, 0.0))


def record_snapshot_quarantine() -> None:
    RECOVERY_QUARANTINED.inc()


def record_warm_restart(restored_entries: int, replayed_records: int) -> None:
    RECOVERY_RESTORED_ENTRIES.set(restored_entries)
    RECOVERY_REPLAYED_RECORDS.set(replayed_records)


def record_reconcile(added: int, removed: int) -> None:
    RECONCILE_RUNS.labels("divergent" if (added or removed) else "clean").inc()
    if added > 0:
        RECONCILE_REPAIRED.labels("added").inc(added)
    if removed > 0:
        RECONCILE_REPAIRED.labels("removed").inc(removed)


def record_drain(seconds: float) -> None:
    DRAIN_SECONDS.set(max(seconds, 0.0))


# --------------------------------------------------------------------------
# BucketHistogram: a histogram primitive with runtime-configurable buckets.
#
# prometheus_client Histograms fix their buckets at module import, which is
# wrong for serving-latency families (TTFT/ITL/TPOT) whose useful resolution
# depends on the deployment (CPU dev loop vs. a v5e pod differ by 100x).
# BucketHistogram takes its buckets from config at construction, supports a
# quantile readback (kvdiag phase percentiles — prometheus_client has no
# read API), and is exported through a single custom collector on the
# default registry so it appears in ``generate_latest()`` exactly like the
# native families. ``observe()`` is allocation-free after construction: one
# bisect into a preallocated bounds tuple plus three stores under a lock.
# --------------------------------------------------------------------------


class BucketHistogram:
    __slots__ = (
        "name",
        "documentation",
        "bounds",
        "_counts",
        "_sum",
        "_count",
        "_exemplars",
        "_lock",
    )

    def __init__(self, name: str, documentation: str, buckets: Sequence[float]):
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds:
            raise ValueError("BucketHistogram needs at least one bucket bound")
        self.name = name
        self.documentation = documentation
        self.bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # per-bucket, +inf last
        self._sum = 0.0
        self._count = 0
        # Per-bucket last exemplar: (trace_id_hex, value, unix_ts) or None.
        # Keeping only the latest per bucket bounds memory and matches the
        # OpenMetrics intent: link a bucket to *a* representative trace.
        self._exemplars: list = [None] * (len(bounds) + 1)
        self._lock = new_lock()

    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        idx = bisect_left(self.bounds, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1
            if trace_id:
                self._exemplars[idx] = (trace_id, float(value), time.time())

    def exemplars(self) -> list:
        """Per-bucket ``(trace_id, value, timestamp) | None``, +Inf last."""
        with self._lock:
            return list(self._exemplars)

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def percentile(self, q: float) -> float:
        """Estimate the q-quantile (q in [0, 1]) from bucket boundaries.

        Linear interpolation inside the containing bucket; the open-ended
        +inf bucket reports its lower bound (the estimate saturates there).
        Returns 0.0 when empty.
        """
        with self._lock:
            total = self._count
            counts = list(self._counts)
        if total <= 0:
            return 0.0
        target = max(q, 0.0) * total
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= target:
                if i == len(self.bounds):  # +inf bucket
                    return self.bounds[-1]
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                frac = (target - cum) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            cum += c
        return self.bounds[-1]

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, acc = self._count, self._sum
        cumulative, cum = [], 0
        for c in counts:
            cum += c
            cumulative.append(cum)
        les = [str(b) for b in self.bounds] + ["+Inf"]
        return {
            "count": total,
            "sum": acc,
            "buckets": dict(zip(les, cumulative)),
        }

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._count = 0
            self._exemplars = [None] * (len(self.bounds) + 1)

    def _sample_buckets(self) -> Iterable[Tuple[str, int]]:
        snap = self.snapshot()
        return list(snap["buckets"].items())


_BUCKET_HISTOGRAMS: Dict[str, BucketHistogram] = {}
_bucket_hist_lock = new_lock()
_bucket_collector_registered = False


class _BucketHistogramCollector:
    """Exports every BucketHistogram as a Prometheus histogram family.

    Buckets carry their last trace-id exemplar (when one was observed) so
    the OpenMetrics exposition (``/metrics?format=openmetrics``) renders
    ``... # {trace_id="..."} value ts`` and a bad bucket links straight to
    a retained trace in the fleet collector. The classic text format
    silently drops exemplars — that path is unchanged.
    """

    def collect(self):
        from prometheus_client.core import Exemplar, HistogramMetricFamily

        with _bucket_hist_lock:
            hists = list(_BUCKET_HISTOGRAMS.values())
        for h in hists:
            snap = h.snapshot()
            exemplars = h.exemplars()
            buckets = []
            for i, (le, cum) in enumerate(snap["buckets"].items()):
                ex = exemplars[i] if i < len(exemplars) else None
                if ex is not None:
                    trace_id, value, ts = ex
                    buckets.append(
                        (le, cum, Exemplar({"trace_id": trace_id}, value, ts))
                    )
                else:
                    buckets.append((le, cum))
            fam = HistogramMetricFamily(h.name, h.documentation)
            fam.add_metric([], buckets=buckets, sum_value=snap["sum"])
            yield fam


def bucket_histogram(
    name: str, documentation: str, buckets: Sequence[float]
) -> BucketHistogram:
    """Get-or-create a named BucketHistogram on the default registry.

    Deduped by name: several engines in one process share the instance
    (the first caller's buckets win), mirroring prometheus_client's
    process-global family semantics.
    """
    global _bucket_collector_registered
    with _bucket_hist_lock:
        hist = _BUCKET_HISTOGRAMS.get(name)
        if hist is None:
            hist = BucketHistogram(name, documentation, buckets)
            _BUCKET_HISTOGRAMS[name] = hist
        register_now = not _bucket_collector_registered
        _bucket_collector_registered = True
    if register_now:
        # Outside the lock: REGISTRY.register() calls collect(), which
        # takes _bucket_hist_lock itself.
        REGISTRY.register(_BucketHistogramCollector())
    return hist


def forget_bucket_histograms(*names: str) -> None:
    """Drop the named BucketHistograms (all of them when none is named), so
    that the next ``bucket_histogram()`` of a name starts an empty family.
    For a process that builds one fleet after another — a test session —
    and must not hand the later one the earlier one's observations; holders
    of a dropped instance keep observing into it, unexported."""
    with _bucket_hist_lock:
        for name in names or list(_BUCKET_HISTOGRAMS):
            _BUCKET_HISTOGRAMS.pop(name, None)


# --------------------------------------------------------------------------
# Engine data-plane families (kvtpu_engine_*): KV-pool occupancy, restore
# outcomes, and request lifecycle counters for the TPU serving engine.
# TTFT/ITL/TPOT are BucketHistograms created by telemetry/engine_telemetry.py
# because their buckets are config-driven; the fixed-shape families live
# here with the rest of the registry.
# --------------------------------------------------------------------------

ENGINE_POOL_FREE_PAGES = Gauge(
    "kvtpu_engine_kv_pool_free_pages",
    "Free pages in the engine KV pool",
    ["group"],
)
ENGINE_POOL_CACHED_BLOCKS = Gauge(
    "kvtpu_engine_kv_pool_cached_blocks",
    "Hashed prefix blocks resident in the engine KV pool",
    ["group"],
)
ENGINE_POOL_ORPHAN_PAGES = Gauge(
    "kvtpu_engine_kv_pool_orphan_pages",
    "Pages held by in-flight requests, not yet hashed into reusable blocks",
    ["group"],
)
ENGINE_POOL_EVICTIONS = Counter(
    "kvtpu_engine_kv_pool_evictions_total",
    "Cached blocks evicted from the engine KV pool to free pages",
    ["group"],
)
ENGINE_RESTORE_JOBS = Counter(
    "kvtpu_engine_restore_jobs_total",
    "Storage-tier KV restore attempts by outcome",
    ["outcome"],  # success|failure|timeout
)
ENGINE_RESTORE_LATENCY = Histogram(
    "kvtpu_engine_restore_latency_seconds",
    "Deferred storage-restore wall time (job start to commit/abandon)",
    buckets=(1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0, 30.0),
)
ENGINE_PREFIX_HIT_BLOCKS = Counter(
    "kvtpu_engine_prefix_hit_blocks_total",
    "HBM-resident prefix blocks reused at request admission",
)
ENGINE_REQUESTS = Counter(
    "kvtpu_engine_requests_total",
    "Requests finished by the engine",
    ["outcome"],  # finished|aborted
)
ENGINE_DECODE_STEPS = Counter(
    "kvtpu_engine_decode_steps_total",
    "Engine step() calls that decoded at least one token",
)
ENGINE_PROFILE_CAPTURES = Counter(
    "kvtpu_engine_profile_captures_total",
    "On-demand jax.profiler captures by outcome",
    ["outcome"],  # success|failure
)
# Padding-waste pair (EngineTelemetry.on_dispatch_tokens): every device
# dispatch reports its real token count against the padded program size —
# the ragged single-kernel path and the padded two-kernel fallback feed
# the same counters, so rate(padded - real) is the padding-FLOP burn and
# the ratio compares the two schedulers directly.
ENGINE_RAGGED_REAL_TOKENS = Counter(
    "kvtpu_engine_ragged_real_tokens_total",
    "Real (non-padding) tokens dispatched by the engine step path",
    ["group"],
)
ENGINE_RAGGED_PADDED_TOKENS = Counter(
    "kvtpu_engine_ragged_padded_tokens_total",
    "Total padded program tokens dispatched by the engine step path",
    ["group"],
)


def record_engine_restore(outcome: str, seconds: Optional[float] = None) -> None:
    ENGINE_RESTORE_JOBS.labels(outcome).inc()
    if seconds is not None:
        ENGINE_RESTORE_LATENCY.observe(max(seconds, 0.0))


def record_profile_capture(outcome: str) -> None:
    ENGINE_PROFILE_CAPTURES.labels(outcome).inc()


def record_ragged_dispatch(group: str, real: int, padded: int) -> None:
    ENGINE_RAGGED_REAL_TOKENS.labels(group).inc(max(real, 0))
    ENGINE_RAGGED_PADDED_TOKENS.labels(group).inc(max(padded, 0))


# --------------------------------------------------------------------------
# Sharded control-plane families (kvtpu_shard_*): the scatter-gather
# router's fan-out latency, per-shard RPC outcomes, degraded lookups,
# the consistent-hash ring's primary-partition balance, and the ring-plan
# prefix cache (docs/architecture.md "Sharded control plane").
# --------------------------------------------------------------------------

SHARD_FANOUT_LATENCY = Histogram(
    "kvtpu_shard_fanout_latency_seconds",
    "Scatter-gather score latency (keys to merged scores, all shards)",
    buckets=(1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0),
)
SHARD_RPCS = Counter(
    "kvtpu_shard_rpcs_total",
    "LookupBlocks RPCs issued by the router, per shard and outcome",
    ["shard", "outcome"],  # outcome: success|failure|skipped (breaker open)
)
SHARD_DEGRADED_LOOKUPS = Counter(
    "kvtpu_shard_degraded_lookups_total",
    "Score calls that served with at least one unreachable shard",
)
SHARD_RING_PARTITIONS = Gauge(
    "kvtpu_shard_ring_partitions",
    "Primary partitions assigned per shard by the consistent-hash ring",
    ["shard"],
)
SHARD_PLAN_CACHE = Counter(
    "kvtpu_shard_plan_cache_total",
    "Ring-plan prefix-cache lookups by outcome",
    ["outcome"],  # hit|miss
)


def record_shard_fanout(seconds: float) -> None:
    SHARD_FANOUT_LATENCY.observe(max(seconds, 0.0))


def record_shard_rpc(shard: str, outcome: str) -> None:
    SHARD_RPCS.labels(shard, outcome).inc()


def record_shard_degraded_lookup(shards: int) -> None:
    if shards > 0:
        SHARD_DEGRADED_LOOKUPS.inc()


def record_shard_plan_cache(hit: bool) -> None:
    SHARD_PLAN_CACHE.labels("hit" if hit else "miss").inc()


def record_ring_load(load: Dict[str, int]) -> None:
    for shard, partitions in load.items():
        SHARD_RING_PARTITIONS.labels(shard).set(partitions)


# --------------------------------------------------------------------------
# Gray-failure tolerance families (kvtpu_hedge_*, kvtpu_shed_*): hedged
# scatter-gather outcomes and adaptive overload-shed decisions
# (docs/resilience.md "Gray failures, deadlines & overload"). Hedge
# outcomes: issued (hedge RPC sent), win (hedge answered first with fresh
# keys), loss (primary answered first, hedge cancelled), failed (hedge
# itself errored), denied (budget exhausted — no hedge sent). Shed
# outcomes: shed (rejected outright), brownout (served degraded),
# deadline (budget already expired at entry), late (served past its
# deadline, flagged degraded), restore_skip (storage restore skipped for
# deadline, recompute instead).
# --------------------------------------------------------------------------

HEDGE_ATTEMPTS = Counter(
    "kvtpu_hedge_attempts_total",
    "Hedged shard-RPC decisions by shard and outcome",
    ["shard", "outcome"],  # issued|win|loss|failed|denied
)
SHED_DECISIONS = Counter(
    "kvtpu_shed_decisions_total",
    "Overload-shed and deadline decisions by site and outcome",
    ["site", "outcome"],  # shed|brownout|deadline|late|restore_skip
)


def record_hedge(shard: str, outcome: str) -> None:
    HEDGE_ATTEMPTS.labels(shard, outcome).inc()


def record_shed(site: str, outcome: str) -> None:
    SHED_DECISIONS.labels(site, outcome).inc()


# --------------------------------------------------------------------------
# Disaggregated-handoff families (kvtpu_handoff_*): prefill→decode KV
# transfers over the offload plane — queue depth, in-flight store jobs,
# per-chunk outcomes, and end-to-end handoff latency (prefill begin to the
# decode pod holding every transferable block). Fed by
# offload.handoff.HandoffCoordinator; kvdiag's ``handoff`` section and the
# docs/architecture.md "Prefill/decode disaggregation" runbook read them.
# --------------------------------------------------------------------------

HANDOFF_QUEUE_DEPTH = Gauge(
    "kvtpu_handoff_transfer_queue_depth",
    "Active prefill-to-decode handoffs not yet completed or failed",
)
HANDOFF_IN_FLIGHT_JOBS = Gauge(
    "kvtpu_handoff_in_flight_jobs",
    "Handoff store jobs issued to the offload plane and not yet landed",
)
HANDOFF_LATENCY = Histogram(
    "kvtpu_handoff_latency_seconds",
    "Prefill-begin to decode-resident handoff wall time",
    buckets=(1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0, 30.0),
)
HANDOFF_CHUNKS = Counter(
    "kvtpu_handoff_chunks_total",
    "Per-chunk handoff transfer completions by outcome",
    ["outcome"],  # landed|failed
)
HANDOFF_REQUESTS = Counter(
    "kvtpu_handoff_requests_total",
    "Handoff requests by terminal outcome",
    ["outcome"],  # complete|failed|timeout|fallback
)


def record_handoff_gauges(queue_depth: int, in_flight_jobs: int) -> None:
    HANDOFF_QUEUE_DEPTH.set(max(queue_depth, 0))
    HANDOFF_IN_FLIGHT_JOBS.set(max(in_flight_jobs, 0))


def record_handoff_chunk(outcome: str) -> None:
    HANDOFF_CHUNKS.labels(outcome).inc()


def record_handoff_request(outcome: str, seconds: Optional[float] = None) -> None:
    HANDOFF_REQUESTS.labels(outcome).inc()
    if seconds is not None:
        HANDOFF_LATENCY.observe(max(seconds, 0.0))


# --------------------------------------------------------------------------
# Fleet observability (kvtpu_trace_*): local span-export health. The ring
# exporter (telemetry/tracing.py) evicts oldest spans once full; every
# eviction lands here so a collector whose pull cursor lags the ring can
# tell "no spans" apart from "spans dropped before I pulled".
# --------------------------------------------------------------------------

TRACE_DROPPED_SPANS = Counter(
    "kvtpu_trace_dropped_spans_total",
    "Finished spans evicted from the in-memory ring exporter before export",
)
TRACE_EXPORTED_SPANS = Counter(
    "kvtpu_trace_exported_spans_total",
    "Finished spans handed to remote pullers via /debug/spans",
)


def record_spans_exported(count: int) -> None:
    if count > 0:
        TRACE_EXPORTED_SPANS.inc(count)


# --------------------------------------------------------------------------
# Continuous profiling (kvtpu_pyprof_*): the always-on sampling profiler
# (telemetry/sampling_profiler.py). samples/overhead are the self-measured
# cost ledger — rate(overhead)/1s is the live CPU fraction the sampler
# steals, gated <1% by ``bench.py --pyprof-overhead``; dropped windows mean
# the collector's /debug/pyprof cursor is lagging the export ring.
# --------------------------------------------------------------------------

PYPROF_SAMPLES = Counter(
    "kvtpu_pyprof_samples_total",
    "Thread-stack samples folded by the sampling profiler",
)
PYPROF_OVERHEAD_SECONDS = Counter(
    "kvtpu_pyprof_overhead_seconds_total",
    "Wall time spent inside sampling-profiler passes (self-measured)",
)
PYPROF_WINDOWS_DROPPED = Counter(
    "kvtpu_pyprof_windows_dropped_total",
    "Sealed profile windows evicted before any /debug/pyprof pull",
)
PYPROF_TRIE_NODES = Gauge(
    "kvtpu_pyprof_trie_nodes",
    "Interned stack-trie nodes in the live (unsealed) profile window",
)


# --------------------------------------------------------------------------
# Per-tier restore latency (ROADMAP item 3): the engine's storage-restore
# paths label each restore with the offload medium (SHARED_STORAGE,
# OBJECT_STORE, ...) so slow-tier restores are visible per tier — and,
# via the fleet collector's restore_latency SLI, in burn-rate alerts.
# kvtpu_engine_restore_latency_seconds stays as the tier-blind aggregate.
# --------------------------------------------------------------------------

OFFLOAD_RESTORE_SECONDS = Histogram(
    "kvtpu_offload_restore_seconds",
    "Storage-tier KV restore wall time per tier (sync + deferred paths)",
    ["tier"],
    buckets=(1e-3, 5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 5.0, 30.0),
)


def record_offload_restore(tier: str, seconds: float) -> None:
    OFFLOAD_RESTORE_SECONDS.labels(tier or "unknown").observe(
        max(seconds, 0.0))


# --------------------------------------------------------------------------
# Working-set analytics (kvtpu_workingset_*): the SHARDS-style reuse
# sampler (telemetry/workingset.py). sampled/overhead are its self-measured
# cost ledger — gated <1% of score p50 by ``bench.py --workingset``;
# tracked_blocks shows how much of the max_tracked_blocks budget the
# sampled working set occupies; dropped windows mean the collector's
# /debug/workingset cursor is lagging the export ring.
# --------------------------------------------------------------------------

WORKINGSET_SAMPLED_TOTAL = Counter(
    "kvtpu_workingset_sampled_accesses_total",
    "Block accesses that passed the working-set spatial sampling filter",
)
WORKINGSET_OVERHEAD_SECONDS = Counter(
    "kvtpu_workingset_overhead_seconds_total",
    "Wall time spent inside working-set tracker hooks (self-measured)",
)
WORKINGSET_TRACKED_BLOCKS = Gauge(
    "kvtpu_workingset_tracked_blocks",
    "Sampled block keys currently tracked for reuse distances (all scopes)",
)
WORKINGSET_WINDOWS_DROPPED = Counter(
    "kvtpu_workingset_windows_dropped_total",
    "Sealed working-set windows evicted before any /debug/workingset pull",
)


# --------------------------------------------------------------------------
# Ground-truth audit plane (kvtpu_audit_*): score-vs-reality calibration.
# The collector's AuditJoiner (telemetry/audit.py) joins score-time
# predictions to engine-realized outcomes per trace and lands the
# per-request error here; the calibration curves themselves are
# exemplar-linked BucketHistograms the joiner constructs
# (kvtpu_audit_predicted_hit_blocks / _realized_hit_blocks /
# _calibration_error_blocks). ``cause`` attributes mispredicted blocks to
# the index staleness observed at score time: "stale" (event lag above
# the configured threshold — the index hadn't caught up yet) vs "fresh"
# (the view was current and still wrong — look at torn restores or
# reconcile lag instead; docs/observability.md "Divergence triage").
# --------------------------------------------------------------------------

AUDIT_JOINED = Counter(
    "kvtpu_audit_joined_total",
    "Prediction/outcome pairs joined by the collector audit leg",
    ["pod"],
)
AUDIT_MISPREDICTED_BLOCKS = Counter(
    "kvtpu_audit_mispredicted_blocks_total",
    "Abs(predicted - realized) hit blocks, attributed by score-time staleness",
    ["pod", "cause"],  # stale|fresh
)
AUDIT_REGRETS = Counter(
    "kvtpu_audit_regret_total",
    "Joined requests where another pod's calibrated prediction beat the "
    "chosen pod's realized hit",
    ["pod"],  # the chosen (losing) pod
)
AUDIT_REGRET_BLOCKS = Counter(
    "kvtpu_audit_regret_blocks_total",
    "Estimated hit blocks forgone to routing regret",
    ["pod"],
)
AUDIT_DROPPED_RECORDS = Counter(
    "kvtpu_audit_dropped_records_total",
    "Audit records evicted from a pod's ring before any /debug/audit pull",
)


def record_audit_join(pod: str, error_blocks: float, cause: str) -> None:
    AUDIT_JOINED.labels(pod).inc()
    if error_blocks > 0:
        AUDIT_MISPREDICTED_BLOCKS.labels(pod, cause).inc(error_blocks)


def record_audit_regret(pod: str, blocks: float) -> None:
    AUDIT_REGRETS.labels(pod).inc()
    if blocks > 0:
        AUDIT_REGRET_BLOCKS.labels(pod).inc(blocks)


def record_audit_dropped(count: int) -> None:
    if count > 0:
        AUDIT_DROPPED_RECORDS.inc(count)


# --------------------------------------------------------------------------
# Continuous index-divergence audit (kvtpu_index_divergence_*): the
# always-on sampled XOR-digest audit (recovery.reconcile.DivergenceAuditor)
# compares each pod's indexed view against ground truth WITHOUT repairing.
# Phantom blocks: the index advertises them but the engine lacks them
# (routing overshoots — realized hits fall short of predictions). Ghost
# blocks: the engine holds them unindexed (routing undershoots — capacity
# the scorer never sees). The checked/divergent counters feed the
# ``index_divergence`` SLI burn windows in the fleet collector; the age
# histogram observes how long each divergence episode lasted when it
# healed (reconcile or natural convergence).
# --------------------------------------------------------------------------

DIVERGENCE_CHECKED = Counter(
    "kvtpu_index_divergence_checked_total",
    "Divergence-audit pod checks (one per pod per audit round)",
    ["pod"],
)
DIVERGENCE_DIVERGENT = Counter(
    "kvtpu_index_divergence_divergent_total",
    "Audit rounds where a pod's indexed view diverged from ground truth",
    ["pod"],
)
DIVERGENCE_PHANTOM_BLOCKS = Gauge(
    "kvtpu_index_divergence_phantom_blocks",
    "Blocks the index advertises on a pod that the engine lacks",
    ["pod"],
)
DIVERGENCE_GHOST_BLOCKS = Gauge(
    "kvtpu_index_divergence_ghost_blocks",
    "Blocks an engine holds that its pod's index view is missing",
    ["pod"],
)
DIVERGENCE_AGE_SECONDS = Histogram(
    "kvtpu_index_divergence_age_seconds",
    "Duration of a divergence episode at the audit round that saw it heal",
    buckets=(0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0, 1800.0),
)


def record_divergence_audit(pod: str, divergent: bool,
                            phantom: int, ghost: int) -> None:
    DIVERGENCE_CHECKED.labels(pod).inc()
    if divergent:
        DIVERGENCE_DIVERGENT.labels(pod).inc()
    DIVERGENCE_PHANTOM_BLOCKS.labels(pod).set(max(phantom, 0))
    DIVERGENCE_GHOST_BLOCKS.labels(pod).set(max(ghost, 0))


def record_divergence_healed(age_s: float) -> None:
    DIVERGENCE_AGE_SECONDS.observe(max(age_s, 0.0))


# --------------------------------------------------------------------------
# Epoch-fenced membership plane (kvtpu_fence_* / kvtpu_topology_* /
# kvtpu_lease_*): the fencing-token discipline in cluster.membership.
# Every fence decision that refuses (or would refuse, in warn mode) a
# stale actor's traffic counts here by receiving site and reason; the
# topology-epoch gauge tracks the newest epoch this process has observed
# (minted by the controller, learned by piggyback); the lease families
# track the renewable pod leases that turn "probably dead" into
# "provably fenced".
# --------------------------------------------------------------------------

FENCE_REJECTIONS = Counter(
    "kvtpu_fence_rejections_total",
    "Stale-epoch / lapsed-lease traffic refused (or flagged in warn mode)",
    ["site", "reason"],
)
TOPOLOGY_EPOCH = Gauge(
    "kvtpu_topology_epoch",
    "Newest fleet topology epoch observed by this process",
)
LEASE_ACTIVE = Gauge(
    "kvtpu_lease_active",
    "Pod leases currently within their TTL",
)
LEASE_RENEWALS = Counter(
    "kvtpu_lease_renewals_total",
    "Successful pod lease renewals",
)
LEASE_EXPIRED = Counter(
    "kvtpu_lease_expired_total",
    "Pod leases that lapsed past their TTL (zombie fence armed)",
)
LEASE_READMISSIONS = Counter(
    "kvtpu_lease_readmissions_total",
    "Lapsed pods re-admitted through the warm-restart gate",
)


def record_fence_rejection(site: str, reason: str) -> None:
    FENCE_REJECTIONS.labels(site, reason).inc()


def record_topology_epoch(epoch: int) -> None:
    TOPOLOGY_EPOCH.set(max(int(epoch), 0))


# --------------------------------------------------------------------------
# Cache-efficiency ledger export (kvtpu_cache_ledger_*): the per-pod
# appearance/win/stored/evicted attribution the Indexer already keeps
# (scoring.indexer.CacheEfficiencyLedger), exported as metric families via
# a custom collector that snapshots the ledger at scrape time — zero cost
# on the score/ingest hot paths, and the /metrics view stays consistent
# with the /debug/vars ledger snapshot.
# --------------------------------------------------------------------------


class _CacheLedgerCollector:
    """Scrape-time bridge from a CacheEfficiencyLedger to /metrics."""

    def __init__(self, snapshot_fn):
        self._snapshot = snapshot_fn

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )

        try:
            snap = self._snapshot()
        except Exception:  # pragma: no cover  # lint: allow-swallow
            return
        appearances = CounterMetricFamily(
            "kvtpu_cache_ledger_appearances_total",
            "Score results a pod appeared in (cache-efficiency ledger)",
            labels=["pod"],
        )
        wins = CounterMetricFamily(
            "kvtpu_cache_ledger_wins_total",
            "Score results a pod won (highest score) per the ledger",
            labels=["pod"],
        )
        score_total = CounterMetricFamily(
            "kvtpu_cache_ledger_score_total",
            "Cumulative weighted prefix score attributed to a pod",
            labels=["pod"],
        )
        stored = GaugeMetricFamily(
            "kvtpu_cache_ledger_stored_blocks",
            "Blocks the event stream has stored minus evicted on a pod",
            labels=["pod"],
        )
        evicted = CounterMetricFamily(
            "kvtpu_cache_ledger_evicted_blocks_total",
            "Blocks the event stream has evicted from a pod",
            labels=["pod"],
        )
        for pod, st in (snap.get("pods") or {}).items():
            appearances.add_metric([pod], st.get("appearances", 0))
            wins.add_metric([pod], st.get("wins", 0))
            score_total.add_metric([pod], st.get("score_total", 0.0))
            stored.add_metric(
                [pod],
                st.get("stored_blocks", 0) - st.get("evicted_blocks", 0))
            evicted.add_metric([pod], st.get("evicted_blocks", 0))
        yield appearances
        yield wins
        yield score_total
        yield stored
        yield evicted


_ledger_collector_lock = new_lock()
_ledger_collector: Optional[_CacheLedgerCollector] = None


def register_cache_ledger(snapshot_fn) -> None:
    """Export a ledger's snapshot() as kvtpu_cache_ledger_* families.

    Process-global and last-writer-wins (one collector instance, its
    snapshot source swapped), matching prometheus_client's process-global
    family semantics — re-registration across tests must not raise.
    """
    global _ledger_collector
    with _ledger_collector_lock:
        if _ledger_collector is None:
            _ledger_collector = _CacheLedgerCollector(snapshot_fn)
            register_now = True
        else:
            _ledger_collector._snapshot = snapshot_fn
            register_now = False
    if register_now:
        REGISTRY.register(_ledger_collector)


_beat_thread: Optional[threading.Thread] = None
_beat_stop = threading.Event()


def start_metrics_logging(interval_s: float) -> None:
    """Log a periodic one-line metrics beat. Idempotent, daemon thread."""
    global _beat_thread
    if _beat_thread is not None and _beat_thread.is_alive():
        if not _beat_stop.is_set():
            return
        # A stop was requested but the old thread hasn't exited yet; wait it
        # out so the restart below actually takes effect.
        _beat_thread.join()
    _beat_stop.clear()

    def _beat() -> None:
        while not _beat_stop.wait(interval_s):
            logger.info(
                "metrics beat: admissions=%d evictions=%d lookups=%d hits=%d",
                INDEX_ADMISSIONS._value.get(),
                INDEX_EVICTIONS._value.get(),
                INDEX_LOOKUP_REQUESTS._value.get(),
                INDEX_LOOKUP_HITS._value.get(),
            )

    _beat_thread = threading.Thread(target=_beat, name="kvtpu-metrics-beat", daemon=True)
    _beat_thread.start()


def stop_metrics_logging() -> None:
    _beat_stop.set()
