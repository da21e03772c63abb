"""Engine data-plane telemetry: request lifecycle, KV-pool gauges, profiler.

The serving engine (``models/engine.py``) is the half of the system the
paper's latency claims rest on, and before this module it emitted nothing.
``EngineTelemetry`` turns the engine's lifecycle into the three serving
histograms operators actually watch — TTFT (enqueue to first token), ITL
(inter-token latency), TPOT (time per output token) — plus KV-pool
occupancy gauges and per-request flight-recorder events, without touching
the step path's allocation budget (``bench.py --engine-telemetry`` asserts
the per-step hook cost stays under 1% of the decode-step p50).

Design constraints, in order:

- **Allocation-light on the step path.** Hooks mutate a preallocated
  ``_ReqState`` (``__slots__``), observe into :class:`BucketHistogram`
  (one bisect + three stores), and scrape pool gauges only every
  ``pool_gauge_every`` steps. No dicts are built per decode step.
- **Config-driven buckets.** TTFT on a CPU dev loop and TTFT on a v5e pod
  differ by two orders of magnitude; bucket bounds come from
  :class:`EngineTelemetryConfig` (``engineTelemetry`` in config files),
  not module constants.
- **One trace from score to serve.** The engine itself creates spans
  (gated on a request carrying a ``traceparent``); this module only keeps
  the lifecycle clock. See ``docs/observability.md``.

``ProfilerCapture`` wraps on-demand ``jax.profiler`` xplane captures for
the admin endpoint's ``/debug/profile?duration_s=N`` (guarded: requires a
configured ``profileDir``; one capture at a time).
"""

from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils.lockdep import new_lock
from ..metrics import collector
from ..utils.logging import get_logger
from . import flight_recorder as fr
from .tracing import parse_traceparent

logger = get_logger("engine_telemetry")


def trace_id_of(traceparent: Optional[str]) -> Optional[str]:
    """Hex trace id from a W3C traceparent, for histogram exemplars."""
    parsed = parse_traceparent(traceparent)
    return None if parsed is None else f"{parsed[0]:032x}"

# Default bucket bounds span CPU dev loops through TPU pods; deployments
# with tighter SLOs override them via EngineTelemetryConfig.
DEFAULT_TTFT_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
DEFAULT_ITL_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)
DEFAULT_STEP_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5, 1.0,
)

MAX_PROFILE_DURATION_S = 60.0


class ProfileInProgress(RuntimeError):
    """A jax.profiler capture is already running (admin maps this to 409)."""


def _as_buckets(value, default: Tuple[float, ...]) -> Tuple[float, ...]:
    if value is None:
        return default
    return tuple(float(v) for v in value)


@dataclass
class EngineTelemetryConfig:
    """Knobs for the engine observability layer (``engineTelemetry``)."""

    enabled: bool = True
    ttft_buckets: Tuple[float, ...] = DEFAULT_TTFT_BUCKETS
    itl_buckets: Tuple[float, ...] = DEFAULT_ITL_BUCKETS
    tpot_buckets: Tuple[float, ...] = DEFAULT_ITL_BUCKETS
    step_buckets: Tuple[float, ...] = DEFAULT_STEP_BUCKETS
    # Pool gauges are scraped once every N steps: gauge label lookups are
    # ~1us each and a tiny-model CPU decode step is sub-millisecond, so an
    # every-step scrape alone could eat the 1% overhead budget.
    pool_gauge_every: int = 16
    # One flight-recorder record per request phase transition (admit,
    # finish); decode steps never write to the ring.
    flight_records: bool = True
    # Directory for on-demand jax.profiler captures; empty disables the
    # /debug/profile endpoint.
    profile_dir: str = ""
    # Ring of per-request lifecycle summaries kept for /debug/vars.
    max_finished: int = 64

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "EngineTelemetryConfig":
        if not d:
            return cls()

        def k(camel, snake, default):
            return d.get(camel, d.get(snake, default))

        return cls(
            enabled=bool(k("enabled", "enabled", True)),
            ttft_buckets=_as_buckets(
                k("ttftBuckets", "ttft_buckets", None), DEFAULT_TTFT_BUCKETS),
            itl_buckets=_as_buckets(
                k("itlBuckets", "itl_buckets", None), DEFAULT_ITL_BUCKETS),
            tpot_buckets=_as_buckets(
                k("tpotBuckets", "tpot_buckets", None), DEFAULT_ITL_BUCKETS),
            step_buckets=_as_buckets(
                k("stepBuckets", "step_buckets", None), DEFAULT_STEP_BUCKETS),
            pool_gauge_every=int(k("poolGaugeEvery", "pool_gauge_every", 16)),
            flight_records=bool(k("flightRecords", "flight_records", True)),
            profile_dir=str(k("profileDir", "profile_dir", "")),
            max_finished=int(k("maxFinished", "max_finished", 64)),
        )


# What ``request.first_token`` and a finished request's summary both show
# of a ``_ReqState`` as it stands: counts, no clock.
_FIRST_TOKEN_COUNTS = (
    "prompt_tokens", "cached_tokens", "chunks", "first_launch",
    "last_launch", "decodes_between", "behind_chunks",
)


class _ReqState:
    """Per-request lifecycle clock. Preallocated; mutated in place.

    Every time is ``time.monotonic()``. Beside the four points of a
    request's life it holds what its first token waited for, as the engine
    saw it (``first_token_split``: what ``request.first_token`` carries and
    ``debug_vars()["requests"]["recent"]`` shows untraced).
    """

    __slots__ = (
        "request_id", "traceparent", "enqueue_ts", "admit_ts",
        "first_token_ts", "last_token_ts", "tokens", "prefix_hit_blocks",
        "sched_ts", "queued_s", "behind_s", "behind_chunks", "chunks",
        "first_launch", "last_launch", "decodes_at_sched", "decodes_between",
        "prompt_tokens", "cached_tokens",
    )

    def __init__(self, request_id: str, now: float, prefix_hit_blocks: int,
                 traceparent: Optional[str]):
        self.request_id = request_id
        self.traceparent = traceparent
        self.enqueue_ts = now   # inside admission (``on_admitted``)
        # The first pick: the start of the ``step()`` whose scheduler first
        # found this request at the head of its queue (``on_first_schedule``),
        # whether a restore or handoff gate then held it or not.
        self.admit_ts: Optional[float] = None
        self.first_token_ts: Optional[float] = None
        self.last_token_ts: Optional[float] = None
        self.tokens = 0
        self.prefix_hit_blocks = prefix_hit_blocks
        # The start of the ``step()`` that first ran a chunk of it (the
        # first pick's where no gate held it: ``on_dispatch``), and the wait
        # from the end of ``enqueue()`` to there.
        self.sched_ts: Optional[float] = None
        self.queued_s = 0.0
        # Of that wait: the engine's steps whose prefill chunk was another
        # request's (their wall time, their count). The rest is its own
        # restore or handoff gate and the caller's time between steps.
        self.behind_s = 0.0
        self.behind_chunks = 0
        # Its own prefill chunks dispatched, and the device's launch
        # ordinals (``MiniEngine._launch_input``) of the first and the last.
        self.chunks = 0
        self.first_launch = -1
        self.last_launch = -1
        # The engine's decode programs launched between ``sched_ts`` (the
        # engine's count there) and its first token; 0 until that stands.
        self.decodes_at_sched = 0
        self.decodes_between = 0
        self.prompt_tokens = 0
        self.cached_tokens = 0  # at the first token: after a restore

    def first_token_split(self) -> dict:
        """What the ``request.first_token`` phase carries: counts, and
        three durations in ns, each a difference of two readings of the
        one clock. ``queued_ns + prefill_ns`` is what the TTFT histogram
        observed less the rest of ``enqueue()`` behind ``on_admitted``."""
        return {
            "request_id": self.request_id,
            **{k: getattr(self, k) for k in _FIRST_TOKEN_COUNTS},
            "queued_ns": int(self.queued_s * 1e9),
            "behind_ns": int(self.behind_s * 1e9),
            "prefill_ns": int((self.first_token_ts - self.sched_ts) * 1e9),
        }

    def summary(self, finish_ts: float, outcome: str) -> dict:
        return {
            "request_id": self.request_id,
            "enqueue_ts": self.enqueue_ts,
            "admit_ts": self.admit_ts,
            "first_token_ts": self.first_token_ts,
            "last_token_ts": self.last_token_ts,
            "finish_ts": finish_ts,
            "tokens": self.tokens,
            "prefix_hit_blocks": self.prefix_hit_blocks,
            "traced": self.traceparent is not None,
            "outcome": outcome,
            "sched_ts": self.sched_ts,
            "queued_s": self.queued_s,
            "behind_s": self.behind_s,
            **{k: getattr(self, k) for k in _FIRST_TOKEN_COUNTS},
        }


class ProfilerCapture:
    """On-demand ``jax.profiler`` xplane capture, one at a time."""

    def __init__(self, profile_dir: str):
        self.profile_dir = profile_dir
        self._lock = new_lock()
        self.last: Optional[dict] = None

    def capture(self, duration_s: float = 1.0) -> dict:
        """Run a blocking capture; returns ``{"dir", "duration_s", ...}``.

        Raises ``ValueError`` on a bad duration, :class:`ProfileInProgress`
        when a capture is already running, and ``RuntimeError`` when the
        platform/profiler refuses (surfaced as HTTP 400/409/500 by
        ``services/admin.py``).
        """
        duration_s = float(duration_s)
        if not (0.0 < duration_s <= MAX_PROFILE_DURATION_S):
            raise ValueError(
                f"duration_s must be in (0, {MAX_PROFILE_DURATION_S}], "
                f"got {duration_s}")
        if not self.profile_dir:
            raise RuntimeError("profiler capture disabled: no profileDir configured")
        if not self._lock.acquire(blocking=False):
            raise ProfileInProgress("a profiler capture is already running")
        try:
            import jax.profiler  # deferred: telemetry imports stay jax-free

            os.makedirs(self.profile_dir, exist_ok=True)
            started = time.time()
            try:
                jax.profiler.start_trace(self.profile_dir)
                time.sleep(duration_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:
            collector.record_profile_capture("failure")
            fr.record(fr.KIND_PROFILE, {"outcome": "failure", "error": str(exc)})
            raise RuntimeError(f"jax.profiler capture failed: {exc}") from exc
        finally:
            self._lock.release()
        self.last = {
            "dir": self.profile_dir,
            "duration_s": duration_s,
            "started_ts": started,
            "completed_ts": time.time(),
        }
        collector.record_profile_capture("success")
        fr.record(fr.KIND_PROFILE, {"outcome": "success", "dir": self.profile_dir,
                                    "duration_s": duration_s})
        return dict(self.last)


class EngineTelemetry:
    """Request-lifecycle + KV-pool telemetry for one ``MiniEngine``.

    Histograms are process-global (deduped by metric name), so several
    engines in one process aggregate into the same families; per-request
    state is per-instance. The engine calls the ``on_*`` hooks; everything
    else (admin endpoint, kvdiag) reads :meth:`debug_vars`.
    """

    def __init__(self, config: Optional[EngineTelemetryConfig] = None,
                 group: str = "0"):
        self.cfg = config or EngineTelemetryConfig()
        self.group = str(group)
        self.ttft = collector.bucket_histogram(
            "kvtpu_engine_ttft_seconds",
            "Time from enqueue to first output token",
            self.cfg.ttft_buckets)
        self.itl = collector.bucket_histogram(
            "kvtpu_engine_itl_seconds",
            "Inter-token latency between decode emissions",
            self.cfg.itl_buckets)
        self.tpot = collector.bucket_histogram(
            "kvtpu_engine_tpot_seconds",
            "Time per output token after the first",
            self.cfg.tpot_buckets)
        self.step_seconds = collector.bucket_histogram(
            "kvtpu_engine_decode_step_seconds",
            "Engine step() wall time",
            self.cfg.step_buckets)
        self._requests: Dict[str, _ReqState] = {}
        self.finished: deque = deque(maxlen=max(1, self.cfg.max_finished))
        self._step_counter = 0
        self._pool_stats: Dict[str, dict] = {}
        self._pool_evictions_seen: Dict[str, int] = {}
        self.profiler = ProfilerCapture(self.cfg.profile_dir)
        # MiniEngine.attention_backends, set by the owning engine: which
        # attention backend serves each phase on which device.
        self.attention_backends: dict = {}
        # Label children resolved once; labels() does a dict lookup + tuple
        # build per call, which the scrape path should not pay repeatedly.
        self._gauge_cache: Dict[str, tuple] = {}
        # Padding-waste accumulators (on_dispatch_tokens): real vs padded
        # tokens per device dispatch, fed by the ragged path AND the
        # padded fallback so the waste ratio compares the schedulers.
        self._dispatch_real = 0
        self._dispatch_padded = 0
        self._dispatches = 0
        self._last_waste_ratio = 0.0
        # Decode programs launched before the one before them was read
        # (on_launched_ahead), and programs in flight that had to be
        # waited for early, by what asked (on_lookahead_drained).
        self._launched_ahead = 0
        self._drained: Dict[str, int] = {}
        # Drafts a model's prediction module had verified, and those the
        # main model agreed with (on_drafts_verified).
        self.spec_drafted = 0
        self.spec_accepted = 0
        # A request's way to its first token (``_ReqState``): the start of
        # the step under way (``begin_step``), the decode programs launched
        # so far, and the request whose chunk is being dispatched
        # (``on_dispatch``) until its launch is numbered (``on_launch``).
        self._step_t0 = 0.0
        self._decode_launches = 0
        self._launching: Optional[_ReqState] = None

    # -- lifecycle hooks (called by MiniEngine) ---------------------------

    def on_admitted(self, request_id: str, prefix_hit_blocks: int,
                    traceparent: Optional[str] = None) -> None:
        now = time.monotonic()
        self._requests[request_id] = _ReqState(
            request_id, now, prefix_hit_blocks, traceparent)
        if prefix_hit_blocks > 0:
            collector.ENGINE_PREFIX_HIT_BLOCKS.inc(prefix_hit_blocks)
        if self.cfg.flight_records:
            fr.record(fr.KIND_ENGINE_REQUEST, {
                "request_id": request_id, "phase": "admit",
                "prefix_hit_blocks": prefix_hit_blocks})

    def set_traceparent(self, request_id: str, traceparent: Optional[str]) -> None:
        st = self._requests.get(request_id)
        if st is not None:
            st.traceparent = traceparent

    def begin_step(self) -> float:
        """The start of an engine ``step()``, which it returns."""
        self._step_t0 = time.monotonic()
        return self._step_t0

    def on_first_schedule(self, request_id: str,
                          enqueued_at: Optional[float] = None) -> None:
        """The scheduler's first pick of a request, in the ``step()`` that
        ``begin_step`` opened; ``enqueued_at``: when ``enqueue()`` ended."""
        st = self._requests.get(request_id)
        if st is not None and st.admit_ts is None:
            st.admit_ts = self._step_t0
            if enqueued_at is not None:
                st.queued_s = max(0.0, self._step_t0 - enqueued_at)

    def on_dispatch(self, request_id: Optional[str]) -> None:
        """A step program about to be launched: a prefill chunk of
        ``request_id`` (the ragged program that carries one too), or with
        None a decode program."""
        if request_id is None:
            self._decode_launches += 1
            self._launching = None
            return
        st = self._launching = self._requests.get(request_id)
        if st is not None and st.sched_ts is None and st.admit_ts is not None:
            # The first chunk of a request the scheduler picked (the
            # synchronous ``add_request`` runs its chunks in no step): its
            # gates are behind it, and what they held it is part of its wait.
            st.sched_ts = self._step_t0
            st.queued_s += st.sched_ts - st.admit_ts
            st.decodes_at_sched = self._decode_launches

    def on_launch(self, launch: int) -> None:
        """The device's ordinal of the program ``on_dispatch`` announced."""
        st = self._launching
        if st is not None:
            self._launching = None
            st.chunks += 1
            if st.first_launch < 0:
                st.first_launch = launch
            st.last_launch = launch

    def on_first_token(self, request_id: str, prompt_tokens: int = 0,
                       cached_tokens: int = 0) -> Optional[_ReqState]:
        """The request's record as its first token stands (what
        ``request.first_token`` is made from), or None for an unknown one."""
        st = self._requests.get(request_id)
        if st is None:
            return None
        now = time.monotonic()
        st.first_token_ts = now
        st.last_token_ts = now
        st.tokens = 1
        st.prompt_tokens = prompt_tokens
        st.cached_tokens = cached_tokens
        if st.admit_ts is None:  # synchronous add_request path
            st.admit_ts = st.enqueue_ts
        if st.sched_ts is None:  # no chunk of its own ran in a step()
            st.sched_ts = st.admit_ts
        else:
            st.decodes_between = self._decode_launches - st.decodes_at_sched
        # The trace-id exemplar links a slow TTFT bucket straight to the
        # retained trace in the fleet collector (OpenMetrics exposition).
        self.ttft.observe(now - st.enqueue_ts,
                          trace_id=trace_id_of(st.traceparent))
        return st

    def on_decode_tokens(self, request_id: str, n: int, now: float) -> None:
        st = self._requests.get(request_id)
        if st is None or n <= 0:
            return
        last = st.last_token_ts
        if last is None:  # decode before a recorded first token: treat as first
            st.first_token_ts = now
            st.tokens = n
            st.last_token_ts = now
            return
        gap = (now - last) / n
        observe = self.itl.observe
        for _ in range(n):
            observe(gap)
        st.tokens += n
        st.last_token_ts = now

    def on_finish(self, request_id: str, outcome: str = "finished") -> None:
        st = self._requests.pop(request_id, None)
        if st is None:
            return
        now = time.monotonic()
        if st.tokens > 1 and st.first_token_ts is not None \
                and st.last_token_ts is not None:
            self.tpot.observe(
                (st.last_token_ts - st.first_token_ts) / (st.tokens - 1))
        collector.ENGINE_REQUESTS.labels(outcome).inc()
        summary = st.summary(now, outcome)
        self.finished.append(summary)
        if self.cfg.flight_records:
            fr.record(fr.KIND_ENGINE_REQUEST, {
                "request_id": request_id, "phase": "finish",
                "outcome": outcome, "tokens": st.tokens})

    def on_step(self, duration_s: float, decoded: bool,
                pools: Sequence[Tuple[str, Any]] = (),
                prefilled: bool = False) -> None:
        """Once per engine ``step()``: step timing + decimated pool scrape.

        ``pools`` is ``[(group_name, block_manager), ...]``; each block
        manager answers :meth:`~models.engine.BlockManager.pool_stats`.
        ``prefilled``: the step ran a prefill chunk. Every request the
        scheduler has not picked yet stood behind it for the step's length
        (the one it picked has its ``admit_ts`` by now).
        """
        self.step_seconds.observe(duration_s)
        if prefilled:
            for st in self._requests.values():
                if st.admit_ts is None:
                    st.behind_s += duration_s
                    st.behind_chunks += 1
        if decoded:
            collector.ENGINE_DECODE_STEPS.inc()
        self._step_counter += 1
        if self._step_counter % max(1, self.cfg.pool_gauge_every) == 0:
            self.scrape_pools(pools)

    def scrape_pools(self, pools: Sequence[Tuple[str, Any]]) -> None:
        for group, bm in pools:
            stats = bm.pool_stats()
            self._pool_stats[group] = stats
            gauges = self._gauge_cache.get(group)
            if gauges is None:
                gauges = (
                    collector.ENGINE_POOL_FREE_PAGES.labels(group),
                    collector.ENGINE_POOL_CACHED_BLOCKS.labels(group),
                    collector.ENGINE_POOL_ORPHAN_PAGES.labels(group),
                )
                self._gauge_cache[group] = gauges
            free_g, cached_g, orphan_g = gauges
            free_g.set(stats["free_pages"])
            cached_g.set(stats["cached_blocks"])
            orphan_g.set(stats["orphan_pages"])
            seen = self._pool_evictions_seen.get(group, 0)
            delta = stats["evictions"] - seen
            if delta > 0:
                collector.ENGINE_POOL_EVICTIONS.labels(group).inc(delta)
                self._pool_evictions_seen[group] = stats["evictions"]

    def on_restore(self, outcome: str, seconds: Optional[float] = None) -> None:
        collector.record_engine_restore(outcome, seconds)

    def on_dispatch_tokens(self, real: int, dispatched: int) -> None:
        """Padding-waste accounting for one device dispatch.

        ``real`` tokens of actual work rode a ``dispatched``-token padded
        program — the gap is pure padding FLOPs. Both the ragged
        single-kernel path and the padded fallback (prefill buckets,
        pad-to-max_batch decode) report here, so the
        ``kvtpu_engine_ragged_*_tokens_total`` counters directly compare
        the two schedulers' waste.
        """
        if dispatched <= 0:
            return
        collector.record_ragged_dispatch(self.group, real, dispatched)
        self._dispatch_real += real
        self._dispatch_padded += dispatched
        self._dispatches += 1
        self._last_waste_ratio = 1.0 - real / dispatched

    def on_launched_ahead(self) -> None:
        """A decode program launched while the tokens of the one before
        it were still unread (``MiniEngine.step``)."""
        self._launched_ahead += 1

    def on_drafts_verified(self, drafted: int, accepted: int) -> None:
        """A speculative decode program read: ``drafted`` rows each had
        one draft verified, ``accepted`` of them emitted two tokens."""
        self.spec_drafted += drafted
        self.spec_accepted += accepted

    def on_lookahead_drained(self, cause: str) -> None:
        """A decode program in flight waited for before its step: an
        abort, a reset, a copier that takes the pools (``cause``)."""
        self._drained[cause] = self._drained.get(cause, 0) + 1

    # -- read side --------------------------------------------------------

    def _phase_stats(self, hist) -> dict:
        return {
            "count": hist.count,
            "p50": hist.percentile(0.50),
            "p90": hist.percentile(0.90),
            "p99": hist.percentile(0.99),
        }

    def debug_vars(self) -> dict:
        """The ``engine`` section of ``/debug/vars`` (and kvdiag)."""
        return {
            "group": self.group,
            "attention_backends": self.attention_backends,
            "pool": {g: dict(s) for g, s in self._pool_stats.items()},
            "requests": {
                "active": len(self._requests),
                "finished_window": len(self.finished),
                "recent": list(self.finished)[-8:],
            },
            "phases": {
                "ttft_seconds": self._phase_stats(self.ttft),
                "itl_seconds": self._phase_stats(self.itl),
                "tpot_seconds": self._phase_stats(self.tpot),
                "step_seconds": self._phase_stats(self.step_seconds),
            },
            "steps": self._step_counter,
            "ragged": {
                "real_tokens_total": self._dispatch_real,
                "padded_tokens_total": self._dispatch_padded,
                "last_waste_ratio": self._last_waste_ratio,
                "dispatches": self._dispatches,
            },
            "lookahead": {
                "launched_ahead": self._launched_ahead,
                "drained": dict(self._drained),
            },
            "speculation": {
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
            },
            "last_profile": self.profiler.last,
        }

    def attach_admin(self, server) -> None:
        """Register the debug provider and (if configured) the profiler."""
        server.register_debug("engine", self.debug_vars)
        if self.cfg.profile_dir:
            server.register_profiler(self.profiler.capture)

    def active_requests(self) -> List[str]:
        return list(self._requests)
