"""OpenTelemetry tracing with a no-op fallback and a built-in recorder.

Counterpart of reference ``pkg/telemetry/tracing.go``: spans are attached
unconditionally throughout the read/write paths via a thin facade and no-op
when no provider is configured (``indexer.go:90-103``). ``init_tracing``
configures an OTLP exporter from the standard ``OTEL_*`` env vars when the
optional exporter packages are importable; in library mode the host
process's global provider is used untouched.

Three operating modes, resolved per ``span()`` call in priority order:

1. **recording** — an in-process :class:`InMemorySpanExporter` installed via
   :func:`install_span_exporter`. Spans are plain Python objects with real
   trace/span ids, parentage via ``contextvars`` plus explicit W3C
   ``traceparent`` strings, and land in the exporter on exit. This needs
   only the stdlib, so cross-hop trace assertions work even on images that
   ship ``opentelemetry-api`` without the SDK.
2. **otel** — a real TracerProvider is installed on the global OTel API
   (either by :func:`init_tracing` or by the host process). Attributes are
   passed at span start; exceptions are recorded with ERROR status.
3. **noop** — neither of the above: a shared zero-allocation span that
   accepts ``set_attribute`` chains and costs one identity check per call.

W3C trace-context helpers (:func:`current_traceparent`,
:func:`parse_traceparent`) are the single source of truth for propagation
across the gRPC tokenizer hop and the ZMQ event wire.

Engine **phases** (:func:`phase`, :class:`EnginePhases`) are the second
half of the facade: what ``MiniEngine`` does inside ``enqueue()`` and
``step()``, cut into named pieces. They are off (the shared no-op, one
identity check) unless the engine was built with ``EngineConfig.telemetry``;
on, each phase is a ``jax.profiler.TraceAnnotation``, so a profiler capture
(``/debug/profile``) shows it on the same clock as the device ops. A phase
given a request's ``traceparent`` also opens the request's span
(``engine.admission``, ``engine.prefill_chunk``), whether or not phases are
on. The router's and the event pool's phases (``route.*``, ``ingest``) go
through the same :func:`phase` under an owner that is no engine
(:class:`Phases`, :func:`process_phases`): on once an engine of the process
has its phases on.
"""

from __future__ import annotations

import contextlib
import os
import random
import re
import threading
import time
from collections import deque
from typing import Iterator, Optional

try:
    from opentelemetry import trace as _otel_trace
except Exception:  # pragma: no cover - otel always present in this image
    _otel_trace = None

import contextvars

from ..utils.lockdep import new_lock

_SERVICE_NAME = "llmd-kv-cache-tpu"

_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def format_traceparent(trace_id: int, span_id: int, sampled: bool = True) -> str:
    """Render a W3C ``traceparent`` header value (version 00)."""
    return f"00-{trace_id:032x}-{span_id:016x}-{0x01 if sampled else 0x00:02x}"


def parse_traceparent(value: Optional[str]) -> Optional[tuple[int, int, int]]:
    """Parse ``traceparent`` → ``(trace_id, span_id, flags)``; None if invalid.

    Malformed values are dropped rather than raised: a bad header from a
    remote peer must never break event ingestion or an RPC.
    """
    if not value or not isinstance(value, str):
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    trace_id = int(m.group(1), 16)
    span_id = int(m.group(2), 16)
    if trace_id == 0 or span_id == 0:
        return None
    return trace_id, span_id, int(m.group(3), 16)


class _NoopSpan:
    """Shared do-nothing span; every mutator chains so call sites can write
    ``span.set_attribute(...).set_attribute(...)`` without mode checks."""

    __slots__ = ()

    def set_attribute(self, *_args, **_kwargs) -> "_NoopSpan":
        return self

    def set_attributes(self, *_args, **_kwargs) -> "_NoopSpan":
        return self

    def add_event(self, *_args, **_kwargs) -> "_NoopSpan":
        return self

    def record_exception(self, *_args, **_kwargs) -> None:
        pass

    def set_status(self, *_args, **_kwargs) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _NoopSpanCM:
    """Reusable, allocation-free context manager for the no-op path."""

    __slots__ = ()

    def __enter__(self) -> _NoopSpan:
        return _NOOP_SPAN

    def __exit__(self, *_exc) -> bool:
        return False


_NOOP_CM = _NoopSpanCM()

# Logical process identity ("engine-pod-0", "shard:127.0.0.1:15920",
# "router", ...) stamped onto every exported span that does not already
# carry an explicit ``process`` attribute. The fleet collector attributes
# critical-path segments to these identities; in production each identity
# also maps to a distinct scrape endpoint.
_PROCESS_IDENTITY: Optional[str] = None


def set_process_identity(identity: Optional[str]) -> None:
    """Set (or clear, with None) this process's span attribution identity."""
    global _PROCESS_IDENTITY
    _PROCESS_IDENTITY = identity


def process_identity() -> Optional[str]:
    return _PROCESS_IDENTITY


_dropped_counter = None


def _count_dropped_span() -> None:
    """Bump ``kvtpu_trace_dropped_spans_total`` (lazy: tracing must stay
    importable without the metrics stack, e.g. under kvdiag deep-debug)."""
    global _dropped_counter
    if _dropped_counter is None:
        try:
            from llmd_kv_cache_tpu.metrics.collector import TRACE_DROPPED_SPANS

            _dropped_counter = TRACE_DROPPED_SPANS
        except Exception:  # pragma: no cover - metrics stack absent
            _dropped_counter = False
    if _dropped_counter:
        try:
            _dropped_counter.inc()
        except Exception:  # pragma: no cover  # lint: allow-swallow
            pass


class RecordedSpan:
    """A finished-or-active span in recording mode.

    Mirrors the slice of the OTel Span API the library uses (set_attribute,
    record_exception, set_status) plus the readback fields tests assert on.
    """

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent_span_id",
        "attributes",
        "events",
        "status",
        "status_description",
        "start_time",
        "end_time",
        "seq",
    )

    def __init__(
        self,
        name: str,
        trace_id: int,
        span_id: int,
        parent_span_id: Optional[int],
        attributes: Optional[dict] = None,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.attributes = dict(attributes) if attributes else {}
        self.events: list[tuple[str, dict]] = []
        self.status = "UNSET"
        self.status_description: Optional[str] = None
        self.start_time = time.time()
        self.end_time: Optional[float] = None
        # Monotonic export sequence number, stamped by the exporter so
        # remote pullers (/debug/spans?since=seq) can resume a cursor.
        self.seq: Optional[int] = None

    def set_attribute(self, key: str, value) -> "RecordedSpan":
        self.attributes[key] = value
        return self

    def set_attributes(self, attributes: dict) -> "RecordedSpan":
        self.attributes.update(attributes)
        return self

    def add_event(self, name: str, attributes: Optional[dict] = None) -> "RecordedSpan":
        self.events.append((name, attributes or {}))
        return self

    def record_exception(self, exc: BaseException) -> None:
        self.events.append(
            ("exception", {"exception.type": type(exc).__name__, "exception.message": str(exc)})
        )

    def set_status(self, status: str, description: Optional[str] = None) -> None:
        self.status = status
        self.status_description = description

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id)

    def to_wire(self) -> dict:
        """JSON-safe dict for span export over ``/debug/spans``.

        Ids travel as hex strings (W3C casing), attribute values are
        coerced to JSON scalars so a numpy int at a span site can never
        break the export path.
        """

        def _scalar(v):
            if isinstance(v, (str, bool)) or v is None:
                return v
            if isinstance(v, (int, float)):
                return v
            try:  # numpy scalars and friends
                return v.item()
            except Exception:
                return str(v)

        return {
            "name": self.name,
            "trace_id": f"{self.trace_id:032x}",
            "span_id": f"{self.span_id:016x}",
            "parent_span_id": (
                None if self.parent_span_id is None else f"{self.parent_span_id:016x}"
            ),
            "start_time": self.start_time,
            "end_time": self.end_time,
            "status": self.status,
            "attributes": {str(k): _scalar(v) for k, v in self.attributes.items()},
            "seq": self.seq,
        }

    @classmethod
    def from_wire(cls, data: dict) -> "RecordedSpan":
        """Inverse of :meth:`to_wire` (collector side)."""
        parent = data.get("parent_span_id")
        sp = cls(
            str(data.get("name", "")),
            int(str(data.get("trace_id", "0")) or "0", 16),
            int(str(data.get("span_id", "0")) or "0", 16),
            None if parent in (None, "") else int(str(parent), 16),
            data.get("attributes") or {},
        )
        sp.start_time = float(data.get("start_time") or 0.0)
        end = data.get("end_time")
        sp.end_time = None if end is None else float(end)
        sp.status = str(data.get("status", "UNSET"))
        sp.seq = data.get("seq")
        return sp

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"RecordedSpan({self.name!r}, trace={self.trace_id:032x}, "
            f"span={self.span_id:016x}, parent="
            f"{'-' if self.parent_span_id is None else format(self.parent_span_id, '016x')})"
        )


class InMemorySpanExporter:
    """Collects finished :class:`RecordedSpan` objects for assembly/export.

    Stand-in for ``opentelemetry.sdk``'s in-memory exporter on images where
    only ``opentelemetry-api`` is installed — and the local buffer behind
    the admin ``/debug/spans?since=seq`` pull endpoint.

    The buffer is a ring: when ``max_spans`` is reached the **oldest** span
    is evicted (previously new spans were silently discarded, which meant a
    long-lived pod stopped tracing entirely once warm). Every eviction is
    counted both locally (:attr:`dropped`) and in the
    ``kvtpu_trace_dropped_spans_total`` counter so the collector can see
    export-loss on a lagging cursor.

    Spans are stamped with a monotonically increasing ``seq`` (and, when
    missing, the process identity) lazily — at pull time under the ring
    lock, not on the per-span export hot path — so :meth:`drain_since`
    lets a remote puller resume from its last cursor while ``export``
    itself stays a bare ring append (gated <1% of score p50 by
    ``bench.py --fleet-telemetry``).
    """

    __slots__ = ("_lock", "_spans", "_max_spans", "_next_seq", "dropped")

    def __init__(self, max_spans: int = 10_000):
        self._lock = new_lock()
        self._spans: deque[RecordedSpan] = deque(maxlen=max(1, int(max_spans)))
        self._max_spans = max(1, int(max_spans))
        self._next_seq = 0
        self.dropped = 0

    def export(self, span: RecordedSpan) -> None:
        # Hot path: runs inline at every span end once fleet span export
        # is on. Everything deferrable (seq + identity stamping, wire
        # encoding) happens at pull time instead.
        spans = self._spans
        with self._lock:
            if len(spans) >= self._max_spans:
                self.dropped += 1
                _count_dropped_span()
            spans.append(span)  # at capacity the deque evicts the oldest

    def _stamp_locked(self) -> None:
        """Assign ``seq`` (and process identity) to not-yet-stamped spans.

        Caller holds ``self._lock``. Spans are stamped newest-backwards
        until the first already-stamped one, so the cost is O(new spans)
        per pull, not O(ring).
        """
        fresh = []
        for span in reversed(self._spans):
            if span.seq is not None:
                break
            fresh.append(span)
        identity = _PROCESS_IDENTITY
        for span in reversed(fresh):
            span.seq = self._next_seq
            self._next_seq += 1
            if identity is not None and "process" not in span.attributes:
                span.attributes["process"] = identity

    @property
    def spans(self) -> list[RecordedSpan]:
        with self._lock:
            return list(self._spans)

    @property
    def next_seq(self) -> int:
        with self._lock:
            self._stamp_locked()
            return self._next_seq

    def drain_since(self, since: int = -1) -> tuple[list[RecordedSpan], int]:
        """Spans with ``seq > since`` (oldest first) and the next cursor.

        Non-destructive: the ring keeps its contents so several pullers
        (or a retried pull) each keep their own cursor; the collector
        dedupes by span id anyway.
        """
        with self._lock:
            self._stamp_locked()
            out = [s for s in self._spans if s.seq is not None and s.seq > since]
            return out, self._next_seq - 1

    def export_since(self, since: int = -1) -> dict:
        """JSON-safe ``/debug/spans`` payload: spans + cursor + drop count."""
        spans, cursor = self.drain_since(since)
        return {
            "spans": [s.to_wire() for s in spans if s.end_time is not None],
            "next_seq": cursor,
            "dropped": self.dropped,
        }

    def find(self, name: str) -> list[RecordedSpan]:
        return [s for s in self.spans if s.name == name]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# Ambient current span for recording mode. contextvars gives correct
# nesting per-thread / per-async-task; cross-thread and cross-process hops
# must pass an explicit traceparent (which is what the wire formats do).
_CURRENT_SPAN: contextvars.ContextVar[Optional[RecordedSpan]] = contextvars.ContextVar(
    "kvtpu_current_span", default=None
)

# Cross-thread view of each thread's innermost active span *name*.
# contextvars are only readable from their own thread, but the sampling
# profiler (telemetry/sampling_profiler.py) walks ``sys._current_frames()``
# from a background thread and must attribute each sampled stack to the
# span the sampled thread is inside. A plain dict keyed by thread ident is
# enough: single-key int reads/writes are atomic under the GIL, so the hot
# path stays two dict stores per span (inside the <1% budget that
# ``bench.py --fleet-telemetry`` gates) and the sampler reads without a
# lock — a momentarily stale name only mis-tags one 15 ms sample.
_THREAD_SPAN_NAMES: dict[int, str] = {}


def active_span_names() -> dict[int, str]:
    """Snapshot of thread ident → innermost active span name.

    Read by the sampling profiler; copies so the caller can iterate while
    spans keep opening/closing.
    """
    return dict(_THREAD_SPAN_NAMES)

_recording_exporter: Optional[InMemorySpanExporter] = None


def _new_trace_id() -> int:
    return random.getrandbits(128) or 1


def _new_span_id() -> int:
    return random.getrandbits(64) or 1


def _otel_provider_configured() -> bool:
    """True when a real (recording) TracerProvider is installed globally.

    The api-only default providers live under ``opentelemetry.trace``; any
    real SDK (or host-supplied) provider comes from another module.
    """
    if _otel_trace is None:
        return False
    provider = _otel_trace.get_tracer_provider()
    return not type(provider).__module__.startswith("opentelemetry.trace")


class _Tracer:
    """Thin facade: recording exporter > OTel provider > no-op."""

    def __init__(self) -> None:
        self._otel_tracer = None
        if _otel_trace is not None and _otel_provider_configured():
            self._otel_tracer = _otel_trace.get_tracer(_SERVICE_NAME)

    def span(
        self,
        name: str,
        parent_traceparent: Optional[str] = None,
        **attributes,
    ):
        """Context manager yielding a span.

        ``parent_traceparent`` (a W3C header value) links this span under a
        remote parent — used on the server side of the gRPC hop and by the
        event-pool ingest loop; when omitted the ambient current span (if
        any) is the parent. Remaining kwargs become span attributes, set at
        span start. On exception exit the exception is recorded on the span
        with ERROR status and re-raised.
        """
        if _recording_exporter is not None:
            return self._recording_span(name, parent_traceparent, attributes)
        if self._otel_tracer is not None:
            return self._otel_span(name, parent_traceparent, attributes)
        return _NOOP_CM

    @contextlib.contextmanager
    def _recording_span(
        self, name: str, parent_traceparent: Optional[str], attributes: dict
    ) -> Iterator[RecordedSpan]:
        exporter = _recording_exporter
        trace_id: Optional[int] = None
        parent_id: Optional[int] = None
        parsed = parse_traceparent(parent_traceparent)
        if parsed is not None:
            trace_id, parent_id, _flags = parsed
        else:
            cur = _CURRENT_SPAN.get()
            if cur is not None:
                trace_id, parent_id = cur.trace_id, cur.span_id
        if trace_id is None:
            trace_id = _new_trace_id()
        sp = RecordedSpan(name, trace_id, _new_span_id(), parent_id, attributes)
        token = _CURRENT_SPAN.set(sp)
        tid = threading.get_ident()
        prev_name = _THREAD_SPAN_NAMES.get(tid)
        _THREAD_SPAN_NAMES[tid] = name
        try:
            yield sp
        except BaseException as exc:
            sp.record_exception(exc)
            sp.set_status("ERROR", str(exc))
            raise
        finally:
            if prev_name is None:
                _THREAD_SPAN_NAMES.pop(tid, None)
            else:
                _THREAD_SPAN_NAMES[tid] = prev_name
            _CURRENT_SPAN.reset(token)
            sp.end_time = time.time()
            if exporter is not None:
                exporter.export(sp)

    @contextlib.contextmanager
    def _otel_span(
        self, name: str, parent_traceparent: Optional[str], attributes: dict
    ) -> Iterator[object]:
        context = None
        parsed = parse_traceparent(parent_traceparent)
        if parsed is not None:
            trace_id, span_id, flags = parsed
            remote = _otel_trace.SpanContext(
                trace_id=trace_id,
                span_id=span_id,
                is_remote=True,
                trace_flags=_otel_trace.TraceFlags(flags),
            )
            context = _otel_trace.set_span_in_context(_otel_trace.NonRecordingSpan(remote))
        tid = threading.get_ident()
        prev_name = _THREAD_SPAN_NAMES.get(tid)
        _THREAD_SPAN_NAMES[tid] = name
        try:
            with self._otel_tracer.start_as_current_span(
                name, context=context, attributes=attributes or None, end_on_exit=True
            ) as sp:
                try:
                    yield sp
                except BaseException as exc:
                    sp.record_exception(exc)
                    try:
                        from opentelemetry.trace import Status, StatusCode

                        sp.set_status(Status(StatusCode.ERROR, str(exc)))
                    except Exception:  # pragma: no cover - api drift  # lint: allow-swallow
                        pass
                    raise
        finally:
            if prev_name is None:
                _THREAD_SPAN_NAMES.pop(tid, None)
            else:
                _THREAD_SPAN_NAMES[tid] = prev_name


_tracer: Optional[_Tracer] = None


def tracer() -> _Tracer:
    global _tracer
    if _tracer is None:
        _tracer = _Tracer()
    return _tracer


def current_traceparent() -> Optional[str]:
    """The ambient span's W3C ``traceparent``, or None when untraced.

    This is what gets injected into outbound gRPC metadata and onto the
    ZMQ event wire.
    """
    if _recording_exporter is not None:
        cur = _CURRENT_SPAN.get()
        if cur is not None:
            return cur.traceparent
        return None
    if _otel_trace is not None:
        ctx = _otel_trace.get_current_span().get_span_context()
        if ctx is not None and ctx.trace_id != 0 and ctx.span_id != 0:
            return format_traceparent(
                ctx.trace_id, ctx.span_id, bool(int(ctx.trace_flags) & 0x01)
            )
    return None


@contextlib.contextmanager
def _ambient_parent(parsed: tuple[int, int, int]) -> Iterator[None]:
    trace_id, span_id, flags = parsed
    if _recording_exporter is not None:
        # A stand-in for the remote span: never exported, only read for
        # its ids by the spans opened inside.
        token = _CURRENT_SPAN.set(RecordedSpan("", trace_id, span_id, None))
        try:
            yield
        finally:
            _CURRENT_SPAN.reset(token)
        return
    from opentelemetry import context as otel_context

    remote = _otel_trace.SpanContext(
        trace_id=trace_id, span_id=span_id, is_remote=True,
        trace_flags=_otel_trace.TraceFlags(flags))
    token = otel_context.attach(_otel_trace.set_span_in_context(
        _otel_trace.NonRecordingSpan(remote)))
    try:
        yield
    finally:
        otel_context.detach(token)


def remote_parent(traceparent: Optional[str]):
    """Context manager making ``traceparent`` the ambient parent of the
    spans opened inside, without opening one itself: deferred work (the
    ingest coalescer's flush) joins the span that caused it after that
    span has ended. The shared no-op for None, a malformed value, or no
    tracing configured."""
    if traceparent is None:
        return _NOOP_CM
    parsed = parse_traceparent(traceparent)
    if parsed is None or (_recording_exporter is None
                          and tracer()._otel_tracer is None):
        return _NOOP_CM
    return _ambient_parent(parsed)


def install_span_exporter(
    exporter: Optional[InMemorySpanExporter] = None,
) -> InMemorySpanExporter:
    """Switch the facade into recording mode (tests, ``kvdiag`` deep-debug).

    Returns the active exporter (created when not supplied). Call
    :func:`uninstall_span_exporter` to restore the previous mode.
    """
    global _recording_exporter, _tracer
    if exporter is None:
        exporter = InMemorySpanExporter()
    _recording_exporter = exporter
    _tracer = None  # rebuild so mode resolution sees the exporter
    return exporter


def uninstall_span_exporter() -> None:
    global _recording_exporter, _tracer
    _recording_exporter = None
    _tracer = None


def active_span_exporter() -> Optional[InMemorySpanExporter]:
    """The currently installed recording exporter, if any (fleet wiring
    reuses an already-installed exporter instead of replacing it)."""
    return _recording_exporter


@contextlib.contextmanager
def recording_tracing(
    exporter: Optional[InMemorySpanExporter] = None,
) -> Iterator[InMemorySpanExporter]:
    """Scoped :func:`install_span_exporter` — the test-fixture form."""
    installed = install_span_exporter(exporter)
    try:
        yield installed
    finally:
        uninstall_span_exporter()


def init_tracing(service_name: Optional[str] = None) -> bool:
    """Standalone-mode init from OTEL_* env (reference tracing.go:72-141).

    Returns True when an OTLP exporter was installed; False when running in
    library mode (host provider reused) or exporters are unavailable.
    """
    global _tracer
    if _otel_trace is None:
        return False
    exporter_kind = os.environ.get("OTEL_TRACES_EXPORTER", "otlp")
    if exporter_kind in ("none", ""):
        return False
    try:
        from opentelemetry.exporter.otlp.proto.grpc.trace_exporter import OTLPSpanExporter
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import TracerProvider
        from opentelemetry.sdk.trace.export import BatchSpanProcessor
        from opentelemetry.sdk.trace.sampling import ParentBasedTraceIdRatio
    except Exception:
        return False

    endpoint = os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT", "http://localhost:4317")
    ratio = float(os.environ.get("OTEL_TRACES_SAMPLER_ARG", "0.1"))
    provider = TracerProvider(
        resource=Resource.create(
            {"service.name": os.environ.get("OTEL_SERVICE_NAME", service_name or _SERVICE_NAME)}
        ),
        sampler=ParentBasedTraceIdRatio(ratio),
    )
    provider.add_span_processor(BatchSpanProcessor(OTLPSpanExporter(endpoint=endpoint)))
    _otel_trace.set_tracer_provider(provider)
    _tracer = None  # rebuild against the new provider
    return True


# -- engine phases -----------------------------------------------------------

# Every phase the program opens, in one place. ``enqueue.*`` run inside
# ``MiniEngine.enqueue``; ``step.*`` inside ``MiniEngine.step`` in this
# order (inputs → dispatch once per program, → fetch where the host reads
# its tokens; commit and emit when a prefill finished or blocks were
# evicted). Every step program samples as its own tail.
# ``step.dispatch`` carries its program's ``rows``, ``tokens`` and ``padded``
# and, where the model has them, what the program reads a layer: a decode
# step that selects ``index_keys`` and ``selected_keys``; with linear layers
# a decode step ``state_rows``, a prefill chunk ``scan_tokens``. A latent
# model's prefill chunk carries ``expanded_keys``
# (``MiniEngine._dispatch_phase``, from the chunk program's own rule,
# ``llama.prefill_per_head``): the key positions a head expands a latent
# layer (whole superblocks up to the chunk's end) where the chunk is padded
# to enough queries to attend per head (``ops.pallas_latent_prefill``), 0
# where the program holds the absorbed kernel (a short chunk, a mesh, the
# ragged scheduler's chunk, the XLA prefill). It names
# what it launched: ``program``, the jitted function's name (a device
# trace calls the execution ``jit_<program>``), and ``launch``, the
# program's ordinal among all that this process sent to the same device
# (``MiniEngine._launch_input``: numbered traced or not, because the engine
# reads from the numbers whether it has the chip to itself); the
# ``step.fetch`` that waits for a program's tokens carries that ``launch``,
# and a prefill chunk behind which the step reads a decode program has no
# fetch (where the step reads nothing, the chunk before it is fetched, to
# wait for it: ``MiniEngine.step``). A padded decode
# step's dispatch carries ``ahead``: 1 where it was launched before the
# tokens of the decode program before it were read (it takes them on the
# device), else 0; that program's fetch then follows a later dispatch.
# ``route.*`` run inside ``KVAwareRouter.route`` and ``ingest`` inside
# ``Pool.process_event_batch``, under an owner that is no engine
# (``process_phases``), so they carry neither ``pod`` nor ``step`` of their
# own: ``route.decide`` (all of a routing decision; nests the five below)
# carries ``keys``, ``pods``, ``pod``, ``best``, ``speculative`` and
# ``expired``; ``ingest`` carries ``pod``, ``events`` and ``keys``.
# ``request.first_token`` is a marker, opened and closed at once where a
# request's first token stands (``MiniEngine._finish_prefill``, inside
# ``step.commit``): its place in the capture is the first token's, beside
# the device's ops, and it carries the request's way there as the engine's
# telemetry kept it (``EngineTelemetry``'s ``_ReqState.first_token_split``):
# ``request_id``, ``prompt_tokens``, ``cached_tokens`` (after a restore),
# ``chunks`` (its prefill chunks dispatched) with ``first_launch`` and
# ``last_launch`` (their dispatches' ``launch``), ``decodes_between`` (this
# engine's decode programs dispatched from its first chunk to here) and three
# durations in ns, each the difference of two readings of one monotonic
# clock: ``queued_ns`` (the end of ``enqueue()`` → the start of the
# ``step()`` that first ran a chunk of it: the one that first picked it,
# unless a restore or handoff gate then held it), of which ``behind_ns``
# (with ``behind_chunks``: this engine's steps in between whose chunk was
# another request's; the rest is its own gate and the caller's time between
# steps), and ``prefill_ns`` (the start of that ``step()`` → the marker).
# The synchronous ``add_request`` emits it with ``queued_ns`` 0.
PHASE_ENQUEUE_ADMIT = "enqueue.admit"      # all of admission (nests the two below)
PHASE_ENQUEUE_HASH = "enqueue.hash"        # tokens → block hashes
PHASE_ENQUEUE_LOOKUP = "enqueue.lookup"    # prefix probe, page allocation, eviction
PHASE_STEP_OFFLOAD_POLL = "step.offload_poll"
PHASE_STEP_SCHEDULE = "step.schedule"      # the pick; restore and handoff gates
PHASE_STEP_INPUTS = "step.inputs"          # the program's arguments built in numpy, packed into one array
PHASE_STEP_DISPATCH = "step.dispatch"      # its one _to_dev transfer + the jitted call returning + the tokens' copy back started
PHASE_STEP_FETCH = "step.fetch"            # the blocking np.asarray of the program's tokens
PHASE_STEP_COMMIT = "step.commit"          # _commit_full_blocks → commit_blocks, write-through
PHASE_STEP_EMIT = "step.emit"              # event batch → sink → Pool/index (nests in commit)
PHASE_STEP_FINISH = "step.finish"          # release of finished requests; carries the step's counters
PHASE_STEP_SNAPSHOT = "step.snapshot"      # a prefill chunk's snapshots planned: slots reserved in the state pool, what they evicted
PHASE_STEP_WINDOW = "step.window"          # a two-pool model's window pages: ensured for what a program writes, reclaimed behind the window
PHASE_ROUTE_DECIDE = "route.decide"        # all of KVAwareRouter.route (nests the five below)
PHASE_ROUTE_EXPIRE = "route.expire"        # speculative entries past their TTL dropped
PHASE_ROUTE_HASH = "route.hash"            # prompt tokens → block keys
PHASE_ROUTE_LOOKUP = "route.lookup"        # the chain looked up in the index
PHASE_ROUTE_SCORE = "route.score"          # the scorer over what was found, and the pick
PHASE_ROUTE_SPECULATE = "route.speculate"  # speculative entries for the chosen pod
PHASE_INGEST = "ingest"                    # Pool.process_event_batch: one batch applied to the index
PHASE_REQUEST_FIRST_TOKEN = "request.first_token"  # a marker: a request's first token stands, and how it got there

PHASE_NAMES = (
    PHASE_ENQUEUE_ADMIT, PHASE_ENQUEUE_HASH, PHASE_ENQUEUE_LOOKUP,
    PHASE_STEP_OFFLOAD_POLL, PHASE_STEP_SCHEDULE, PHASE_STEP_INPUTS,
    PHASE_STEP_DISPATCH, PHASE_STEP_FETCH,
    PHASE_STEP_COMMIT, PHASE_STEP_EMIT, PHASE_STEP_FINISH,
    PHASE_STEP_SNAPSHOT, PHASE_STEP_WINDOW,
    PHASE_ROUTE_EXPIRE, PHASE_ROUTE_HASH, PHASE_ROUTE_LOOKUP,
    PHASE_ROUTE_SCORE, PHASE_ROUTE_SPECULATE, PHASE_ROUTE_DECIDE,
    PHASE_INGEST, PHASE_REQUEST_FIRST_TOKEN,
)

SPAN_ENGINE_ADMISSION = "llm_d.kv_cache.engine.admission"
SPAN_ENGINE_PREFILL_CHUNK = "llm_d.kv_cache.engine.prefill_chunk"
SPAN_ENGINE_DECODE_STEP = "llm_d.kv_cache.engine.decode_step"

# The request span a phase opens when it is given a ``traceparent``.
_SPAN_OF_PHASE = {
    PHASE_ENQUEUE_ADMIT: SPAN_ENGINE_ADMISSION,
    PHASE_STEP_DISPATCH: SPAN_ENGINE_PREFILL_CHUNK,
}

# What a phase yields when nothing listens: ``if sp is not NOOP_SPAN``
# guards attributes that cost something to build.
NOOP_SPAN = _NOOP_SPAN


class Phases:
    """An owner of phases that is no engine (the router's, the pool's):
    it has no ``step()`` to count, so its phases carry neither ``pod`` nor
    ``step`` but what their call sites give them. Nothing on it changes
    once built, so any thread may open a phase under it."""

    __slots__ = ("_annotation",)
    step = None               # no ordinal: `_Phase` stamps nothing of its own
    transfers = bytes = 0     # and it moves nothing to a device

    def __init__(self):
        # Reached here, not at import: ``scoring/`` and ``events/`` import
        # this module and must not need JAX to.
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation


# What the phases of the router and the pool are opened under, or None
# while they are off: set when the first engine of this process switches
# its phases on (they share its profiler capture), never unset.
_process_phases: Optional[Phases] = None


def process_phases() -> Optional[Phases]:
    """The owner of the phases that belong to no engine, or None while no
    engine of this process has phases: read at each call (a router is
    built after the engines, a pool before them), so off such a site costs
    this read and ``phase``'s identity check."""
    return _process_phases


class EnginePhases(Phases):
    """What one engine's phases share: whose they are, the ordinal of the
    ``step()`` they run in (0 before the first), and what that step has
    dispatched and moved to the device so far (``programs``: jitted calls,
    counted by the phases given ``programs=``; ``transfers``/``bytes``:
    counted by the engine's ``_to_dev``). Every phase carries ``pod`` and
    ``step``; ``step.finish`` carries the step's totals. Touched by the one
    thread that owns the engine.
    """

    __slots__ = ("pod", "step", "programs", "transfers", "bytes")

    def __init__(self, pod: str):
        global _process_phases
        super().__init__()
        self.pod = pod
        self.step = 0
        self.programs = self.transfers = self.bytes = 0
        if _process_phases is None:
            _process_phases = Phases()

    def begin_step(self) -> None:
        self.step += 1
        self.programs = self.transfers = self.bytes = 0


class _Phase:
    """An open phase: a TraceAnnotation (+ the request's span)."""

    __slots__ = ("_phases", "_name", "_attrs", "_ann", "_span_cm", "_span",
                 "_moved")

    def __init__(self, phases: Phases, name: str, span_cm, attrs: dict):
        self._phases, self._name, self._attrs = phases, name, attrs
        self._span_cm, self._span = span_cm, None

    def __enter__(self) -> "_Phase":
        phases = self._phases
        self._moved = (phases.transfers, phases.bytes)
        if self._span_cm is not None:
            self._span = self._span_cm.__enter__()
        if phases.step is None:
            self._ann = phases._annotation(self._name, **self._attrs)
        else:
            self._ann = phases._annotation(
                self._name, pod=phases.pod, step=phases.step, **self._attrs)
        self._ann.__enter__()
        return self

    def set_attribute(self, key: str, value) -> "_Phase":
        """A size known only inside the phase (blocks committed, …)."""
        self._ann.set_metadata(**{key: value})
        if self._span is not None:
            self._span.set_attribute(key, value)
        return self

    def __exit__(self, *exc) -> bool:
        phases = self._phases
        transfers = phases.transfers - self._moved[0]
        if transfers:
            self._ann.set_metadata(
                transfers=transfers, bytes=phases.bytes - self._moved[1])
        self._ann.__exit__(*exc)
        if self._span_cm is not None:
            self._span_cm.__exit__(*exc)
        return False


def phase(phases: Optional[Phases], name: str,
          traceparent: Optional[str] = None, programs: int = 0, **attrs):
    """Context manager around one phase.

    ``phases`` is the engine's :class:`EnginePhases` (the router's and the
    pool's sites pass :func:`process_phases`), or None when the engine was
    built without ``EngineConfig.telemetry``: then this is the shared no-op
    (nothing is built, no clock is read) — unless the request carries a
    ``traceparent`` and the phase has a request span, which is opened as
    before (itself the no-op without an exporter or provider). On, the
    phase is a ``jax.profiler.TraceAnnotation`` named ``name`` with
    ``attrs`` and, of an engine, ``pod`` and ``step``: it costs a few
    hundred nanoseconds while no profiler captures and lands on the
    capture's host plane while one does, beside the device's ops (the
    profiler lines the two clocks up to about a millisecond: PERF.md §6,
    PR 38). ``programs`` is the number of jitted calls made inside. What
    is yielded takes ``set_attribute(key, value)`` either way.

    A call site on a hot path passes ``attrs`` only behind its own check
    of ``phases`` (or sets them inside, through ``set_attribute``), so that
    off it evaluates and builds nothing.
    """
    span_cm = None
    if traceparent is not None and name in _SPAN_OF_PHASE:
        span_cm = tracer().span(_SPAN_OF_PHASE[name],
                                parent_traceparent=traceparent, **attrs)
    if phases is None:
        return _NOOP_CM if span_cm is None else span_cm
    if programs:
        phases.programs += programs
        attrs["programs"] = programs
    if span_cm is not None:
        attrs["traceparent"] = traceparent
    return _Phase(phases, name, span_cm, attrs)


def span_event(name: str, traceparent: str, **attrs) -> None:
    """An event-style span under ``traceparent``: opened and closed at
    once, it marks a point (a decode step's emission) in a request's trace."""
    with tracer().span(name, parent_traceparent=traceparent, **attrs):
        pass
