"""The serving loop, in wall-clock time.

One thread per replica owns its ``MiniEngine`` (the engine has no lock and
``step()`` blocks on the token fetch): it drains the replica's inbox, admits
what fits, and steps while it has work. One generator thread walks the
schedule: at each due time (open loop) or when a client's previous request
completed (closed loop) it routes the prompt and puts it into the chosen
replica's inbox. Every request is timed from its due time (open loop) or
its send (closed loop).

The engine admits at once and has no waiting queue, so the replica thread
keeps one: a request waits there while the replica already runs
``max_batch`` requests or has no pages for it, and that wait is part of its
time to first token.

Everything the metric readers need is recorded here as plain numbers on
:class:`Run`; nothing here computes or names a metric.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax

from .fleet import Fleet
from .stats import percentile

now = time.perf_counter
Span = jax.profiler.TraceAnnotation


@dataclass
class RequestRecord:
    idx: int
    arrival: object            # generators.common.Arrival
    prompt_len: int
    max_new: int
    start: float = 0.0         # due (open loop) or send (closed loop)
    sent: float = 0.0          # when the generator routed it
    pod: str = ""
    route_s: float = 0.0
    enqueued: Optional[float] = None     # engine.enqueue returned
    first_sched: Optional[float] = None  # start of the first step that
                                         # advanced its prefill
    token_times: list = field(default_factory=list)
    cached_len: int = 0
    hbm_hit_blocks: int = 0
    restored_blocks: int = 0
    restore_t0: Optional[float] = None
    restore_t1: Optional[float] = None
    done: bool = False
    failed: str = ""
    tokens_ok: bool = True
    sampled: bool = False
    req: object = None         # the engine's Request while it lives


@dataclass
class StepRecord:
    t0: float
    t1: float
    prefill_tokens: int     # real (unpadded) prompt tokens computed
    prefill_pos: int        # context before them
    prefill_done: bool      # a prefill finished in this step (commit inside)
    decode_rows: int
    decode_ctx: int         # sum over rows of keys attended (window-capped)


@dataclass
class Run:
    """What one window left behind. Times are ``time.perf_counter()``."""

    seconds: float
    t_start: float = 0.0
    t_sample: float = 0.0      # sampling starts (after the warm fraction)
    t_sample_end: float = 0.0  # arrivals after this are served, not sampled
    t_end: float = 0.0
    loop: str = "open"
    requests: list = field(default_factory=list)
    steps: dict = field(default_factory=dict)      # pod -> [StepRecord]
    spans: dict = field(default_factory=dict)      # name -> [(t0, t1)]
    lateness: list = field(default_factory=list)   # send - due, seconds
    pool_before: dict = field(default_factory=dict)  # pod -> pool_stats()
    pool_after: dict = field(default_factory=dict)
    inflight_start: int = 0    # requests alive when sampling started
    inflight_end: int = 0      # and when the window ended
    compiles_in_window: int = 0
    errors: list = field(default_factory=list)
    # For run.py's "after the window" line. ``stages``: host seconds of
    # what the run waited for once the window had ended (the joins).
    # ``tracer``: what its thread did beside the window, in seconds:
    # ``start_trace``, ``slice`` and what ``ended_by``, ``stop_trace`` and
    # the part of it that lay past the window's end.
    stages: dict = field(default_factory=dict)
    tracer: dict = field(default_factory=dict)
    # Filled by run.py: the model, its counts (``names.counts``), the data
    # files, the reduced trace.
    cfg: object = None
    counts: object = None
    traffic: dict = field(default_factory=dict)
    setup_seconds: float = 0.0
    trace: object = None
    trace_bytes: int = 0       # size of the .xplane.pb
    peaks: dict = field(default_factory=dict)

    def span(self, name: str, t0: float, t1: float) -> None:
        self.spans.setdefault(name, []).append((t0, t1))

    def sampled(self) -> list:
        return [r for r in self.requests if r.sampled]

    def failed(self) -> list:
        """Sampled requests that were refused, raised, or had no first
        token when the window ended."""
        return [r for r in self.sampled() if r.failed or not r.token_times
                or r.token_times[0] > self.t_end]

    def summary(self) -> str:
        late = [x * 1e3 for x in self.lateness]
        failed = self.failed()
        return (f"{len(self.requests)} scheduled, "
                f"{sum(1 for r in self.requests if r.sent)} sent, "
                f"{len(self.sampled())} sampled, {len(failed)} failed "
                f"{[r.failed or 'no first token' for r in failed][:3]}, "
                f"{sum(r.done and not r.failed for r in self.requests)} "
                f"completed; in flight {self.inflight_start} at sampling "
                f"start, {self.inflight_end} at window end; generator late "
                f"p50 {percentile(late, 50)} ms max {max(late, default=None)}"
                f" ms; steps {sum(len(s) for s in self.steps.values())}")


class RoundRobin:
    """The router a ``"router": "rr"`` mix asks for."""

    def __init__(self, pods):
        self.pods = list(pods)
        self.n = 0

    def route(self, _tokens, _model) -> str:
        pod = self.pods[self.n % len(self.pods)]
        self.n += 1
        return pod


class Replica(threading.Thread):
    """Owns one engine."""

    def __init__(self, pod: str, fleet: Fleet, run: Run, window: int | None,
                 finished: "queue.Queue | None"):
        super().__init__(name=f"replica-{pod}", daemon=True)
        self.pod, self.eng, self.run_rec = pod, fleet.engines[pod], run
        self.inbox: collections.deque = collections.deque()
        self.wake = threading.Event()
        self.stop = threading.Event()
        self.max_batch = self.eng.cfg.max_batch
        self.window = window        # attention window in tokens, or None
        self.finished = finished    # closed loop: records of done requests
        self.waiting: collections.deque = collections.deque()
        self.prefilling: list = []  # records admitted, no first token yet
        self.live: dict = {}        # request id -> record
        self.steps: list = []
        self.error: Optional[BaseException] = None

    # -- called by the generator thread --

    def put(self, rec: RequestRecord) -> None:
        self.inbox.append(rec)
        self.wake.set()

    # -- the thread --

    def run(self) -> None:
        try:
            self._serve()
        except BaseException as exc:  # reported by the main thread
            self.error = exc

    def _fail(self, rec: RequestRecord, why: str) -> None:
        rec.failed = why
        rec.done = True
        if self.finished is not None:
            self.finished.put(rec)

    def _admit(self) -> None:
        while self.inbox:
            self.waiting.append(self.inbox.popleft())
        eng = self.eng
        while self.waiting and len(eng.requests) < self.max_batch:
            rec = self.waiting[0]
            t0 = now()
            try:
                with Span("enqueue"):
                    req = eng.enqueue(f"r{rec.idx}", rec.arrival.prompt,
                                      max_new_tokens=rec.max_new)
            except RuntimeError as exc:
                if "out of KV pages" not in str(exc):
                    raise
                if eng.requests:
                    return  # pages come back as running requests finish
                self.waiting.popleft()
                self._fail(rec, "refused: no pages on an idle replica")
                continue
            except ValueError as exc:
                self.waiting.popleft()
                self._fail(rec, f"refused: {exc}")
                continue
            t1 = now()
            self.run_rec.span("enqueue", t0, t1)
            self.waiting.popleft()
            rec.req, rec.enqueued = req, t1
            rec.cached_len = req.cached_len
            rec.hbm_hit_blocks = req.hbm_hit_blocks
            self.live[req.request_id] = rec
            self.prefilling.append(rec)

    def _step(self) -> bool:
        """One ``engine.step()`` and its bookkeeping; False when the step
        had nothing to run (a restore in flight)."""
        eng, window = self.eng, self.window
        head = [(rec, rec.req.computed_len) for rec in self.prefilling]
        decode_ctx = 0
        for rec in self.live.values():
            if rec.token_times:
                ctx = rec.prompt_len + len(rec.token_times)
                decode_ctx += min(ctx, window) if window else ctx
        t0 = now()
        with Span("step", pod=self.pod):
            emitted = eng.step()
        t1 = now()
        self.run_rec.span("step", t0, t1)

        pre_tokens = pre_pos = 0
        pre_done = False
        for rec, before in head:
            req = rec.req
            if rec.restore_t0 is None and (req.restore_job is not None
                                           or req.restored_blocks):
                rec.restore_t0 = t0
            if rec.restore_t1 is None and req.restored_blocks:
                rec.restore_t1 = t1
                rec.restored_blocks = req.restored_blocks
                # A restore moves the cached prefix; the prefill that
                # follows starts after it.
                rec.cached_len = max(rec.cached_len, req.cached_len)
                before = max(before, req.cached_len)
            if req.computed_len > before:
                pre_tokens, pre_pos = req.computed_len - before, before
                if rec.first_sched is None:
                    rec.first_sched = t0
        decode_rows = len(emitted)
        for rid in emitted:
            rec = self.live[rid]
            if not rec.token_times:
                pre_done = True
                decode_rows -= 1
                self.prefilling.remove(rec)
                if rec.first_sched is None:
                    rec.first_sched = t0
            rec.token_times.append(t1)
            if rec.req.done:
                self._finish(rid, rec)
        self.steps.append(StepRecord(t0, t1, pre_tokens, pre_pos, pre_done,
                                     decode_rows, decode_ctx))
        with Span("step.work", pod=self.pod, prefill_tokens=pre_tokens,
                  prefill_pos=pre_pos, decode_rows=decode_rows,
                  decode_ctx=decode_ctx):
            pass
        return bool(emitted) or pre_tokens > 0

    def _finish(self, rid: str, rec: RequestRecord) -> None:
        del self.live[rid]
        out = rec.req.output
        vocab = self.eng.cfg.model.vocab_size
        rec.tokens_ok = (len(out) == rec.max_new
                         and all(0 <= t < vocab for t in out))
        rec.done = True
        rec.req = None
        if self.finished is not None:
            self.finished.put(rec)

    def _serve(self) -> None:
        eng = self.eng
        while not self.stop.is_set():
            self._admit()
            if eng.requests:
                if not self._step():
                    t0 = now()
                    with Span("restore.wait"):
                        time.sleep(0.0005)
                    self.run_rec.span("restore.wait", t0, now())
            else:
                self.wake.clear()
                if not self.inbox:
                    t0 = now()
                    self.wake.wait(0.05)
                    self.run_rec.span("replica.idle", t0, now())

    def alive_requests(self) -> int:
        return len(self.live) + len(self.waiting) + len(self.inbox)


def _send(rec: RequestRecord, router, fleet: Fleet, replicas: dict,
          run: Run) -> None:
    t0 = now()
    with Span("route"):
        pod = router.route(rec.arrival.prompt, fleet.model_name)
    t1 = now()
    run.span("route", t0, t1)
    rec.sent, rec.pod, rec.route_s = t0, pod, t1 - t0
    replicas[pod].put(rec)


def _generate_open(records, router, fleet, replicas, run, stop) -> None:
    for rec in records:
        due = run.t_start + rec.arrival.due
        wait = due - now()
        if wait > 0:
            t0 = now()
            with Span("generator.sleep"):
                if stop.wait(wait):
                    return
            run.span("generator.sleep", t0, now())
        elif stop.is_set():
            return
        rec.start = due
        rec.sampled = run.t_sample <= due < run.t_sample_end
        _send(rec, router, fleet, replicas, run)
        run.lateness.append(rec.sent - due)


def _generate_closed(records, router, fleet, replicas, run, stop,
                     finished) -> None:
    by_client: dict = collections.defaultdict(collections.deque)
    for rec in records:
        by_client[rec.arrival.client].append(rec)

    def send_next(client) -> None:
        if by_client[client]:
            rec = by_client[client].popleft()
            _send(rec, router, fleet, replicas, run)
            rec.start = rec.sent
            rec.sampled = run.t_sample <= rec.sent < run.t_sample_end

    for client in sorted(by_client):
        send_next(client)
    while not stop.is_set():
        try:
            rec = finished.get(timeout=0.05)
        except queue.Empty:
            continue
        send_next(rec.arrival.client)


def wait_for_slice(stop: threading.Event, seconds: float,
                   steps: Optional[int], steps_done) -> str:
    """Block until ``seconds`` have passed, ``steps_done()`` has grown by
    ``steps``, or ``stop`` is set, and say which: ``"seconds"``,
    ``"steps"`` or ``"stop"``. The count is polled every 50 ms: the slice
    may run a few steps over, never short."""
    if steps is None:
        return "stop" if stop.wait(seconds) else "seconds"
    until, first = now() + seconds, steps_done()
    while True:
        left = until - now()
        if left <= 0:
            return "seconds"
        if steps_done() - first >= steps:
            return "steps"
        if stop.wait(min(0.05, left)):
            return "stop"


def serve(fleet: Fleet, schedule, traffic: dict, seconds: float,
          compile_count, at_fraction=None) -> Run:
    """Run one window of ``seconds`` over ``schedule.arrivals``.

    ``compile_count()`` reads the process's count of compiled programs.
    ``at_fraction`` is ``(fraction, seconds, steps, start, stop)``: the
    tracer's two calls, made ``fraction`` into the window and, from a thread
    of their own so that a slow ``stop`` stalls no request, ``seconds`` or
    ``steps`` engine steps later, whichever comes first (``steps`` None:
    by time alone). ``stop`` costs the profiler time for every device op
    of the slice, so a slice bounded by time alone costs a faster engine
    more; bounded by steps as well it costs what it costs today.
    """
    run = Run(seconds=seconds, loop=traffic["loop"], traffic=traffic)
    records = [RequestRecord(idx=i, arrival=a, prompt_len=len(a.prompt),
                             max_new=a.max_new)
               for i, a in enumerate(schedule.arrivals)]
    run.requests = records
    closed = traffic["loop"] == "closed"
    finished = queue.Queue() if closed else None
    window = fleet.cfg.sliding_window
    replicas = {pod: Replica(pod, fleet, run, window, finished)
                for pod in fleet.engines}
    router = (RoundRobin(fleet.engines) if traffic.get("router") == "rr"
              else fleet.router)
    fleet.on_ingest = lambda t0, t1, _pod: run.span("ingest", t0, t1)
    stop = threading.Event()

    run.pool_before = {p: e.block_manager.pool_stats()
                       for p, e in fleet.engines.items()}
    compiles0 = compile_count()
    run.t_start = now()
    run.t_sample = run.t_start + seconds * float(traffic["warm_fraction"])
    run.t_sample_end = run.t_start + seconds * (
        1.0 - float(traffic.get("tail_fraction", 0.0)))
    run.t_end = run.t_start + seconds
    if closed:
        gen = threading.Thread(
            target=_generate_closed, name="generator", daemon=True,
            args=(records, router, fleet, replicas, run, stop, finished))
    else:
        gen = threading.Thread(
            target=_generate_open, name="generator", daemon=True,
            args=(records, router, fleet, replicas, run, stop))
    threads = [*replicas.values(), gen]

    tracer = None
    if at_fraction is not None:
        fraction, trace_s, trace_steps, start_trace, stop_trace = at_fraction

        def steps_done() -> int:
            return sum(len(r.steps) for r in replicas.values())

        def trace_slice() -> None:
            if stop.wait(max(0.0, run.t_start + fraction * seconds - now())):
                return
            t0 = now()
            start_trace()
            t_on = now()
            ended_by = wait_for_slice(stop, trace_s, trace_steps, steps_done)
            t_off = now()
            stop_trace()
            t1 = now()
            # The stop is library code, on this thread so that it stalls no
            # request; what of it lies past the window's end the run waits
            # for (in its join of this thread).
            run.tracer.update({
                "start_trace": t_on - t0, "slice": t_off - t_on,
                "ended_by": ended_by, "stop_trace": t1 - t_off,
                "stop_trace.past_end": max(0.0, t1 - max(t_off, run.t_end))})

        tracer = threading.Thread(target=trace_slice, name="tracer",
                                  daemon=True)
        threads.append(tracer)

    for t in threads:
        t.start()
    time.sleep(max(0.0, run.t_sample - now()))
    run.inflight_start = sum(r.alive_requests() for r in replicas.values())
    time.sleep(max(0.0, run.t_end - now()))
    run.inflight_end = sum(r.alive_requests() for r in replicas.values())
    run.compiles_in_window = compile_count() - compiles0
    stop.set()
    for r in replicas.values():
        r.stop.set()
        r.wake.set()
    for t in threads:
        t0 = now()
        t.join(timeout=120.0)
        key = "join.tracer" if t is tracer else "join.serving"
        run.stages[key] = run.stages.get(key, 0.0) + now() - t0
        if t.is_alive():
            run.errors.append(f"thread {t.name} did not stop")
    fleet.on_ingest = None
    for pod, r in replicas.items():
        if r.error is not None:
            run.errors.append(f"{pod}: {type(r.error).__name__}: {r.error}")
        run.steps[pod] = r.steps
        # What is still running when the window ends is aborted: the next
        # window (a sweep's) starts from idle engines.
        for rid in list(r.eng.requests):
            r.eng.abort_request(rid)
    run.pool_after = {p: e.block_manager.pool_stats()
                      for p, e in fleet.engines.items()}
    return run


def serve_to_completion(fleet: Fleet, arrivals,
                        limit_s: float = 900.0) -> list:
    """Set-up traffic: route and serve ``arrivals`` one after another on the
    calling thread, each to its last token. Returns the engines' requests."""
    done = []
    for i, a in enumerate(arrivals):
        pod = fleet.router.route(a.prompt, fleet.model_name)
        eng = fleet.engines[pod]
        req = eng.enqueue(f"setup{i}", a.prompt, max_new_tokens=a.max_new)
        deadline = now() + limit_s
        while not req.done:
            if not eng.step():
                time.sleep(0.0005)
            if now() > deadline:
                raise TimeoutError(f"set-up request {i} is stuck")
        done.append((pod, req))
    return done
