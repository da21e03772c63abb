#!/usr/bin/env python3
"""One run of a cell as ``kvbench/run.py`` makes it, with the window's
requests dumped before the result line: for every request its replica,
prompt, what of it was cached at admission, its wait for the first step,
its time to the first token and its longest gap between two tokens, so
that a median which stands between a cluster of hits and one of misses
can be seen for what it is. Where the model keeps a sequence state beside
its pages, ``matched`` is the tokens of pages that matched at admission
(``Request.page_hit_blocks``): ``cached`` short of it is a miss for want
of a state to resume from, both short of the prompt one for want of
pages; a last line a replica gives its pools' evictions, and the state
pool's ``state_replaced``, in set-up and in the window. Nothing of the
measurement changes: the dump is written after the window, where ``run.py`` decides ``correct``, to stderr (``run.py``'s own
log goes to stdout, ahead of its result line); ``matched`` is noted as
``enqueue`` returns, since the harness lets go of the engine's request
when it finishes. With ``--trace 1`` every ``request.first_token`` marker
of the traced slice follows, one line a request as its engine told it
(``kvbench/metrics/_first_token.py``): queued, of which behind another's
chunks, its prefill and its own chunks' device time inside it, and what
its time to the first token holds outside the engine; then one line for
the slice (``first_token_summary``): the split of its median first token,
and three checks of the markers against the trace around them. In a cell
that lists none of the five readers too.

  chiprun --timeout 1200 -- python3 hack/kvbench_requests.py \\
      --workload solar-open2-ep16-l8.sessions-64k --seed 7 --seconds 50
  python3 hack/kvbench_requests.py --workload <cell> --seconds 12 --rehearse
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench import run as bench_run  # noqa: E402
from llmd_kv_cache_tpu.models.engine import MiniEngine  # noqa: E402


# ``pool_stats()``'s lifetime counts the dump's last lines give.
COUNTERS = ("evictions", "state_evictions", "state_orphaned",
            "state_replaced")


def note_matched(matched: dict) -> None:
    """Have every engine's ``enqueue`` leave ``(pod, request id) -> tokens
    of pages matched`` in ``matched``, where it keeps a state pool."""
    enqueue = MiniEngine.enqueue

    def noting(self, request_id, *args, **kwargs):
        req = enqueue(self, request_id, *args, **kwargs)
        if self.state_pool is not None:
            matched[self.cfg.pod_identifier, request_id] = (
                req.page_hit_blocks * self.cfg.model.page_size)
        return req

    MiniEngine.enqueue = noting


def dump(run, matched: dict) -> None:
    def ms(a, b):
        return "-" if a is None or b is None else f"{(b - a) * 1e3:.0f}"

    for r in sorted(run.requests, key=lambda r: r.start):
        first = r.token_times[0] if r.token_times else None
        gaps = [(b - a, b) for a, b in zip(r.token_times, r.token_times[1:])]
        longest, at = max(gaps, default=(0.0, run.t_start))
        pages = matched.get((r.pod, f"r{r.idx}"))
        print(f"[requests] at {r.start - run.t_start:6.2f}s "
              f"{'sampled' if r.sampled else 'unsampled'} {r.pod} "
              f"prompt {r.prompt_len} "
              f"{f'matched {pages} ' if pages is not None else ''}"
              f"cached {r.cached_len} "
              f"uncached {r.prompt_len - r.cached_len} "
              f"wait_ms {ms(r.start, r.first_sched)} "
              f"ttft_ms {ms(r.start, first)} tokens {len(r.token_times)} "
              f"longest_gap_ms {longest * 1e3:.0f} ending at "
              f"{at - run.t_start:.2f}s"
              f"{' failed ' + r.failed if r.failed else ''}",
              file=sys.stderr)
    # The pools' lifetime counts at the window's two ends (what stood at
    # its start is the set-up's): pages evicted, and a state pool's own.
    for pod, after in sorted(run.pool_after.items()):
        before = run.pool_before.get(pod, {})
        print(f"[requests] {pod} pools, set-up + window: " + ", ".join(
            f"{k} {before.get(k, 0)} + {v - before.get(k, 0)}"
            for k, v in after.items() if k in COUNTERS), file=sys.stderr)


def first_token_summary(markers: list, run) -> str:
    """One traced slice's split of the median first token (medians do not
    add; the means at the end do), and three checks of every marker:
    ``behind_ns`` within ``queued_ns``; ``queued_ns + prefill_ns`` beside
    the trace's own interval from the end of the request's ``enqueue.admit``
    to the marker; ``chunks`` beside the slice's ``step.dispatch``es that
    name the request, where it holds the first."""
    from kvbench.harness.stats import percentile
    from kvbench.metrics import _first_token, _launches, _read

    ms = _first_token.MS

    def p50(values):
        got = percentile([v for v in values if v is not None], 50)
        return None if got is None else round(got, 3)

    joined = [m for m in markers if m.outside_ms is not None]
    whole = [m for m in markers if m.own_device_ns is not None]
    admits = {str(e.stats.get("request_id")): e
              for e in _read.phase_events(run, "enqueue.admit")}
    drift = [round(abs(m.engine_ns - (m.event.start
                                      - admits[m.request_id].end)) * ms, 3)
             for m in markers if m.request_id in admits]
    mine = {m.request_id: m for m in markers}
    dispatched = dict.fromkeys(mine, 0)
    launches = []
    for d in _read.phase_events(run, _launches.DISPATCH):
        if "launch" in d.stats:
            launches.append(int(d.stats["launch"]))
        m = _first_token.owner(mine, d)
        if m is not None:
            dispatched[m.request_id] += 1
    held = [m for m in markers if m.first_launch >= min(launches, default=0)]
    total = {k: sum(getattr(m, k) for m in markers) * ms
             for k in ("queued_ns", "behind_ns", "prefill_ns")}
    n = max(1, len(joined))
    inside = sum(m.engine_ns for m in joined) * ms / n
    outside = sum(m.outside_ms for m in joined) / n
    return (
        f"{len(markers)} markers, {len(joined)} joined, "
        f"{sum(1 for m in markers if m.cached_tokens > 0)} with "
        f"cached_tokens > 0 (p50 {p50(m.cached_tokens for m in markers)} "
        f"of prompt_tokens {p50(m.prompt_tokens for m in markers)}); ms "
        f"p50 of: due -> first token "
        f"{p50(m.outside_ms + m.engine_ns * ms for m in joined)}, its route {p50(m.record.route_s * 1e3 for m in joined)}, outside "
        f"the engine {p50(m.outside_ms for m in joined)}, queued "
        f"{p50(m.queued_ns * ms for m in markers)}, of which behind "
        f"another's chunks {p50(m.behind_ns * ms for m in markers)} "
        f"(behind_chunks p50 {p50(m.behind_chunks for m in markers)}, max "
        f"{max((m.behind_chunks for m in markers), default=None)}), "
        f"prefill_ns {p50(m.prefill_ns * ms for m in markers)}, of which "
        f"own chunks on the device "
        f"{p50(m.own_device_ns * ms for m in whole)} over the {len(whole)} "
        f"whose chunks are all placed (chunks p50 "
        f"{p50(m.chunks for m in markers)}, decodes_between p50 "
        f"{p50(m.decodes_between for m in markers)}); sums ms: queued "
        f"{total['queued_ns']:.3f} behind {total['behind_ns']:.3f} prefill "
        f"{total['prefill_ns']:.3f}; means ms over the joined: due -> first "
        f"token {inside + outside:.3f} = engine {inside:.3f} + outside "
        f"{outside:.3f}; checks: behind_ns > queued_ns in "
        f"{sum(1 for m in markers if m.behind_ns > m.queued_ns)}; "
        f"queued_ns + prefill_ns off the trace's (enqueue.admit's end -> "
        f"marker) by at most {max(drift, default=None)} ms over "
        f"{len(drift)} whose admission the slice holds; chunks differ from "
        f"the slice's dispatches in "
        f"{sum(1 for m in held if dispatched[m.request_id] != m.chunks)} of "
        f"{len(held)} whose first chunk it holds")


def dump_first_tokens(run) -> None:
    from kvbench.metrics import _first_token

    def ms(ns):
        return "-" if ns is None else f"{ns * 1e-6:.1f}"

    markers = _first_token.of(run)
    if markers is None:
        return
    for m in markers:
        rec = m.record
        print(f"[first_token] {m.request_id} {m.event.stats.get('pod')} "
              f"prompt {m.prompt_tokens} cached {m.cached_tokens} chunks "
              f"{m.chunks} launches {m.first_launch}-{m.last_launch} "
              f"decodes_between {m.decodes_between} queued_ms "
              f"{ms(m.queued_ns)} behind_ms {ms(m.behind_ns)} "
              f"behind_chunks {m.behind_chunks} prefill_ms "
              f"{ms(m.prefill_ns)} own_device_ms {ms(m.own_device_ns)}"
              + ("" if rec is None else
                 f" due at {rec.start - run.t_start:.2f}s route_ms "
                 f"{rec.route_s * 1e3:.1f} ttft_ms "
                 + ("- outside_ms -" if m.outside_ms is None else
                    f"{(rec.token_times[0] - rec.start) * 1e3:.1f} "
                    f"outside_ms {m.outside_ms:.1f}")),
              file=sys.stderr)
    print(f"[first_tokens] {first_token_summary(markers, run)}",
          file=sys.stderr)


def main(argv=None) -> int:
    decide = bench_run.correctness
    matched: dict = {}
    note_matched(matched)

    def correctness(ctx, run):
        dump(run, matched)
        dump_first_tokens(run)
        return decide(ctx, run)

    bench_run.correctness = correctness
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
