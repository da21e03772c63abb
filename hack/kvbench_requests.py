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
when it finishes.

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


def main(argv=None) -> int:
    decide = bench_run.correctness
    matched: dict = {}
    note_matched(matched)

    def correctness(ctx, run):
        dump(run, matched)
        return decide(ctx, run)

    bench_run.correctness = correctness
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
