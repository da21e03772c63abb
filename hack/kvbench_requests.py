#!/usr/bin/env python3
"""One run of a cell as ``kvbench/run.py`` makes it, with the window's
requests dumped before the result line: for every request its replica,
prompt, what of it was cached at admission, its wait for the first step,
its time to the first token and its longest gap between two tokens, so
that a median which stands between a cluster of hits and one of misses
can be seen for what it is. Nothing of the measurement changes: the dump
is written after the window, where ``run.py`` decides ``correct``, to
stderr (``run.py``'s own log goes to stdout, ahead of its result line).

  chiprun --timeout 1200 -- python3 hack/kvbench_requests.py \\
      --workload solar-open2-ep16-l8.sessions-64k --seed 7 --seconds 50
  python3 hack/kvbench_requests.py --workload <cell> --seconds 12 --rehearse
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench import run as bench_run  # noqa: E402


def dump(run) -> None:
    def ms(a, b):
        return "-" if a is None or b is None else f"{(b - a) * 1e3:.0f}"

    for r in sorted(run.requests, key=lambda r: r.start):
        first = r.token_times[0] if r.token_times else None
        gaps = [(b - a, b) for a, b in zip(r.token_times, r.token_times[1:])]
        longest, at = max(gaps, default=(0.0, run.t_start))
        print(f"[requests] at {r.start - run.t_start:6.2f}s "
              f"{'sampled' if r.sampled else 'unsampled'} {r.pod} "
              f"prompt {r.prompt_len} cached {r.cached_len} "
              f"uncached {r.prompt_len - r.cached_len} "
              f"wait_ms {ms(r.start, r.first_sched)} "
              f"ttft_ms {ms(r.start, first)} tokens {len(r.token_times)} "
              f"longest_gap_ms {longest * 1e3:.0f} ending at "
              f"{at - run.t_start:.2f}s"
              f"{' failed ' + r.failed if r.failed else ''}",
              file=sys.stderr)


def main(argv=None) -> int:
    decide = bench_run.correctness

    def correctness(ctx, run):
        dump(run)
        return decide(ctx, run)

    bench_run.correctness = correctness
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
