"""Look at one trace by hand: planes, lines, how many events, their time
range, the names that take most time, and which stats they carry.

    python3 kvbench/trace/dump.py <file.xplane.pb> [--top N]
"""

from __future__ import annotations

import argparse
import collections


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(args.path)
    for pl in data.planes:
        print(f"PLANE {pl.name!r}")
        for ln in pl.lines:
            n, lo, hi = 0, float("inf"), float("-inf")
            by_name = collections.defaultdict(lambda: [0, 0.0])
            stat_keys = collections.Counter()
            sample = {}
            for e in ln.events:
                n += 1
                lo, hi = min(lo, e.start_ns), max(hi, e.start_ns
                                                  + e.duration_ns)
                slot = by_name[e.name]
                slot[0] += 1
                slot[1] += e.duration_ns
                if slot[0] == 1:
                    stats = dict(e.stats)
                    stat_keys.update(stats.keys())
                    sample[e.name] = {k: str(v)[:80]
                                      for k, v in list(stats.items())[:8]}
            if not n:
                continue
            print(f"  LINE {ln.name!r}: {n} events, "
                  f"{lo * 1e-9:.6f}s .. {hi * 1e-9:.6f}s")
            top = sorted(by_name.items(), key=lambda kv: -kv[1][1])
            for name, (cnt, dur) in top[:args.top]:
                print(f"     {dur * 1e-9:10.6f}s x{cnt:<6} {name[:100]}  "
                      f"{sample.get(name, '')}")
            print(f"     stat keys: {dict(stat_keys.most_common(12))}")


if __name__ == "__main__":
    main()
