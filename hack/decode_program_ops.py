#!/usr/bin/env python3
"""A kept trace's decode programs split by op: how many there are and their
median, every op's microseconds a program (an op that holds others, a
``cond``, beside the ops inside it), then one ``cond`` split by the ops
that start inside it, each with the shape it writes (a trace names an XLA
``fusion`` by a number: its output tells the index-key gather from the
weights' stream), and the ops whose HLO line matches ``--find``.

  python3 kvbench/run.py --workload <cell> ... --trace 1 --keep-trace .kvbench_tmp/t.xplane.pb
  python3 hack/decode_program_ops.py .kvbench_tmp/t.xplane.pb [--find 'bf16\\[4224,64,128\\]']

A kept trace of a real cell is some 55 MiB and does not fit what a chip call
brings back: run this in the call, and bring its output.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import re
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench.trace import reduce as R  # noqa: E402


def _wrote(text: str) -> str:
    """``%fusion.3 = bf16[4224,64,128]{...} fusion(...)`` -> the shape."""
    found = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", text)
    return found.group(1) if found else ""


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--program", default="forward_decode_pallas")
    ap.add_argument("--holder", default=r"^cond",
                    help="the ops that are split by what starts inside them")
    ap.add_argument("--find", default="",
                    help="count the program's ops whose HLO line matches")
    ap.add_argument("--top", type=int, default=30)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(args.path)
    plane = next(pl for pl in data.planes if R.DEVICE_PLANE.match(pl.name))
    lines = {ln.name: ln for ln in plane.lines}
    programs = sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for e in lines[R.MODULES_LINE].events if args.program in e.name)
    if not programs:
        raise SystemExit(f"no program named *{args.program}* in the trace")
    starts = [a for a, _ in programs]
    ops = []  # (start, end, name, what it writes, the HLO line)
    for e in lines[R.OPS_LINE].events:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < programs[i][1]:
            ops.append((e.start_ns, e.start_ns + e.duration_ns,
                        R.op_name(e.name), _wrote(e.name), e.name))
    n = len(programs)
    durs = [b - a for a, b in programs]
    print(f"{args.program}: {n} programs, {sum(durs) * 1e-9:.4f} s, median "
          f"{statistics.median(durs) * 1e-6:.3f} ms, mean "
          f"{sum(durs) / n * 1e-6:.3f} ms")
    by = collections.defaultdict(lambda: [0, 0])
    for a, b, name, _, _ in ops:
        slot = by[R.base_name(name)]
        slot[0] += 1
        slot[1] += b - a
    print("us a program, by op:")
    for name, (count, ns) in sorted(by.items(),
                                    key=lambda kv: -kv[1][1])[:args.top]:
        print(f"  {ns * 1e-3 / n:9.1f}  x{count / n:6.1f}  {name}")

    holder = re.compile(args.holder)
    held = sorted((a, b) for a, b, name, _, _ in ops if holder.search(name))
    if held:
        inside = collections.defaultdict(lambda: [0, 0])
        at = [a for a, _ in held]
        for a, b, name, wrote, _ in ops:
            i = bisect.bisect_right(at, a) - 1
            if i >= 0 and a < held[i][1] and not holder.search(name):
                slot = inside[(R.base_name(name), wrote)]
                slot[0] += 1
                slot[1] += b - a
        each = sum(b - a for a, b in held) / len(held)
        print(f"{len(held)} ops named {args.holder}, {each * 1e-3:.1f} us "
              "each; inside one, by op and what it writes:")
        for (name, wrote), (count, ns) in sorted(
                inside.items(), key=lambda kv: -kv[1][1])[:args.top]:
            print(f"  {ns * 1e-3 / len(held):9.1f} us  x{count / len(held):4.1f}"
                  f"  {name}  {wrote}")
    if args.find:
        rx = re.compile(args.find)
        hits = [(b - a, name) for a, b, name, _, line in ops
                if rx.search(line.split(" = ", 1)[-1].split("{", 1)[0])]
        print(f"ops that write {args.find}: {len(hits)}"
              + (f", {sum(d for d, _ in hits) * 1e-3 / n:.1f} us a program "
                 f"({sorted({R.base_name(h) for _, h in hits})})"
                 if hits else ""))


if __name__ == "__main__":
    main()
