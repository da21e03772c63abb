#!/usr/bin/env python3
"""Why a line put into a function on the stack of a jitted call has cost
every program seconds of tracing and lowering (PERF.md §6, PR 47; ROADMAP
S7): CPython 3.11+ keeps a thread's frames in chunks of 16 KiB, a frame that
does not fit the current chunk gets a new one, and the chunk is freed when
that frame returns. A call site whose callee is the first frame of a chunk
therefore maps and unmaps memory on every call: about 8 us where a call
costs 0.05. Which call sites lie there is decided by the summed sizes of the
frames below them (a frame is its code's locals + stack + 10 words or so),
so a new local, a new frame or a longer expression anywhere below moves it.

This prints the depths of a plain recursion at which a leaf call falls off
that cliff, then the same under a trampoline whose own frame is 64 KiB: it
gets a chunk of 128 KiB and the 60 KiB of frames above it lie in that one
chunk. Runs anywhere in seconds; no JAX.
"""

from __future__ import annotations

import time


def big_frame(slots: int = 8200):
    """``tramp(fn, *args, **kw)`` calling ``fn`` from a frame of ``slots``
    locals (never assigned: the compiler drops the branch and keeps the
    names)."""
    names = "=".join(f"v{i}" for i in range(slots))
    scope: dict = {}
    exec(f"def tramp(fn, *a, **k):\n    if 0:\n        {names}=None\n"
         f"    return fn(*a, **k)\n", scope)
    return scope["tramp"]


def leaf(a=1, b=2):
    return a + b


def hot(calls):
    for _ in range(calls):
        leaf()


def deep(depth, calls):
    return hot(calls) if depth == 0 else deep(depth - 1, calls)


def cliffs(call, depths=700, calls=20_000):
    took = []
    for depth in range(depths):
        start = time.perf_counter()
        call(depth, calls)
        took.append(time.perf_counter() - start)
    median = sorted(took)[len(took) // 2]
    return median / calls * 1e6, [
        (depth, round(t / calls * 1e6, 2)) for depth, t in enumerate(took)
        if t > 10 * median]


def main() -> None:
    tramp = big_frame()
    for name, call in (("plain", deep), ("under a 64 KiB frame",
                                         lambda d, n: tramp(deep, d, n))):
        median, slow = cliffs(call)
        print(f"{name}: a leaf call {median:.3f} us at most depths; "
              f"(depth, us a call) over ten times that: {slow}")
    start = time.perf_counter()
    for _ in range(20_000):
        tramp(leaf)
    print(f"a call through the trampoline itself: "
          f"{(time.perf_counter() - start) / 20_000 * 1e6:.1f} us")


if __name__ == "__main__":
    main()
