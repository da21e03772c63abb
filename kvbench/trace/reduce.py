"""From a profiler trace (``.xplane.pb``) to busy time, per-op time, idle
gaps by host span, and the harness's own work markers.

The rules, each pinned by a test on a recorded or synthetic plane:

- Busy time of a chip is the **union** of the op intervals on its ops line
  only ("XLA Ops"; the modules and steps lines cover the same time again),
  clipped to the window. It can never pass the window.
- Over several chips, busy is the **mean** over the cell's device planes of
  each plane's union, never their sum.
- The window is the span of the trace's own timestamps (device ops and the
  harness's host spans), never the host's clock.
- An idle gap is attributed to the host spans that covered it (the
  harness's, and in a traced run the engine's phases), by overlap.
- Every kept host event that is not the work marker is also handed on whole
  (``Reduced.events``: name, start, duration, attributes), so that a reader
  reaches a counter that rides a phase.

``load`` turns a file into plain :class:`Plane` objects; everything else
works on those, so a synthetic plane is three lists.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# The harness's work marker (loop.py): its stats carry what a step did.
WORK_MARKER = "step.work"
NS = 1e-9


@dataclass
class Event:
    name: str
    start: float   # ns
    dur: float     # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Plane:
    name: str
    lines: dict = field(default_factory=dict)  # line name -> [Event]


def op_name(text: str) -> str:
    """The op's own name. A TPU trace names an op by its whole HLO line,
    ``%fusion.12 = bf16[...] fusion(...)``; the name is what stands before
    `` = ``, without the ``%``."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the calls of one kernel in the layers
    of a model differ in that number only."""
    head, dot, tail = name.rpartition(".")
    return head if dot and tail.isdigit() else name


def load(source, span_names: Iterable[str]) -> list:
    """Planes of a trace (the path of an ``.xplane.pb``, or the serialized
    XSpace itself as bytes), keeping what the reduction reads: every
    event of a device plane's ops and modules lines, and of the host plane
    the harness's spans and (on a backend with no device plane, the CPU of
    the rehearsal) the events that carry an ``hlo_module``."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {WORK_MARKER}
    data = (ProfileData.from_serialized_xspace(source)
            if isinstance(source, bytes) else ProfileData.from_file(source))
    has_device = any(DEVICE_PLANE.match(pl.name) for pl in data.planes)
    planes = []
    for pl in data.planes:
        if DEVICE_PLANE.match(pl.name):
            out = Plane(pl.name)
            for ln in pl.lines:
                if ln.name == OPS_LINE:
                    out.lines[ln.name] = [
                        Event(op_name(e.name), e.start_ns, e.duration_ns)
                        for e in ln.events]
                elif ln.name == MODULES_LINE:
                    out.lines[ln.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in ln.events]
            planes.append(out)
        elif pl.name == HOST_PLANE:
            out = Plane(pl.name)
            for i, ln in enumerate(pl.lines):
                evs = []
                for e in ln.events:
                    if e.name in keep:
                        evs.append(Event(e.name, e.start_ns, e.duration_ns,
                                         dict(e.stats)))
                    elif (not has_device and e.duration_ns > 0
                          and not e.name.startswith(("Thread", "end:"))):
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            evs.append(Event(e.name, e.start_ns,
                                             e.duration_ns, stats))
                if evs:
                    # Threads share names ("python3"): one key a line.
                    out.lines[f"{ln.name}#{i}"] = evs
            planes.append(out)
    return planes


# -- intervals ---------------------------------------------------------------


def union(intervals: Iterable[tuple], clip: Optional[tuple] = None) -> list:
    """Disjoint sorted intervals covering the same points, inside ``clip``."""
    xs = []
    for a, b in intervals:
        if clip is not None:
            a, b = max(a, clip[0]), min(b, clip[1])
        if b > a:
            xs.append((a, b))
    xs.sort()
    out: list = []
    for a, b in xs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[tuple]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: list, window: tuple) -> list:
    """What ``window`` holds beside the disjoint sorted ``busy``."""
    out, at = [], window[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def overlap(a: tuple, cover: list) -> float:
    """Length of ``a`` inside the disjoint sorted ``cover``."""
    return sum(max(0.0, min(a[1], d) - max(a[0], c)) for c, d in cover
               if d > a[0] and c < a[1])


def take(pieces: list, cover: list, acc: float = 0.0) -> tuple:
    """``(acc + length of pieces inside cover, pieces outside it)``; both
    lists sorted and disjoint, and so is the result. Each piece bisects to
    the first interval that can reach it and walks on while they touch, so
    an interval is looked at once per piece it touches and once more at
    most: O(pieces * log(cover) + cover). ``acc`` grows piece by piece, a
    piece's intervals summed first: the order that keeps a sum over many
    pieces the same to the last bit however the pieces were found."""
    rest = []
    n = len(cover)
    for a, b in pieces:
        # The last interval that starts at or before ``a`` may reach into
        # the piece; none before it can (they are disjoint).
        i = bisect.bisect_right(cover, (a, float("inf"))) - 1
        if i < 0 or cover[i][1] <= a:
            i += 1
        got, at = 0, a
        while i < n:
            c, d = cover[i]
            if c >= b:
                break
            if c > at:
                rest.append((at, c))
            got += min(b, d) - max(a, c)
            at = d
            i += 1
        acc += got
        if b > at:
            rest.append((at, b))
    return acc, rest


# -- the reduction -----------------------------------------------------------


@dataclass
class Reduced:
    window: tuple                      # ns
    planes: list                       # device plane names, in chip order
    busy: dict                         # plane -> disjoint busy intervals
    ops: dict                          # plane -> [Event] (program in stats)
    modules: dict                      # plane -> [Event]
    spans: dict                        # span name -> disjoint intervals
    work: list                         # stats of the work markers inside
    # span name -> its host events in start order, attributes in ``stats``
    # (an engine phase's: ``pod``, ``step`` and what the phase carries).
    events: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * NS

    @property
    def busy_s(self) -> float:
        """Mean over the cell's chips of each chip's busy seconds."""
        if not self.planes:
            return 0.0
        return sum(total(self.busy[p]) for p in self.planes
                   ) * NS / len(self.planes)

    def op_seconds(self) -> dict:
        """Device seconds by op (``base_name``: one row for a kernel's calls
        in every layer), mean over the chips."""
        out: dict = {}
        for p in self.planes:
            for e in self.ops[p]:
                key = base_name(e.name)
                out[key] = out.get(key, 0.0) + e.dur * NS
        n = max(1, len(self.planes))
        return {k: v / n for k, v in out.items()}

    def idle_by_span(self, priority: list) -> dict:
        """Idle seconds (mean over chips) by what the host was doing: each
        gap is split over the spans that covered it; where spans nest or
        run side by side the one earlier in ``priority`` takes the time,
        and what no span covered is ``"(no span)"``.

        One sweep per name over the sorted pieces still unclaimed and the
        name's sorted, disjoint intervals (:func:`take`): the work grows
        with the gaps plus the intervals, not with their product."""
        out: dict = {}
        for p in self.planes:
            left = gaps(self.busy[p], self.window)
            for name in priority:
                cover = self.spans.get(name, [])
                if not cover or not left:
                    continue
                got, left = take(left, cover, out.get(name, 0.0))
                if got:
                    out[name] = got
            if left:
                out["(no span)"] = out.get("(no span)", 0.0) + total(left)
        n = max(1, len(self.planes))
        return {k: v * NS / n for k, v in out.items()}

    def longest_gaps(self, n: int = 5) -> list:
        """The ``n`` longest idle gaps as (seconds, plane, start_ns)."""
        found = [((b - a) * NS, p, a) for p in self.planes
                 for a, b in gaps(self.busy[p], self.window)]
        return sorted(found, reverse=True)[:n]


def _programs(ops: list, modules: list) -> None:
    """Name, on every op, the module event that contains its start."""
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < mods[i].end:
            e.stats["program"] = mods[i].name


def reduce(planes: list, chips: int, span_names: Iterable[str]) -> Reduced:
    """Reduce the planes of one trace for a cell on ``chips`` chips."""
    span_names = list(span_names)
    device = sorted((pl for pl in planes if DEVICE_PLANE.match(pl.name)),
                    key=lambda pl: int(DEVICE_PLANE.match(pl.name).group(1)))
    host = [pl for pl in planes if pl.name == HOST_PLANE]
    ops: dict = {}
    modules: dict = {}
    if device:
        for pl in device[:chips]:
            ops[pl.name] = list(pl.lines.get(OPS_LINE, []))
            modules[pl.name] = list(pl.lines.get(MODULES_LINE, []))
            _programs(ops[pl.name], modules[pl.name])
    else:
        # No device plane (the CPU backend of the rehearsal): the executed
        # HLO ops are on the host plane, each naming its module.
        evs = [e for pl in host for line in pl.lines.values() for e in line
               if "hlo_module" in e.stats]
        runs: dict = {}
        for e in evs:
            e.stats["program"] = str(e.stats["hlo_module"])
            key = (e.stats["program"], e.stats.get("run_id"))
            lo, hi = runs.get(key, (e.start, e.end))
            runs[key] = (min(lo, e.start), max(hi, e.end))
        if evs:
            ops[HOST_PLANE] = evs
            # One module event per execution: the span of its ops.
            modules[HOST_PLANE] = [Event(prog, lo, hi - lo)
                                   for (prog, _), (lo, hi) in runs.items()]
    names = list(ops)

    kept = set(span_names)
    events: dict = {}
    work = []
    for pl in host:
        for line in pl.lines.values():
            for e in line:
                if e.name == WORK_MARKER:
                    work.append((e.start, e.stats))
                elif e.name in kept:
                    events.setdefault(e.name, []).append(e)
    for evs in events.values():
        evs.sort(key=lambda e: e.start)
    raw_spans = {n: [(e.start, e.end) for e in events.get(n, [])]
                 for n in span_names}

    marks = [t for p in names for e in ops[p] for t in (e.start, e.end)]
    marks += [t for ivs in raw_spans.values() for iv in ivs for t in iv]
    marks += [t for t, _ in work]
    if not marks:
        raise ValueError("the trace holds no device op and no harness span")
    window = (min(marks), max(marks))
    busy = {p: union(((e.start, e.end) for e in ops[p]), clip=window)
            for p in names}
    spans = {n: union(ivs, clip=window) for n, ivs in raw_spans.items()}
    inside = [s for t, s in sorted(work, key=lambda w: w[0])
              if window[0] <= t <= window[1]]
    return Reduced(window=window, planes=names, busy=busy, ops=ops,
                   modules=modules, spans=spans, work=inside, events=events)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
