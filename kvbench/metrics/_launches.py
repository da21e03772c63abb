"""Every step program of the traced slice beside the phases that launched it
and waited for it: what ``launch_lag_ms_p50``, ``readback_lag_ms_p50`` and
``fetch_other_pod_share`` read, paired once a run (``of(run)``).

The engine's ``step.dispatch`` names what it launched (``program``: the
jitted function's name; ``launch``: the program's ordinal among all that the
process sent to that device) and the ``step.fetch`` that reads a program's
tokens carries that ``launch`` (``telemetry/tracing.py``). A device runs what
one process sends it in that order, so the step programs on the modules line
in start order are the dispatches in ``launch`` order, but for what the slice
cut: programs at its head that were launched before it began, dispatches at
its tail whose programs it does not hold. The one unknown is that offset, and
it is the one under which the most pairs keep every rule (the names agree; a
program starts no earlier than its dispatch opened and ends no later than its
fetch returned), the smallest where several do as well: under a host clock
that lies off the device's no offset keeps them, and none is preferred.

Under that offset a pair whose names differ cannot be placed without a guess
and is dropped, and a program with no dispatch is ``unpaired`` with it. A
chunk dispatched ahead of the device has no fetch and is placed by its order
alone. A placed program that breaks a rule of time is a ``clock_fault``: its
owner is known (``placed``), its times are not to be trusted, and it is kept
out of ``pairs``. Where such faults are more than a rare exception, the
profiler laid the device's timeline off the host's for the whole slice (by
0.5-1.4 ms in one traced run of two, my chip runs, PR 38: PERF.md §6); the
pairs that still fit are then those with the longest lags, so the readers of
times (``timed()``) read every placed program, shifted as it is (a lag below
zero says so), and the run's ``launches:`` line says by how much.

A program older than the attributes (the parent's) opens the same phases
without them: its dispatches are taken in the order they ended, a prefill's
told by the ``prefill_pos`` it has carried since PR 25, and a fetch belongs
to the dispatch its replica closed last before it.

One chip: replicas that share a device. A cell on several gives nothing here
(a dispatch does not say which plane its program ran on).
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Optional

from kvbench.harness.fleet import log
from kvbench.harness.stats import percentile
from kvbench.metrics import _read

DISPATCH, FETCH = "step.dispatch", "step.fetch"
# What the parent's two step programs are jitted under (``llama.PROGRAM_*``).
PREFILL, DECODE = "forward_prefill_pallas", "forward_decode_pallas"
# Offsets tried: dispatches whose programs the device trace began too late
# for, and programs launched before the slice (a replica with nothing to
# decode runs a prompt's chunks ahead: 64 of them for a document of 32 k).
BEFORE, AFTER = 8, 128
MS = 1e-6


@dataclass
class Pair:
    program: object             # the device event (trace/reduce.py: Event)
    dispatch: object            # its ``step.dispatch``
    fetch: Optional[object]     # the ``step.fetch`` that read its tokens
    waited_from: float          # its dispatch's start, or the end of what
                                # the chip ran before it if that is later
    off: float = 0.0            # ns it lies outside its phases; 0: sound

    @property
    def pod(self):
        return self.dispatch.stats.get("pod")


@dataclass
class Launches:
    placed: list = field(default_factory=list)  # in the device's order
    programs: int = 0           # step programs on the modules line
    numbered: bool = False      # the dispatches carried ``launch``
    offset: int = 0             # programs before the first dispatch's

    @property
    def pairs(self) -> list:
        """The placed programs that lie inside their phases."""
        return [p for p in self.placed if not p.off]

    @property
    def unpaired(self) -> int:
        """Step programs cut by the slice, or not placed without a guess."""
        return self.programs - len(self.placed)

    @property
    def clock_faults(self) -> int:
        return sum(1 for p in self.placed if p.off)

    @property
    def worst_fault_ms(self) -> float:
        """The furthest a placed program lies outside its phases: the
        least the two clocks differ by."""
        return max((p.off for p in self.placed), default=0.0) * MS

    def timed(self) -> list:
        """What a reader of times reads: the sound pairs where a fault is
        the exception (under one program in twenty), else every placed
        program: the clock is what is off, and the pairs that still fit
        are those with the longest lags."""
        if 20 * self.clock_faults <= len(self.placed):
            return self.pairs
        return self.placed

    def lone_gaps_ms(self) -> list:
        """The idle time between two programs of one replica where the
        second was dispatched to an idle chip: a lone replica's round
        trip (ROADMAP S4)."""
        return [(b.program.start - a.program.end) * MS
                for a, b in zip(self.placed, self.placed[1:])
                if a.pod == b.pod and b.waited_from == b.dispatch.start]

    def summary(self) -> str:
        lone = self.lone_gaps_ms()
        starts = [(p.program.start - p.dispatch.start) * MS
                  for p in self.placed]
        ends = [(p.fetch.end - p.program.end) * MS for p in self.placed
                if p.fetch is not None]
        # Launch and read-back together, on the host's clock alone.
        both = [(p.fetch.end - p.dispatch.start - p.program.dur) * MS
                for p in self.placed if p.fetch is not None
                and p.waited_from == p.dispatch.start]
        return (f"{self.programs} step programs, {len(self.placed)} placed "
                f"({'by launch' if self.numbered else 'by order: no launch'}"
                f", offset {self.offset}), unpaired {self.unpaired}, "
                f"clock_fault {self.clock_faults} (worst "
                f"{self.worst_fault_ms:.3f} ms); ms min / p50 of a program's "
                f"start after its dispatch opened: {min(starts, default=None)}"
                f" / {percentile(starts, 50)}, of its fetch's end after its "
                f"end: {min(ends, default=None)} / {percentile(ends, 50)}; "
                f"dispatch's start to fetch's end less the program's run, "
                f"on an idle chip, ms p50: {percentile(both, 50)}; "
                f"a lone replica's gap between two programs, ms p50: "
                f"{percentile(lone, 50)} (n={len(lone)})")


def program_of(module_name: str) -> str:
    """``jit_forward_decode_pallas(123)`` -> ``forward_decode_pallas``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _named(dispatch, numbered: bool) -> str:
    if numbered:
        return str(dispatch.stats["program"])
    return PREFILL if "prefill_pos" in dispatch.stats else DECODE


def _fetches(dispatches: list, fetches: list, numbered: bool) -> dict:
    """id(dispatch) -> the fetch that waited for its program."""
    if numbered:
        by_launch = {int(f.stats["launch"]): f for f in fetches
                     if "launch" in f.stats}
        return {id(d): by_launch[int(d.stats["launch"])] for d in dispatches
                if int(d.stats["launch"]) in by_launch}
    by_pod: dict = {}
    for d in sorted(dispatches, key=lambda d: d.end):
        by_pod.setdefault(d.stats.get("pod"), []).append(d)
    out = {}
    for f in fetches:
        mine = by_pod.get(f.stats.get("pod"), [])
        i = bisect.bisect_right([d.end for d in mine], f.start) - 1
        if i >= 0:
            out[id(mine[i])] = f
    return out


def _broken(program, dispatch, fetch) -> float:
    """ns by which the program lies outside its phases; 0 inside."""
    out = max(0.0, dispatch.start - program.start)
    if fetch is not None:
        out = max(out, program.end - fetch.end)
    return out


def pair(dispatches: list, fetches: list, modules: list) -> Launches:
    """``modules``: every event of the chip's modules line."""
    found = Launches(numbered=bool(dispatches) and all(
        "launch" in d.stats and "program" in d.stats for d in dispatches))
    if found.numbered:
        order = sorted(dispatches, key=lambda d: int(d.stats["launch"]))
        first = int(order[0].stats["launch"])
        slots = [int(d.stats["launch"]) - first for d in order]
    else:
        order = sorted(dispatches, key=lambda d: d.end)
        slots = list(range(len(order)))
    names = [_named(d, found.numbered) for d in order]
    fetch_of = _fetches(order, fetches, found.numbered)
    chip = sorted(modules, key=lambda e: e.start)
    wanted = set(names)
    # (index on the chip's line, the event, the name it was jitted under)
    step = [(i, e, program_of(e.name)) for i, e in enumerate(chip)]
    step = [row for row in step if row[2] in wanted]
    found.programs = len(step)
    if not order or not step:
        return found

    def placed(offset):
        for d, slot, name in zip(order, slots, names):
            at = offset + slot
            if 0 <= at < len(step):
                yield d, name, step[at]

    def sound(offset):
        return sum(1 for d, name, (_, p, jitted) in placed(offset)
                   if jitted == name
                   and not _broken(p, d, fetch_of.get(id(d))))

    found.offset = max(range(-BEFORE, min(AFTER, len(step)) + 1),
                       key=lambda o: (sound(o), -abs(o)))
    for d, name, (i, p, jitted) in placed(found.offset):
        if jitted != name:
            continue
        f = fetch_of.get(id(d))
        # A chip runs one program at a time; the CPU of the rehearsal runs
        # the replicas' side by side, and then nothing was waited for.
        before = min(chip[i - 1].end, p.start) if i else d.start
        found.placed.append(
            Pair(p, d, f, max(d.start, before), _broken(p, d, f)))
    found.placed.sort(key=lambda pr: pr.program.start)
    return found


def of(run) -> Optional[Launches]:
    """The run's pairing, made once; None where the run was not traced or
    its cell is not on one chip."""
    if run.trace is None or len(run.trace.planes) != 1:
        return None
    if getattr(run, "launches", None) is None:
        run.launches = pair(_read.phase_events(run, DISPATCH),
                            _read.phase_events(run, FETCH),
                            _read.module_events(run, ""))
        log(f"launches: {run.launches.summary()}")
    return run.launches
