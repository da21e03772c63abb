"""The host's side of a pool of sequence states (``llama.init_state_pool``).

A model with linear layers keeps, beside its pages, one state a sequence:
megabytes where a token's page share is a kilobyte, so a state is not kept
at every block. A slot of the pool is either a running row's working state
or a snapshot: the state as it stood at one block boundary of one prefix,
keyed by the hash of the block it stands on. A prefix hit needs both kinds
of cache: the pages up to a boundary and a snapshot at it
(``MiniEngine._acquire_pages``).

Snapshots leave least-recently-used first, by a heap kept as
``BlockManager``'s is; a page eviction takes the snapshots that stand on
the evicted block or after it (``drop_dependents``: without their pages
they serve nobody). A prefill keeps one periodic checkpoint behind it:
passing the next one, it writes it over its last (``reserve``'s
``give_up``), so a long prompt costs the pool one slot for them and not
one every ``state_checkpoint_tokens``. What the index is told: a snapshot
is a ``BlockStored`` of cache group ``group_idx`` and kind ``mamba`` on
the block it stands on, once that block's pages are committed
(``announce``), and a ``BlockRemoved`` of that group when it leaves.
Events gather here and leave in the batch of whoever calls ``drain``.

Slot 0 is spare (``llama.init_state_pool``) and never handed out.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass
from typing import Optional, Sequence

from ..core.hma import SPEC_MAMBA
from ..events.model import BlockRemovedEvent, BlockStoredEvent, GenericEvent


@dataclass
class _Snapshot:
    slot: int
    # Every block hash of the prefix it stands on, its own last.
    chain: frozenset
    parent_hash: int
    tokens: tuple
    last_used: float
    entry_seq: int
    # Told to the index: only then does its leaving need telling.
    announced: bool = False


class StatePool:
    def __init__(self, slots: int, block_size: int, group_idx: int = 1):
        self.slots = slots
        self.block_size = block_size
        self.group_idx = group_idx
        self.free: list[int] = list(range(slots, 0, -1))
        self.working: dict[str, int] = {}       # request id -> slot
        self.snapshots: dict[int, _Snapshot] = {}  # block hash -> snapshot
        self._heap: list[tuple[float, int, int]] = []
        self._entry_seq = 0
        self.evictions = 0                      # lifetime, as BlockManager's
        # Of those, the snapshots that left because a page under them did
        # (``drop_dependents``): the pages ran out before the slots.
        self.orphaned = 0
        # Not among them: periodic checkpoints a prefill overwrote with
        # its next one (``reserve``'s ``give_up``).
        self.replaced = 0
        self._removed: list[int] = []
        self._stored: list[GenericEvent] = []

    def stats(self) -> dict:
        return {"state_slots": self.slots,
                "state_working": len(self.working),
                "state_snapshots": len(self.snapshots),
                "state_evictions": self.evictions,
                "state_orphaned": self.orphaned,
                "state_replaced": self.replaced}

    # -- slots --

    def _touch(self, h: int, snap: _Snapshot) -> None:
        snap.last_used = time.monotonic()
        heapq.heappush(self._heap, (snap.last_used, snap.entry_seq, h))
        if len(self._heap) > 4 * len(self.snapshots) + 64:
            # Mostly entries of earlier uses: start over from the snapshots.
            self._heap[:] = [(s.last_used, s.entry_seq, k)
                             for k, s in self.snapshots.items()]
            heapq.heapify(self._heap)

    def _remove(self, h: int) -> None:
        snap = self.snapshots.pop(h)
        self.free.append(snap.slot)
        if snap.announced:
            self._removed.append(h)

    def _take(self, keep: Optional[int] = None) -> Optional[int]:
        """A free slot, or the least recently used snapshot's (never the
        one standing on ``keep``); None when every slot is a working one."""
        if self.free:
            return self.free.pop()
        heap, kept = self._heap, []
        slot = None
        while heap:
            entry = heapq.heappop(heap)
            last_used, seq, h = entry
            snap = self.snapshots.get(h)
            if (snap is None or snap.entry_seq != seq
                    or snap.last_used != last_used):
                continue  # an entry of an earlier use
            if h == keep:
                kept.append(entry)
                continue
            self._remove(h)
            self.evictions += 1
            slot = self.free.pop()
            break
        for entry in kept:
            heapq.heappush(heap, entry)
        return slot

    def acquire(self, request_id: str, keep: Optional[int] = None) -> int:
        """A working slot for a row admitted now."""
        slot = self._take(keep)
        if slot is None:
            raise RuntimeError("out of state slots")
        self.working[request_id] = slot
        return slot

    def release(self, request_id: str) -> None:
        slot = self.working.pop(request_id, None)
        if slot is not None:
            self.free.append(slot)

    # -- snapshots --

    def lookup(self, block_hashes: Sequence[int],
               limit: int) -> tuple[int, Optional[int]]:
        """``(depth, slot)`` of the deepest snapshot standing on one of the
        first ``limit`` blocks of ``block_hashes``; ``(0, None)`` without."""
        snapshots = self.snapshots
        for depth in range(min(limit, len(block_hashes)), 0, -1):
            snap = snapshots.get(block_hashes[depth - 1])
            if snap is not None:
                self._touch(block_hashes[depth - 1], snap)
                return depth, snap.slot
        return 0, None

    def reserve(self, h: int, give_up: Optional[int] = None) -> Optional[int]:
        """A slot for a snapshot about to be written on block ``h``; None
        where one stands there already (it counts as used now) or every
        slot is a working one. The slot is that of the snapshot on
        ``give_up`` where that one was never announced (the caller's own
        periodic checkpoint, which its next one replaces: nobody has
        matched pages up to it, so nobody resumes from it); it leaves
        without an event and is counted as ``replaced``, not evicted."""
        snap = self.snapshots.get(h)
        if snap is not None:
            self._touch(h, snap)
            return None
        old = self.snapshots.get(give_up)
        if old is None or old.announced:
            return self._take()
        del self.snapshots[give_up]
        self.replaced += 1
        return old.slot

    def store(self, h: int, slot: int, chain: Sequence[int],
              parent_hash: int, tokens: Sequence[int]) -> None:
        """The snapshot a program has written to ``slot`` (``reserve``)."""
        self._entry_seq += 1
        snap = _Snapshot(slot=slot, chain=frozenset(chain),
                         parent_hash=parent_hash, tokens=tuple(tokens),
                         last_used=0.0, entry_seq=self._entry_seq)
        self.snapshots[h] = snap
        self._touch(h, snap)

    def announce(self, hashes: Sequence[int]) -> None:
        """The blocks these snapshots stand on are committed: tell."""
        for h in hashes:
            snap = self.snapshots.get(h)
            if snap is None or snap.announced:
                continue
            snap.announced = True
            self._stored.append(BlockStoredEvent(
                block_hashes=[h], tokens=list(snap.tokens),
                parent_hash=snap.parent_hash, block_size=self.block_size,
                group_idx=self.group_idx, kv_cache_spec_kind=SPEC_MAMBA))

    def forget(self, hashes: Sequence[int]) -> None:
        """Snapshots of a prefill that ended before its blocks were
        committed: nobody can reach them."""
        for h in hashes:
            snap = self.snapshots.get(h)
            if snap is not None and not snap.announced:
                self._remove(h)

    def drop_dependents(self, victims: Sequence[int]) -> None:
        """Pages of ``victims`` were evicted: the snapshots standing on
        them or after them go too."""
        gone = set(victims)
        for h in [h for h, snap in self.snapshots.items()
                  if not gone.isdisjoint(snap.chain)]:
            self._remove(h)
            self.evictions += 1
            self.orphaned += 1

    def drain(self) -> list[GenericEvent]:
        """The events gathered since the last call, removals first."""
        events: list[GenericEvent] = []
        if self._removed:
            events.append(BlockRemovedEvent(block_hashes=self._removed,
                                            group_idx=self.group_idx))
            self._removed = []
        events.extend(self._stored)
        self._stored = []
        return events

    def clear(self) -> None:
        """Drop every snapshot (the pod-wide AllBlocksCleared covers it)."""
        self.free.extend(s.slot for s in self.snapshots.values())
        self.snapshots.clear()
        self._heap.clear()
        self._removed, self._stored = [], []
