"""Hybrid-model-attention (HMA) group catalog.

Counterpart of reference ``pkg/kvcache/kvblock/hma.go``. Engines with hybrid
attention (sliding-window + full, Mamba mixers, MLA, ...) maintain several KV
cache groups with distinct block semantics; BlockStored events carry the
group index plus its spec. The catalog records what each pod's groups mean so
scoring can become group-aware.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..utils.lockdep import new_lock

# KV cache spec kinds as emitted by vLLM (reference pkg/kvevents/events.go:32-43).
SPEC_FULL_ATTENTION = "full_attention"
SPEC_MLA = "mla_attention"
SPEC_SLIDING_WINDOW = "sliding_window"
SPEC_SLIDING_WINDOW_MLA = "sliding_window_mla"
SPEC_MAMBA = "mamba"
SPEC_CHUNKED_LOCAL = "chunked_local_attention"
SPEC_SINK_FULL = "sink_full_attention"
SPEC_ENCODER_ONLY = "encoder_only_attention"
SPEC_CROSS = "cross_attention"
SPEC_UNKNOWN = "unknown"


@dataclass(frozen=True)
class GroupMetadata:
    """Per-group KV cache spec learned from BlockStored events."""

    kind: str
    block_size: int
    sliding_window_size: Optional[int] = None


class GroupCatalog:
    """Thread-safe per-pod catalog of KV-cache group metadata."""

    def __init__(self) -> None:
        self._lock = new_lock()
        self._entries: dict[str, dict[int, GroupMetadata]] = {}
        # pod -> the group whose blocks are sequence-state snapshots (kind
        # ``mamba``). Replaced whole when it changes, so a scorer reads it
        # without the lock; empty while no pod has such a group.
        self.state_groups: dict[str, int] = {}
        # pod -> (group, blocks of its window) for the pods that keep a
        # window group (kind ``sliding_window``) BESIDE a group of another
        # kind: a pool of its own whose pages are reclaimed behind the
        # window, so a resume needs the trailing window of it. A pod whose
        # only group is a window (a uniform window, one pool, nothing
        # reclaimed) resumes by longest prefix and is not listed. Replaced
        # whole when it changes, as ``state_groups``.
        self.window_groups: dict[str, tuple[int, int]] = {}

    def learn(self, pod_id: str, group_idx: int, meta: GroupMetadata) -> None:
        with self._lock:
            groups = self._entries.setdefault(pod_id, {})
            known = groups.get(group_idx) == meta
            groups[group_idx] = meta
            if (meta.kind == SPEC_MAMBA
                    and self.state_groups.get(pod_id) != group_idx):
                self.state_groups = {**self.state_groups, pod_id: group_idx}
            if known or all(m.kind == SPEC_SLIDING_WINDOW
                            for m in groups.values()):
                return
            window = next(
                ((g, -(-m.sliding_window_size // max(m.block_size, 1)))
                 for g, m in sorted(groups.items())
                 if m.kind == SPEC_SLIDING_WINDOW and m.sliding_window_size),
                None)
            if window is not None and self.window_groups.get(
                    pod_id) != window:
                self.window_groups = {**self.window_groups, pod_id: window}

    def get(self, pod_id: str, group_idx: int) -> Optional[GroupMetadata]:
        with self._lock:
            groups = self._entries.get(pod_id)
            if groups is None:
                return None
            return groups.get(group_idx)

    def pods(self) -> list[str]:
        with self._lock:
            return list(self._entries.keys())

    def groups(self, pod_id: str) -> dict[int, GroupMetadata]:
        """All known groups for a pod (empty dict if none learned)."""
        with self._lock:
            return dict(self._entries.get(pod_id, {}))
