"""KV-block scorers.

Counterpart of reference ``pkg/kvcache/kvblock_scorer.go`` +
``pkg/kvcache/backend.go``. Scores candidate pods by the longest consecutive
run of cached blocks from block 0, weighting each hit by the device tier it
lives on. Default tier weights are TPU-first: ``tpu-hbm`` (1.0) is the fast
tier (the reference's ``gpu``), ``cpu`` host memory 0.8, shared storage 0.5.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

from ..core.hma import SPEC_SINK_FULL
from ..core.keys import (
    TIER_CPU,
    TIER_OBJECT_STORE,
    TIER_SHARED_STORAGE,
    TIER_TPU_HBM,
    BlockHash,
    PodEntry,
)

LONGEST_PREFIX_MATCH = "LongestPrefix"
HYBRID_AWARE = "HybridAware"


@dataclass
class KVCacheBackendConfig:
    """A device tier/medium and its scoring weight (``backend.go:19-24``)."""

    name: str
    weight: float


def default_backend_configs() -> list[KVCacheBackendConfig]:
    """TPU-first tier weights.

    ``gpu`` kept as an alias tier for interop with engines that emit GPU
    mediums (weight equal to HBM).
    """
    return [
        KVCacheBackendConfig(name=TIER_TPU_HBM, weight=1.0),
        KVCacheBackendConfig(name="gpu", weight=1.0),
        KVCacheBackendConfig(name=TIER_CPU, weight=0.8),
        KVCacheBackendConfig(name=TIER_SHARED_STORAGE, weight=0.5),
        KVCacheBackendConfig(name=TIER_OBJECT_STORE, weight=0.5),
    ]


@dataclass
class KVBlockScorerConfig:
    scoring_strategy: str = LONGEST_PREFIX_MATCH
    backend_configs: list[KVCacheBackendConfig] = field(default_factory=default_backend_configs)

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "KVBlockScorerConfig":
        if not d:
            return cls()
        backends = d.get("backendConfigs", d.get("backend_configs"))
        cfg = cls(scoring_strategy=d.get("scoringStrategy", d.get("scoring_strategy", LONGEST_PREFIX_MATCH)))
        if backends:
            cfg.backend_configs = [
                KVCacheBackendConfig(name=b["name"], weight=float(b["weight"])) for b in backends
            ]
        return cfg


class LongestPrefixScorer:
    """Longest-consecutive-prefix scorer with tier weighting.

    Mirrors reference ``kvblock_scorer.go:106-154``: per key, each pod takes
    the max weight across its tiers holding the block; pods drop out of the
    active set at their first gap; scores accumulate while active.
    """

    def __init__(self, medium_weights: Optional[dict[str, float]] = None):
        self.medium_weights = (
            medium_weights
            if medium_weights is not None
            else {b.name: b.weight for b in default_backend_configs()}
        )
        # Optional PodLivenessTracker (resilience.liveness), attached by
        # the host (Indexer.attach_liveness): demotes pods whose event
        # stream — and therefore whose index view — has gone stale.
        self.liveness = None
        # The index the scored entries come from (``Indexer`` sets it): its
        # ``group_catalog`` says which pods keep sequence states beside
        # their pages and which a window pool beside a global one, and in
        # which group (``state_groups``, ``window_groups``).
        self.index = None

    @property
    def strategy(self) -> str:
        return LONGEST_PREFIX_MATCH

    def _apply_liveness(self, scores: dict[str, float]) -> dict[str, float]:
        """Degraded-mode weighting: multiply each pod's score by its
        liveness factor (1 fresh → 0 dead) and drop zeroed pods. With every
        pod stale, scores empty out and the router falls back to
        round-robin — degrading toward fairness, never toward a corpse."""
        if self.liveness is None or not scores:
            return scores
        out = {}
        for pod, s in scores.items():
            f = self.liveness.factor(pod)
            if s * f > 0.0:
                out[pod] = s * f
        return out

    def state_groups(self) -> dict[str, int]:
        """pod -> the cache group whose blocks are snapshots of a sequence
        state (kind ``mamba``), for the pods that have one: empty for a
        fleet of pages alone, and then nothing below changes."""
        catalog = getattr(self.index, "group_catalog", None)
        return catalog.state_groups if catalog is not None else {}

    def window_groups(self) -> dict[str, tuple[int, int]]:
        """pod -> (the cache group that is a window pool beside a global
        one, its window in blocks), for the pods that have one
        (``GroupCatalog.window_groups``)."""
        catalog = getattr(self.index, "group_catalog", None)
        return catalog.window_groups if catalog is not None else {}

    def reads_groups(self) -> bool:
        """Whether any pod's entries have to be read by group: the fused
        native paths count every entry as a page."""
        return bool(self.state_groups() or self.window_groups())

    def _score_with_groups(
        self,
        keys: Sequence[BlockHash],
        key_to_pods: dict[BlockHash, list[PodEntry]],
        state_groups: dict[str, int],
        window_groups: dict[str, tuple[int, int]],
    ) -> dict[str, float]:
        """The longest-prefix rule where some pods' hits need more than
        pages. A pod that keeps a sequence state beside its pages can
        resume only where a snapshot stands, so its score is its pages'
        weights up to the deepest block that has a snapshot and all of
        whose predecessors have pages; pages beyond it would be computed
        again. A pod that keeps a window pool beside a global one can
        resume at depth ``d`` only where the window group's trailing
        ``min(window blocks, d)`` blocks below ``d`` are all present (the
        engine's own walk, ``MiniEngine._acquire_pages``): its score is its
        global chain's weights up to the deepest such block. An entry of
        such a group is no page; an entry without a group (a router's
        speculative one, a tier update) speaks for both kinds. The other
        pods score as ``score`` scores them."""
        sums: dict[str, float] = {}
        usable: dict[str, float] = {}
        run: dict[str, int] = {}  # a pod's window blocks in a row, to here
        active: set = set()
        for i, key in enumerate(keys):
            pages: dict[str, float] = {}
            extras = set()  # pods whose other group stands at this block
            for e in key_to_pods.get(key, []):
                pod = e.pod_identifier
                group = state_groups.get(pod)
                if group is None and pod in window_groups:
                    group = window_groups[pod][0]
                if group is not None:
                    if e.has_group and e.group_idx == group:
                        extras.add(pod)
                        continue  # a snapshot or a window block is no page
                    if not e.has_group:
                        extras.add(pod)
                w = self.medium_weights.get(e.device_tier, 1.0)
                if w > pages.get(pod, -1.0):
                    pages[pod] = w
            if i == 0:
                sums, active = dict(pages), set(pages)
            else:
                for pod in list(active):
                    w = pages.get(pod)
                    if w is None:
                        active.discard(pod)
                    else:
                        sums[pod] += w
            for pod in active:
                if pod in window_groups:
                    run[pod] = run.get(pod, 0) + 1 if pod in extras else 0
                    if run[pod] >= min(window_groups[pod][1], i + 1):
                        usable[pod] = sums[pod]
                elif pod in extras:
                    usable[pod] = sums[pod]
            if not active:
                break
        return {pod: (usable.get(pod, 0.0)
                      if pod in state_groups or pod in window_groups else s)
                for pod, s in sums.items()}

    def _fill_max_weights(
        self, entries: Sequence[PodEntry]
    ) -> dict[str, float]:
        weights: dict[str, float] = {}
        for entry in entries:
            w = self.medium_weights.get(entry.device_tier, 1.0)
            cur = weights.get(entry.pod_identifier)
            if cur is None or w > cur:
                weights[entry.pod_identifier] = w
        return weights

    def score(
        self,
        keys: Sequence[BlockHash],
        key_to_pods: dict[BlockHash, list[PodEntry]],
    ) -> dict[str, float]:
        if not keys:
            return {}
        state_groups, window_groups = (self.state_groups(),
                                       self.window_groups())
        if state_groups or window_groups:
            return self._apply_liveness(self._score_with_groups(
                keys, key_to_pods, state_groups, window_groups))

        cur_weights = self._fill_max_weights(key_to_pods.get(keys[0], []))
        pod_scores = dict(cur_weights)
        active = set(cur_weights)

        for key in keys[1:]:
            if not active:
                break
            cur_weights = self._fill_max_weights(key_to_pods.get(key, []))
            for pod in list(active):
                w = cur_weights.get(pod)
                if w is not None:
                    pod_scores[pod] += w
                else:
                    active.discard(pod)

        return self._apply_liveness(pod_scores)


class HybridAwareScorer(LongestPrefixScorer):
    """Sliding-window-aware scoring (the reference's documented WIP,
    ``docs/architecture.md`` "Hybrid attention").

    For a full-attention pod, a cached prefix of L blocks saves L blocks of
    prefill — the longest-prefix rule. For a pod whose cache group is
    ``sliding_window`` with window W, resuming at length L only requires
    the blocks covering the last W tokens of L: **early blocks falling out
    of the window don't matter**, so the usable prefix is the deepest L
    whose trailing window of blocks is fully present, and the saving is
    capped at the window itself.

    Per pod: score = tier-weighted count of present blocks inside the best
    usable trailing window (full-attention pods fall back to the exact
    longest-prefix accumulation). Requires the pool's ``GroupCatalog`` to
    know the pod's group spec; unknown pods score as full attention.

    Which scorer knows what: this strategy values a window group by the
    window itself (its saving capped there, a uniform-window pod's one
    group included) and takes the minimum over a pod's groups; it does not
    know sequence states. The DEFAULT ``LongestPrefixScorer`` reads the
    same catalog from the index and keeps the longest-prefix value: a pod
    that keeps states, or a window pool BESIDE a global one, scores its
    global chain up to the deepest block it can resume on (a snapshot; a
    standing trailing window), and every other pod as ever.
    """

    def __init__(self, medium_weights=None, group_catalog=None,
                 block_size_tokens: int = 16):
        super().__init__(medium_weights)
        self.group_catalog = group_catalog
        self.block_size_tokens = block_size_tokens

    def _window_blocks(self, pod: str, group_idx) -> Optional[int]:
        """A group's sliding window in blocks; None = full attention.

        ``sink_full_attention`` groups also return None: their mask keeps
        the sink prefix attendable past the window, and the producing
        engines resume by longest prefix over a non-reclaiming pool — so
        a trailing window without block 0 is worthless there, and valuing
        it like plain SWA would systematically overscore sink pods that
        lost early blocks to eviction.
        """
        if group_idx is None or self.group_catalog is None:
            return None
        meta = self.group_catalog.get(pod, group_idx)
        if (meta is not None and meta.sliding_window_size
                and meta.kind != SPEC_SINK_FULL):
            return max(1, -(-meta.sliding_window_size // self.block_size_tokens))
        return None

    @staticmethod
    def _merge_max(dst: dict[int, float], src: dict[int, float]) -> None:
        """Fold ``src`` into ``dst`` keeping the per-index max weight."""
        for i, w in src.items():
            if w > dst.get(i, 0.0):
                dst[i] = w

    @staticmethod
    def _prefix_value(blocks: dict[int, float]) -> float:
        """Longest-consecutive-from-0 weighted value."""
        total = 0.0
        i = 0
        while i in blocks:
            total += blocks[i]
            i += 1
        return total

    def _window_value(self, blocks: dict[int, float], n_keys: int,
                      wb: int) -> float:
        """Deepest resume length whose trailing min(wb, L) blocks are all
        present; value = their weights (capped at the window).

        Single forward pass (O(n_keys)): track the consecutive-present run
        ending at each position plus a weight prefix sum; end L is usable
        iff the run covers min(wb, L) blocks.
        """
        run = 0
        best_end = 0
        prefix = [0.0] * (n_keys + 1)
        for i in range(n_keys):
            w = blocks.get(i)
            prefix[i + 1] = prefix[i] + (w or 0.0)
            run = run + 1 if w is not None else 0
            if run >= min(wb, i + 1):
                best_end = i + 1
        if best_end == 0:
            return 0.0
        start = max(0, best_end - wb)
        return prefix[best_end] - prefix[start]

    def score(self, keys, key_to_pods):
        if not keys:
            return {}
        if self.group_catalog is None:
            return super().score(keys, key_to_pods)

        # One pass: per-pod {group: presence map} for tagged entries, plus
        # a per-pod map for untagged entries (tokenless tier updates carry
        # no group; they assert residency for every group).
        tagged: dict[str, dict[int, dict[int, float]]] = {}
        untagged: dict[str, dict[int, float]] = {}
        for i, key in enumerate(keys):
            for e in key_to_pods.get(key, []):
                w = self.medium_weights.get(e.device_tier, 1.0)
                slot = (
                    tagged.setdefault(e.pod_identifier, {}).setdefault(e.group_idx, {})
                    if e.has_group
                    else untagged.setdefault(e.pod_identifier, {})
                )
                if w > slot.get(i, 0.0):
                    slot[i] = w

        # A resume needs EVERY group of the pod to supply its share: score
        # = min across all cataloged groups (full-attention: longest
        # prefix; SWA: trailing window) — conservative for hybrid pods. A
        # cataloged group with no residency zeroes the pod. Pods with no
        # cataloged groups score by the plain longest-prefix rule; tagged
        # entries whose group the catalog doesn't know (e.g. a persistent
        # index surviving an indexer restart, before a new BlockStored
        # re-teaches the spec) still assert residency and fold into that
        # full-attention fallback instead of being dropped.
        pods = set(tagged) | set(untagged)
        scores: dict[str, float] = {}
        for pod in pods:
            pod_groups = tagged.get(pod, {})
            cataloged = self.group_catalog.groups(pod)
            extra = dict(untagged.get(pod, {}))
            for g, presence in pod_groups.items():
                if g not in cataloged:
                    self._merge_max(extra, presence)
            if not cataloged:
                scores[pod] = self._prefix_value(extra) if extra else 0.0
                continue
            value = None
            for g in cataloged:
                blocks = dict(extra)
                self._merge_max(blocks, pod_groups.get(g, {}))
                wb = self._window_blocks(pod, g)
                if wb is None:
                    gv = self._prefix_value(blocks)
                else:
                    gv = self._window_value(blocks, len(keys), wb)
                value = gv if value is None else min(value, gv)
            scores[pod] = value or 0.0
        return self._apply_liveness(
            {p: v for p, v in scores.items() if v > 0.0})

    @property
    def strategy(self) -> str:
        return HYBRID_AWARE


def create_scorer(config: Optional[KVBlockScorerConfig] = None,
                  block_size_tokens: int = 16):
    config = config or KVBlockScorerConfig()
    weights = {b.name: b.weight for b in config.backend_configs}
    if config.scoring_strategy == LONGEST_PREFIX_MATCH:
        return LongestPrefixScorer(weights)
    if config.scoring_strategy == HYBRID_AWARE:
        # The GroupCatalog is wired post-construction by the host
        # (Indexer.attach_group_catalog), since it lives on the event pool.
        return HybridAwareScorer(weights, None, block_size_tokens)
    raise ValueError(f"unsupported scoring strategy: {config.scoring_strategy}")
