"""Hybrid-aware scoring tests: SWA pods valued by their usable trailing
window, not the raw prefix (the reference's documented-WIP feature)."""

import pytest

from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, GroupCatalog, GroupMetadata, PodEntry, TokenProcessorConfig
from llmd_kv_cache_tpu.events.model import BlockStoredEvent, EventBatch
from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig, KVBlockScorerConfig
from llmd_kv_cache_tpu.scoring.scorer import HybridAwareScorer

BLOCK = 4


def swa_pod(name, group=0):
    return PodEntry(name, "tpu-hbm", has_group=True, group_idx=group)


def full_pod(name):
    return PodEntry(name, "tpu-hbm")


def make_scorer(catalog):
    return HybridAwareScorer(
        {"tpu-hbm": 1.0, "cpu": 0.8}, catalog, block_size_tokens=BLOCK
    )


class TestHybridAwareScorer:
    def test_full_attention_pods_unchanged(self):
        catalog = GroupCatalog()
        s = make_scorer(catalog)
        key_to_pods = {1: [full_pod("a")], 2: [full_pod("a")]}
        assert s.score([1, 2, 3], key_to_pods) == {"a": 2.0}

    def test_swa_pod_missing_early_blocks_still_scores(self):
        """The longest-prefix rule scores this pod 0; window-aware scoring
        sees the usable trailing window."""
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))  # 2 blocks
        s = make_scorer(catalog)
        # blocks 2,3 present (the last window); 0,1 evicted out-of-window
        key_to_pods = {3: [swa_pod("s")], 4: [swa_pod("s")]}
        scores = s.score([1, 2, 3, 4], key_to_pods)
        assert scores == {"s": 2.0}

    def test_swa_score_capped_at_window(self):
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # full 4-block residency: usable value is the 2-block window
        key_to_pods = {k: [swa_pod("s")] for k in (1, 2, 3, 4)}
        assert s.score([1, 2, 3, 4], key_to_pods) == {"s": 2.0}

    def test_swa_hole_in_window_drops_to_earlier_window(self):
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # blocks 1,2 present, 3 missing: best usable trailing window ends at
        # block index 2 (keys 2,3)
        key_to_pods = {2: [swa_pod("s")], 3: [swa_pod("s")]}
        scores = s.score([1, 2, 3, 4], key_to_pods)
        assert scores == {"s": 2.0}

    def test_swa_isolated_blocks(self):
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # block 2 alone can't fill the window ending at L=3, but block 0
        # alone IS usable: resuming at L=1 needs only min(W, L) = 1 block.
        key_to_pods = {1: [swa_pod("s")], 3: [swa_pod("s")]}
        assert s.score([1, 2, 3, 4], key_to_pods) == {"s": 1.0}

    def test_swa_mid_prompt_orphan_unusable(self):
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # only block 2: every candidate resume length lacks its window
        key_to_pods = {3: [swa_pod("s")]}
        assert s.score([1, 2, 3, 4], key_to_pods) == {}

    def test_mixed_fleet_comparison(self):
        """SWA and full pods rank by actual prefill savings."""
        catalog = GroupCatalog()
        catalog.learn("s", 0, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        key_to_pods = {
            1: [full_pod("f")], 2: [full_pod("f")],
            3: [swa_pod("s")], 4: [swa_pod("s")],
        }
        scores = s.score([1, 2, 3, 4], key_to_pods)
        assert scores == {"f": 2.0, "s": 2.0}


class TestHybridEndToEnd:
    def test_pool_catalog_feeds_indexer(self):
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size_tokens=BLOCK),
                scorer_config=KVBlockScorerConfig(scoring_strategy="HybridAware"),
            ),
            index=InMemoryIndex(InMemoryIndexConfig(size=1000)),
        )
        pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                    indexer.token_processor)
        indexer.attach_group_catalog(pool.group_catalog)

        tokens = list(range(16))  # 4 canonical blocks
        # SWA pod (window 8 = 2 blocks) stored ONLY the last two blocks —
        # an event chain resuming mid-prompt is impossible without the
        # parent, so simulate the tail residency directly plus the learn.
        pool.process_event_batch(
            EventBatch(timestamp=0.0, events=[
                BlockStoredEvent(
                    block_hashes=[1, 2, 3, 4], tokens=tokens, parent_hash=0,
                    block_size=BLOCK, group_idx=0,
                    kv_cache_spec_kind="sliding_window",
                    kv_cache_spec_sliding_window=8,
                )
            ]),
            "swa-pod", "m",
        )
        # out-of-window eviction of the first two blocks
        from llmd_kv_cache_tpu.events.model import BlockRemovedEvent

        pool.process_event_batch(
            EventBatch(timestamp=1.0, events=[
                BlockRemovedEvent(block_hashes=[1], group_idx=0),
                BlockRemovedEvent(block_hashes=[2], group_idx=0),
            ]),
            "swa-pod", "m",
        )

        scores = indexer.score_tokens(tokens, "m")
        # longest-prefix would score 0 (prefix broken at block 0); hybrid
        # sees the usable trailing window
        assert scores == {"swa-pod": 2.0}

    def test_truly_hybrid_pod_scores_conservatively(self):
        """A pod with both a full-attention and an SWA group: the usable
        value is the min across groups (every group must supply its share)."""
        catalog = GroupCatalog()
        catalog.learn("h", 0, GroupMetadata("full_attention", BLOCK, None))
        catalog.learn("h", 1, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # full group holds blocks 0,1; SWA group holds the trailing window 2,3
        key_to_pods = {
            1: [swa_pod("h", group=0)], 2: [swa_pod("h", group=0)],
            3: [swa_pod("h", group=1)], 4: [swa_pod("h", group=1)],
        }
        scores = s.score([1, 2, 3, 4], key_to_pods)
        # full group usable = 2 (prefix), swa group usable = 2 (window
        # ending at 4)... but the SWA window ending at L=4 requires the
        # full group also present through 4 — conservative min = 2
        assert scores == {"h": 2.0}

    def test_hybrid_pod_full_group_gap_limits_score(self):
        catalog = GroupCatalog()
        catalog.learn("h", 0, GroupMetadata("full_attention", BLOCK, None))
        catalog.learn("h", 1, GroupMetadata("sliding_window", BLOCK, 8))
        s = make_scorer(catalog)
        # full group missing everything; SWA group has a perfect window
        key_to_pods = {
            3: [swa_pod("h", group=1)], 4: [swa_pod("h", group=1)],
        }
        assert s.score([1, 2, 3, 4], key_to_pods) == {}

    def test_uncataloged_pod_keeps_tagged_residency(self):
        """A persistent index can hold group-tagged entries for a pod the
        (restarted) indexer hasn't re-learned yet: they must score by the
        full-attention rule, not drop to zero."""
        catalog = GroupCatalog()  # empty: nothing learned for "s"
        s = make_scorer(catalog)
        key_to_pods = {1: [swa_pod("s")], 2: [swa_pod("s")]}
        assert s.score([1, 2, 3], key_to_pods) == {"s": 2.0}

    def test_orphan_group_tag_merges_into_fallback(self):
        """Tagged entries whose group is absent from the pod's catalog
        still assert residency (merged with untagged/full groups)."""
        catalog = GroupCatalog()
        catalog.learn("h", 0, GroupMetadata("full_attention", BLOCK, None))
        s = make_scorer(catalog)
        # group 0 holds blocks 0,1; an orphan group-7 tag holds block 2.
        key_to_pods = {
            1: [swa_pod("h", group=0)],
            2: [swa_pod("h", group=0)],
            3: [swa_pod("h", group=7)],
        }
        assert s.score([1, 2, 3], key_to_pods) == {"h": 3.0}

    def test_window_value_linear_scan_equivalence(self):
        """The O(n) run-length _window_value matches a brute-force scan."""
        import itertools
        s = make_scorer(GroupCatalog())
        for n in (1, 3, 5):
            for wb in (1, 2, 4):
                for mask in itertools.product([0, 1], repeat=n):
                    blocks = {i: 1.0 + 0.1 * i for i, m in enumerate(mask) if m}
                    brute = 0.0
                    for end in range(n, 0, -1):
                        start = max(0, end - wb)
                        if all(i in blocks for i in range(start, end)):
                            brute = sum(blocks[i] for i in range(start, end))
                            break
                    assert s._window_value(blocks, n, wb) == pytest.approx(brute), (
                        n, wb, mask)


# -- pods that keep a sequence state beside their pages -----------------------


def page(name):
    return PodEntry(name, "tpu-hbm", has_group=True, group_idx=0)


def state(name):
    return PodEntry(name, "tpu-hbm", has_group=True, group_idx=1)


def stateful_scorer(*pods):
    """The DEFAULT scorer, finding the catalog where an event pool leaves
    it: on the index it reads (no ``attach_group_catalog``)."""
    from types import SimpleNamespace

    from llmd_kv_cache_tpu.scoring.scorer import LongestPrefixScorer

    catalog = GroupCatalog()
    for pod in pods:
        catalog.learn(pod, 0, GroupMetadata("mla_attention", BLOCK))
        catalog.learn(pod, 1, GroupMetadata("mamba", BLOCK))
    scorer = LongestPrefixScorer({"tpu-hbm": 1.0, "cpu": 0.8})
    scorer.index = SimpleNamespace(group_catalog=catalog)
    return scorer


class TestStateGroups:
    KEYS = [1, 2, 3, 4, 5, 6]

    def test_the_pod_with_the_snapshot_beats_the_pod_with_more_pages(self):
        """``lost`` holds all six blocks' pages and no state any more:
        sent there, the turn would be computed from its first token.
        ``kept`` holds four and a snapshot on the fourth."""
        s = stateful_scorer("kept", "lost")
        key_to_pods = {k: [page("lost")] for k in self.KEYS}
        for k in self.KEYS[:4]:
            key_to_pods[k].append(page("kept"))
        key_to_pods[4].append(state("kept"))
        assert s.score(self.KEYS, key_to_pods) == {"kept": 4.0, "lost": 0.0}

    def test_pages_beyond_the_deepest_snapshot_do_not_count(self):
        s = stateful_scorer("p")
        key_to_pods = {k: [page("p")] for k in self.KEYS}
        key_to_pods[2].append(state("p"))
        key_to_pods[5].append(state("p"))
        assert s.score(self.KEYS, key_to_pods) == {"p": 5.0}

    def test_a_snapshot_behind_a_missing_page_is_out_of_reach(self):
        s = stateful_scorer("p")
        key_to_pods = {k: [page("p")] for k in (1, 2, 4, 5)}
        key_to_pods[2].append(state("p"))
        key_to_pods[5] = [page("p"), state("p")]
        assert s.score(self.KEYS, key_to_pods) == {"p": 2.0}

    def test_an_entry_without_a_group_speaks_for_pages_and_state(self):
        """A router's speculative entry: the pod was just sent this."""
        s = stateful_scorer("p")
        spec = PodEntry("p", "tpu-hbm", speculative=True)
        assert s.score(self.KEYS, {k: [spec] for k in self.KEYS[:3]}) == {
            "p": 3.0}

    def test_pods_without_a_state_group_score_as_before(self):
        s = stateful_scorer("stateful")
        key_to_pods = {k: [page("plain"), page("stateful")]
                       for k in self.KEYS[:3]}
        assert s.score(self.KEYS, key_to_pods) == {"plain": 3.0,
                                                   "stateful": 0.0}
        plain = stateful_scorer()             # nobody keeps states
        assert plain.state_groups() == {}
        assert plain.score(self.KEYS, key_to_pods) == {"plain": 3.0,
                                                       "stateful": 3.0}

    def test_as_the_harness_wires_it_the_router_follows_the_snapshot(self):
        """``Indexer``, ``Pool`` and ``KVAwareRouter`` built with defaults
        and never handed a catalog (``kvbench/harness/fleet.py``): pages as
        group 0 of kind ``mla_attention``, snapshots as group 1 of kind
        ``mamba``."""
        from llmd_kv_cache_tpu.events.model import BlockRemovedEvent
        from llmd_kv_cache_tpu.scoring.router import KVAwareRouter

        indexer = Indexer(IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK)))
        pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                    indexer.token_processor)
        tokens = list(range(1, 1 + 6 * BLOCK))
        keys = indexer.compute_block_keys(tokens, "m")

        def stored(pod, first, count, group, kind):
            pool.process_event_batch(EventBatch(0.0, [BlockStoredEvent(
                block_hashes=keys[first:first + count],
                tokens=tokens[first * BLOCK:(first + count) * BLOCK],
                parent_hash=keys[first - 1] if first else 0,
                block_size=BLOCK, group_idx=group,
                kv_cache_spec_kind=kind)]), pod, "m")

        stored("pod-0", 0, 6, 0, "mla_attention")
        stored("pod-1", 0, 4, 0, "mla_attention")
        stored("pod-0", 5, 1, 1, "mamba")
        stored("pod-1", 3, 1, 1, "mamba")
        router = KVAwareRouter(indexer, ["pod-0", "pod-1"])
        assert indexer.score_tokens(tokens, "m") == {"pod-0": 6.0,
                                                     "pod-1": 4.0}
        # pod-0 loses its state and keeps every page.
        pool.process_event_batch(EventBatch(0.0, [BlockRemovedEvent(
            block_hashes=[keys[5]], group_idx=1)]), "pod-0", "m")
        assert indexer.score_tokens(tokens, "m") == {"pod-0": 0.0,
                                                     "pod-1": 4.0}
        assert router.route(tokens, "m") == "pod-1"


# -- pods that keep a window pool beside a global pool ------------------------


def windowed_scorer(*pods, window_blocks=2, uniform=()):
    """The DEFAULT scorer over a catalog as an event pool leaves it: each of
    ``pods`` keeps group 0 (``full_attention``) and group 1
    (``sliding_window`` of ``window_blocks`` blocks); each of ``uniform``
    one group, a window (one pool: a uniform-window model)."""
    from types import SimpleNamespace

    from llmd_kv_cache_tpu.scoring.scorer import LongestPrefixScorer

    catalog = GroupCatalog()
    for pod in pods:
        catalog.learn(pod, 1, GroupMetadata(
            "sliding_window", BLOCK, window_blocks * BLOCK))
        catalog.learn(pod, 0, GroupMetadata("full_attention", BLOCK))
    for pod in uniform:
        catalog.learn(pod, 0, GroupMetadata(
            "sliding_window", BLOCK, window_blocks * BLOCK))
    scorer = LongestPrefixScorer({"tpu-hbm": 1.0, "cpu": 0.8})
    scorer.index = SimpleNamespace(group_catalog=catalog)
    return scorer


class TestWindowGroups:
    """``LongestPrefixScorer`` reads a window group from the index's
    catalog: a pod's score is its global chain up to the deepest block
    whose trailing window of group 1 still stands
    (``MiniEngine._acquire_pages``' walk)."""

    KEYS = [1, 2, 3, 4, 5, 6]

    def test_the_catalog_lists_a_window_beside_another_group_only(self):
        s = windowed_scorer("two", uniform=("one",))
        assert s.window_groups() == {"two": (1, 2)} and s.reads_groups()
        assert not windowed_scorer(uniform=("one",)).reads_groups()
        odd = GroupCatalog()                 # 40 tokens over blocks of 16
        odd.learn("p", 0, GroupMetadata("full_attention", 16))
        odd.learn("p", 1, GroupMetadata("sliding_window", 16, 40))
        assert odd.window_groups == {"p": (1, 3)}

    def test_the_pod_that_kept_its_tail_beats_the_longer_chain(self):
        """``lost`` holds all six global blocks and its window blocks were
        evicted under other sessions: sent there, the turn is computed from
        its first token. ``kept`` holds five and the two below the fifth."""
        s = windowed_scorer("kept", "lost")
        key_to_pods = {k: [page("lost")] for k in self.KEYS}
        for k in self.KEYS[:5]:
            key_to_pods[k].append(page("kept"))
        for k in (4, 5):
            key_to_pods[k].append(state("kept"))   # group 1: window blocks
        assert s.score(self.KEYS, key_to_pods) == {"kept": 5.0, "lost": 0.0}

    @pytest.mark.parametrize("window_at,score", [
        ((1, 2, 3, 4, 5, 6), 6.0),   # nothing reclaimed yet
        ((5, 6), 6.0),               # the trailing window alone
        ((4, 5), 5.0),               # the tail's last block evicted
        ((3, 4, 6), 4.0),            # a hole: the deepest whole window
        ((6,), 0.0),                 # half a window is no window
        ((1,), 1.0),                 # depth 1 needs one block
    ])
    def test_the_score_is_the_chain_to_the_deepest_whole_window(
            self, window_at, score):
        s = windowed_scorer("p")
        key_to_pods = {k: [page("p")] for k in self.KEYS}
        for k in window_at:
            key_to_pods[k].append(state("p"))
        assert s.score(self.KEYS, key_to_pods) == {"p": score}

    def test_a_window_behind_a_missing_global_block_is_out_of_reach(self):
        s = windowed_scorer("p")
        key_to_pods = {k: [page("p"), state("p")] for k in (1, 2, 4, 5)}
        assert s.score(self.KEYS, key_to_pods) == {"p": 2.0}

    def test_an_entry_without_a_group_speaks_for_both_pools(self):
        s = windowed_scorer("p")
        spec = PodEntry("p", "tpu-hbm", speculative=True)
        assert s.score(self.KEYS, {k: [spec] for k in self.KEYS[:3]}) == {
            "p": 3.0}

    def test_a_fleet_without_window_groups_scores_as_before(self):
        """A uniform-window pod's one group is a window and resumes by
        longest prefix; a pod with no group at all likewise."""
        s = windowed_scorer(uniform=("uniform",))
        key_to_pods = {k: [page("uniform"), PodEntry("bare", "tpu-hbm")]
                       for k in self.KEYS[:4]}
        assert s.score(self.KEYS, key_to_pods) == {"uniform": 4.0,
                                                   "bare": 4.0}
        mixed = windowed_scorer("two", uniform=("uniform",))
        key_to_pods[1].append(page("two"))
        assert mixed.score(self.KEYS, key_to_pods) == {
            "uniform": 4.0, "bare": 4.0, "two": 0.0}

    def test_as_the_harness_wires_it_the_router_follows_the_tail(self):
        """``Indexer``, ``Pool`` and ``KVAwareRouter`` with defaults, the
        events a two-pool engine sends: group 0 ``full_attention``, group 1
        ``sliding_window`` with its size."""
        from llmd_kv_cache_tpu.events.model import BlockRemovedEvent
        from llmd_kv_cache_tpu.scoring.router import KVAwareRouter

        indexer = Indexer(IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size_tokens=BLOCK)))
        pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                    indexer.token_processor)
        tokens = list(range(1, 1 + 6 * BLOCK))
        keys = indexer.compute_block_keys(tokens, "m")

        def stored(pod, first, count, group):
            pool.process_event_batch(EventBatch(0.0, [BlockStoredEvent(
                block_hashes=keys[first:first + count],
                tokens=tokens[first * BLOCK:(first + count) * BLOCK],
                parent_hash=keys[first - 1] if first else 0,
                block_size=BLOCK, group_idx=group,
                kv_cache_spec_kind=("sliding_window" if group
                                    else "full_attention"),
                kv_cache_spec_sliding_window=2 * BLOCK if group else None,
            )]), pod, "m")

        for pod, blocks in (("pod-0", 6), ("pod-1", 4)):
            stored(pod, 0, blocks, 0)
            stored(pod, blocks - 2, 2, 1)
        router = KVAwareRouter(indexer, ["pod-0", "pod-1"])
        assert indexer.score_tokens(tokens, "m") == {"pod-0": 6.0,
                                                     "pod-1": 4.0}
        # pod-0's window pool evicts the tail's last block; every global
        # block stays.
        pool.process_event_batch(EventBatch(0.0, [BlockRemovedEvent(
            block_hashes=[keys[5]], group_idx=1)]), "pod-0", "m")
        assert indexer.score_tokens(tokens, "m") == {"pod-0": 0.0,
                                                     "pod-1": 4.0}
        assert router.route(tokens, "m") == "pod-1"
