"""BlockManager's eviction order and its one batch an admission.

The manager keeps its unreferenced blocks in eviction order (a heap) where
it used to walk the whole pool for every victim. The old walk stays here as
the oracle (``ScanManager``): the same victims in the same order, and the
same pool after every call, is the contract.
"""

import random
import time

import pytest

from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
from llmd_kv_cache_tpu.events.model import (
    BlockRemovedEvent,
    BlockStoredEvent,
    EventBatch,
)
from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig
from llmd_kv_cache_tpu.models import engine as engine_mod
from llmd_kv_cache_tpu.models.engine import (
    BlockManager,
    EngineConfig,
    MiniEngine,
)
from llmd_kv_cache_tpu.models.llama import LlamaConfig

PAGE = LlamaConfig.tiny().page_size  # 4


class ScanManager:
    """The pool as it was before the order was kept: every victim found by
    a walk of ``blocks`` for the unreferenced block with the smallest
    ``last_used`` (``<`` over the dict's order, so ties go to the block that
    entered first), one page and one event a call."""

    def __init__(self, num_pages, clock, removed):
        self.clock, self.removed = clock, removed
        self.free_pages = list(range(1, num_pages))
        self.blocks = {}  # hash -> [page, ref_count, last_used, parent, tokens]
        self.page_to_hash = {}
        self.evictions = 0

    def acquire_prefix(self, hashes):
        pages = []
        for h in hashes:
            if h not in self.blocks:
                break
            pages.append(self.blocks[h][0])
        now = self.clock()
        for h in hashes[:len(pages)]:
            self.blocks[h][1] += 1
            self.blocks[h][2] = now
        return pages

    def try_acquire_blocks(self, hashes):
        if any(h not in self.blocks for h in hashes):
            return None
        now = self.clock()
        for h in hashes:
            self.blocks[h][1] += 1
            self.blocks[h][2] = now
        return [self.blocks[h][0] for h in hashes]

    def allocate_page(self):
        if not self.free_pages and not self._evict_one():
            return None
        return self.free_pages.pop()

    def _evict_one(self):
        victim, victim_time = None, float("inf")
        for h, info in self.blocks.items():
            if info[1] == 0 and info[2] < victim_time:
                victim, victim_time = h, info[2]
        if victim is None:
            return False
        info = self.blocks.pop(victim)
        self.page_to_hash.pop(info[0], None)
        self.free_pages.append(info[0])
        self.evictions += 1
        self.removed.append(victim)
        return True

    def commit_blocks(self, hashes, pages, tokens_per_block, parent):
        now = self.clock()
        canonical = []
        for h, page, toks in zip(hashes, pages, tokens_per_block):
            existing = self.blocks.get(h)
            if existing is None:
                self.blocks[h] = [page, 1, now, parent, tuple(toks)]
                self.page_to_hash[page] = h
                canonical.append(page)
            else:
                existing[1] += 1
                existing[2] = now
                if page != existing[0]:
                    self.free_pages.append(page)
                canonical.append(existing[0])
            parent = h
        return canonical

    def release(self, hashes, orphan_pages):
        for h in hashes:
            info = self.blocks.get(h)
            if info is not None and info[1] > 0:
                info[1] -= 1
        self.free_pages.extend(orphan_pages)

    def clear(self):
        for info in self.blocks.values():
            self.free_pages.append(info[0])
        self.blocks.clear()
        self.page_to_hash.clear()


class _Clock:
    """``time`` for ``models.engine`` with a ``monotonic`` the test sets."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(engine_mod, "time", c)
    return c


def make_manager(num_pages, sink=None, group_idx=0):
    cfg = EngineConfig(model=LlamaConfig.tiny(), num_pages=num_pages,
                       max_pages_per_seq=16, model_name="tiny")
    processor = ChunkedTokenDatabase(
        TokenProcessorConfig(block_size_tokens=PAGE))
    return BlockManager(cfg, processor, event_sink=sink, group_idx=group_idx)


def removed_hashes(events):
    return [h for e in events if isinstance(e, BlockRemovedEvent)
            for h in e.block_hashes]


def state(bm):
    if isinstance(bm, ScanManager):
        blocks = [(h, *info) for h, info in bm.blocks.items()]
    else:
        blocks = [(h, i.page, i.ref_count, i.last_used, i.parent_hash,
                   i.tokens) for h, i in bm.blocks.items()]
    return (blocks, list(bm.free_pages), dict(bm.page_to_hash), bm.evictions)


class _Twin:
    """One sequence of calls through the manager and through the oracle."""

    def __init__(self, num_pages, clock, seed):
        self.clock, self.rng = clock, random.Random(seed)
        self.events = []
        self.new = make_manager(num_pages, sink=self.events.extend)
        self.ref_removed = []
        self.ref = ScanManager(num_pages, clock.monotonic, self.ref_removed)
        # 12 chains of up to 10 blocks; chains 3k, 3k+1, 3k+2 share their
        # first four blocks, as sessions share a system prompt.
        self.chains = [
            [hash(("c", c // 3 if i < 4 else 100 + c, i)) for i in range(10)]
            for c in range(12)]
        self.held = []  # [hashes referenced, private pages]

    def both(self, call):
        got = call(self.new), call(self.ref)
        assert got[0] == got[1]
        self.check()
        return got[0]

    def check(self):
        assert removed_hashes(self.events) == self.ref_removed
        assert state(self.new) == state(self.ref)
        assert self.new._idle == sum(
            1 for i in self.new.blocks.values() if i.ref_count == 0)

    def allocate(self, n, batched):
        """``n`` pages: one ``allocate_pages`` against ``n`` walks."""
        if batched:
            new = self.new.allocate_pages(n)
        else:
            new = [p for p in (self.new.allocate_page() for _ in range(n))
                   if p is not None]
        ref = []
        for _ in range(n):
            page = self.ref.allocate_page()
            if page is None:
                break
            ref.append(page)
        assert new == ref
        self.check()
        return new

    def admit(self, duplicate=False):
        rng = self.rng
        chain = rng.choice(self.chains)[:rng.randint(1, 10)]
        cached = [] if duplicate else self.both(
            lambda m: m.acquire_prefix(chain))
        k = len(cached)
        private = rng.randint(0, 2)
        lacking = len(chain) - k + private
        pages = self.allocate(lacking, batched=rng.random() < 0.8)
        if len(pages) < lacking:  # out of pages: the engine's rollback
            self.both(lambda m: (m.free_pages.extend(pages),
                                 m.release(chain[:k], []))[1])
            return
        parent = chain[k - 1] if k else 0
        n = len(chain) - k
        self.both(lambda m: m.commit_blocks(
            chain[k:], pages[:n], [[7] * PAGE] * n, parent))
        # A duplicate's own pages were freed by the commit where the block
        # was resident; what it keeps private is the rest.
        self.held.append([list(chain), pages[n:]])

    def window(self):
        chain = self.rng.choice(self.chains)
        lo = self.rng.randint(0, 8)
        hashes = chain[lo:lo + self.rng.randint(1, 4)]
        if self.both(lambda m: m.try_acquire_blocks(hashes)) is not None:
            self.held.append([list(hashes), []])

    def release(self):
        if self.held:
            hashes, private = self.held.pop(
                self.rng.randrange(len(self.held)))
            self.both(lambda m: m.release(hashes, private))

    def clear(self):
        self.both(lambda m: m.clear())
        for holder in self.held:  # its blocks are gone, its pages not
            holder[0] = []

    def step(self):
        rng = self.rng
        if rng.random() < 0.4:  # otherwise a tie with the call before
            self.clock.now += rng.choice([0.0, 0.5, 1.0, 3.0])
        op = rng.random()
        if op < 0.35:
            self.admit()
        elif op < 0.42:
            self.admit(duplicate=True)
        elif op < 0.50:
            self.window()
        elif op < 0.90:
            self.release()
        elif op < 0.995:
            pages = self.allocate(1, batched=False)
            if pages:
                self.held.append([[], pages])
        else:
            self.clear()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("num_pages", [9, 24, 64])
def test_victims_and_pool_equal_the_scan(clock, num_pages, seed):
    """Victim hashes in order, ``free_pages``, ``blocks``, ``page_to_hash``
    and ``evictions`` equal the linear scan's after every call."""
    twin = _Twin(num_pages, clock, seed)
    for _ in range(1500):
        twin.step()
    assert twin.new.evictions > 20, "the sequence has to evict"
    # Ties were the common case, as in serving (one last_used an admission).
    times = [i.last_used for i in twin.new.blocks.values()]
    assert len(set(times)) < len(times) or len(times) < 2


def test_ties_go_to_the_block_that_entered_first(clock):
    bm = make_manager(8)
    hashes = [50, 10, 30, 20, 40]  # no order but the order of entry
    pages = bm.allocate_pages(5)
    bm.commit_blocks(hashes, pages, [[1] * PAGE] * 5, 0)
    bm.release(hashes[::-1], [])   # released last to first: of no account
    bm.allocate_pages(2)           # the two free pages
    evicted = []
    bm.event_sink = evicted.extend
    bm.allocate_pages(5)
    assert removed_hashes(evicted) == hashes


def test_a_referenced_block_is_never_evicted(clock):
    bm = make_manager(6)
    a, b = [1, 2], [3, 4]
    bm.commit_blocks(a, bm.allocate_pages(2), [[1] * PAGE] * 2, 0)
    clock.now += 1
    bm.commit_blocks(b, bm.allocate_pages(2), [[2] * PAGE] * 2, 0)
    bm.release(a, [])
    bm.release(b, [])
    clock.now += 1
    assert len(bm.acquire_prefix(a)) == 2     # the older chain, held again
    free = bm.num_free()
    pages = bm.allocate_pages(free + 3)       # wants one more than is idle
    assert len(pages) == free + 2 and set(bm.blocks) == set(a)
    assert all(i.ref_count == 1 for i in bm.blocks.values())
    assert bm.allocate_page() is None


def test_released_twice_leaves_once_at_its_newer_place(clock):
    events = []
    bm = make_manager(6, sink=events.extend)
    for h in (1, 2, 3):
        bm.commit_blocks([h], bm.allocate_pages(1), [[h] * PAGE], 0)
        clock.now += 1
    for h in (1, 2, 3):
        bm.release([h], [])
    clock.now += 1
    bm.acquire_prefix([1])     # oldest by entry, newest by use
    bm.release([1], [])
    assert len(bm._idle_heap) == 4            # the stale entry is still in
    bm.allocate_pages(bm.num_free())
    del events[:]
    bm.allocate_pages(3)
    assert removed_hashes(events) == [2, 3, 1]
    assert bm.allocate_page() is None and not bm._idle_heap


def test_the_heap_stays_bounded(clock):
    """10^5 operations on a small pool: stale entries are thrown away."""
    num_pages = 33
    bm = make_manager(num_pages)
    rng = random.Random(5)
    chains = [[hash((c, i)) for i in range(8)] for c in range(6)]
    held, peak = [], 0
    for _ in range(100_000):
        clock.now += rng.choice([0.0, 0.0, 1.0])
        if held and rng.random() < 0.5:
            bm.release(held.pop(rng.randrange(len(held))), [])
        else:
            chain = rng.choice(chains)[:rng.randint(1, 8)]
            k = len(bm.acquire_prefix(chain))
            pages = bm.allocate_pages(len(chain) - k)
            if len(pages) < len(chain) - k:
                bm.free_pages.extend(pages)
                bm.release(chain[:k], [])
                continue
            bm.commit_blocks(chain[k:], pages, [[0] * PAGE] * len(pages),
                             chain[k - 1] if k else 0)
            held.append(chain)
        peak = max(peak, len(bm._idle_heap))
    # 2 x idle + 64 after a release; an idle block is a page of the pool.
    assert peak <= 2 * num_pages + 64
    assert bm.evictions > 1000


# -- the batch --------------------------------------------------------------


def make_engine(sink, num_pages, **over):
    cfg = dict(model=LlamaConfig.tiny(), num_pages=num_pages,
               max_pages_per_seq=16, model_name="tiny",
               pod_identifier="pod-0")
    cfg.update(over)
    return MiniEngine(EngineConfig(**cfg), event_sink=sink, seed=0)


def scan_order(bm):
    """What the old walk would evict, first to last, were all of it asked."""
    idle = [(i.last_used, at, h) for at, (h, i) in enumerate(bm.blocks.items())
            if i.ref_count == 0]
    return [h for _, _, h in sorted(idle)]


def filled_engine(calls, num_pages=14):
    """Three finished requests of three blocks each: nine idle blocks and
    four free pages."""
    engine = make_engine(calls.append, num_pages)
    for r in range(3):
        engine.generate(f"r{r}", list(range(100 * r, 100 * r + 3 * PAGE)),
                        max_new_tokens=1)
    assert engine.block_manager.num_free() == 4
    assert engine.block_manager.num_cached_blocks() == 9
    return engine


def test_an_admission_evicts_in_one_batch_before_its_first_store():
    calls = []  # one list of events a call of the sink
    engine = filled_engine(calls)
    bm = engine.block_manager
    ages = []
    bm.on_evict = ages.append
    want = scan_order(bm)
    del calls[:]
    before = bm.evictions
    # 8 blocks + 1 token: 9 pages + 1 of room, 4 of them free: 6 victims.
    req = engine.add_request("big", list(range(900, 900 + 8 * PAGE + 1)),
                             max_new_tokens=1)
    k = 6
    (first,), later = calls[0], calls[1:]
    assert isinstance(first, BlockRemovedEvent)
    assert first.block_hashes == want[:k] and first.group_idx == bm.group_idx
    assert bm.evictions - before == k and len(ages) == k
    assert all(age >= 0 for age in ages)
    stored = [e for call in later for e in call]
    assert stored and all(isinstance(e, BlockStoredEvent) for e in stored)
    assert [h for e in stored for h in e.block_hashes] == req.block_hashes
    assert not set(first.block_hashes) & set(bm.blocks)


def test_one_batch_leaves_the_index_as_k_single_events_do():
    def ingest(split):
        calls = []
        engine = filled_engine(calls)
        engine.add_request("big", list(range(900, 900 + 8 * PAGE + 1)),
                           max_new_tokens=1)
        events = [e for call in calls for e in call]
        if split:
            events = [
                one for e in events for one in (
                    [BlockRemovedEvent(block_hashes=[h],
                                       group_idx=e.group_idx)
                     for h in e.block_hashes]
                    if isinstance(e, BlockRemovedEvent) else [e])]
        index = InMemoryIndex(InMemoryIndexConfig())
        pool = Pool(PoolConfig(concurrency=1), index, ChunkedTokenDatabase(
            TokenProcessorConfig(block_size_tokens=PAGE)))
        for at, e in enumerate(events):
            pool.process_event_batch(
                EventBatch(timestamp=float(at), events=[e]), "pod-0", "tiny")
        dump = index.dump_state()
        return (removed_hashes(events), len(events),
                sorted(dump["entries"]), sorted(dump["mappings"]))

    removed, n_batched, entries, mappings = ingest(split=False)
    removed_split, n_split, entries_split, mappings_split = ingest(split=True)
    assert removed == removed_split and len(removed) == 6
    assert n_split - n_batched == 5          # one event where there were six
    assert (entries, mappings) == (entries_split, mappings_split)
    # What is left is what the pool holds: r2's last block of the filling
    # (nine blocks less six victims, r0's and r1's) and the new request's.
    assert len([rows for _, rows in entries if rows]) == 3 + 8


def test_out_of_pages_reports_what_it_evicted_and_returns_the_pages():
    calls = []
    engine = filled_engine(calls, num_pages=14)
    bm = engine.block_manager
    # Pin one chain (a request that waits for its first step): six idle
    # blocks and two free pages are all there is.
    pinned = engine.enqueue("pin", list(range(0, 3 * PAGE)),
                            max_new_tokens=4)
    assert pinned.cached_len == 3 * PAGE and bm.num_free() == 2
    free_before, want = bm.num_free(), scan_order(bm)
    assert len(want) == 6
    del calls[:]
    with pytest.raises(RuntimeError, match="out of KV pages"):
        engine.add_request("big", list(range(900, 900 + 12 * PAGE)),
                           max_new_tokens=1)
    (only,) = calls
    (event,) = only
    assert isinstance(event, BlockRemovedEvent)
    assert event.block_hashes == want         # all six, in order, once
    assert bm.num_free() == free_before + 6   # the popped pages are back
    assert set(bm.blocks) == set(pinned.block_hashes[:3])
    assert "big" not in engine.requests


def test_allocate_page_goes_through_the_same_code(clock):
    """One page at a time: one event a victim, as before."""
    events = []
    bm = make_manager(5, sink=events.append)
    bm.commit_blocks([1, 2, 3, 4], bm.allocate_pages(4),
                     [[0] * PAGE] * 4, 0)
    bm.release([1, 2, 3, 4], [])
    pages = [bm.allocate_page() for _ in range(5)]
    assert pages[4] is None and sorted(pages[:4]) == [1, 2, 3, 4]
    assert [e[0].block_hashes for e in events[1:]] == [[1], [2], [3], [4]]
    assert bm.evictions == 4


def test_hybrid_managers_keep_their_group_tag(clock):
    events = []
    bm = make_manager(4, sink=events.extend, group_idx=1)
    bm.commit_blocks([1, 2, 3], bm.allocate_pages(3), [[0] * PAGE] * 3, 0)
    bm.release([1, 2, 3], [])
    assert len(bm.allocate_pages(2)) == 2
    (event,) = events[1:]
    assert event.block_hashes == [1, 2] and event.group_idx == 1
