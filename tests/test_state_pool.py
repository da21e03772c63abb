"""The pool of sequence states beside the pages (``models/state_pool.py``)
and the engine's side of it: slots, snapshots, their eviction order, what
the index is told and when, and what the engine refuses to serve a model
with linear layers with."""

import dataclasses

import pytest

from llmd_kv_cache_tpu.core.hma import SPEC_MAMBA
from llmd_kv_cache_tpu.events.model import BlockRemovedEvent, BlockStoredEvent
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LinearAttention, LlamaConfig
from llmd_kv_cache_tpu.models.state_pool import StatePool

BLOCK = 4


def pool(slots=4):
    return StatePool(slots, BLOCK)


def snapshot(p, chain, announce=True):
    """A snapshot on the last block of ``chain``, as the engine leaves it."""
    slot = p.reserve(chain[-1])
    assert slot is not None
    p.store(chain[-1], slot, chain, chain[-2] if len(chain) > 1 else 0,
            range(BLOCK))
    if announce:
        p.announce([chain[-1]])
    return slot


def test_slot_zero_is_never_handed_out():
    p = pool(3)
    got = {p.acquire(f"r{i}") for i in range(3)}
    assert got == {1, 2, 3}
    with pytest.raises(RuntimeError, match="out of state slots"):
        p.acquire("r3")
    p.release("r1")
    assert p.acquire("r4") in got


def test_lookup_finds_the_deepest_snapshot_within_the_limit():
    p = pool()
    chain = [11, 12, 13, 14, 15]
    s2 = snapshot(p, chain[:2])
    s4 = snapshot(p, chain[:4])
    assert p.lookup(chain, 5) == (4, s4)
    assert p.lookup(chain, 3) == (2, s2)   # the pages end before block 4
    assert p.lookup(chain, 1) == (0, None)
    assert p.lookup([99, 98], 2) == (0, None)


def test_snapshots_leave_least_recently_used_first():
    p = pool(3)
    for h in (1, 2, 3):
        snapshot(p, [h])
    p.lookup([1], 1)                       # 1 is used again: 2 is oldest
    p.drain()
    p.acquire("row")
    assert sorted(p.snapshots) == [1, 3]
    assert p.evictions == 1
    (removed,) = p.drain()
    assert removed.block_hashes == [2]


def test_events_name_group_kind_and_only_what_was_announced():
    p = pool(2)
    snapshot(p, [7, 8], announce=False)
    assert p.drain() == []                 # its pages are not committed
    p.announce([8])
    (stored,) = p.drain()
    assert isinstance(stored, BlockStoredEvent)
    assert (stored.block_hashes, stored.parent_hash, stored.group_idx,
            stored.kv_cache_spec_kind) == ([8], 7, 1, SPEC_MAMBA)
    assert len(stored.tokens) == BLOCK
    snapshot(p, [9], announce=False)
    p.acquire("a")                         # evicts 8 (older), announced
    p.acquire("b")                         # evicts 9, never announced
    (removed,) = p.drain()
    assert isinstance(removed, BlockRemovedEvent)
    assert (removed.block_hashes, removed.group_idx) == ([8], 1)


def test_keep_protects_the_snapshot_a_row_is_admitted_on():
    p = pool(2)
    snapshot(p, [1])
    snapshot(p, [2])
    p.lookup([2], 1)
    p.acquire("row", keep=1)
    assert list(p.snapshots) == [1]        # 2 went though it was newer
    p.acquire("next")                      # nothing is kept now
    assert not p.snapshots


def test_a_page_eviction_takes_the_snapshots_on_or_after_it():
    p = pool()
    snapshot(p, [1, 2])
    snapshot(p, [1, 2, 3, 4])
    snapshot(p, [5, 6])
    p.drain()
    p.drop_dependents([3])                 # block 3's pages are gone
    assert sorted(p.snapshots) == [2, 6]
    (removed,) = p.drain()
    assert removed.block_hashes == [4]
    p.drop_dependents([1])
    assert sorted(p.snapshots) == [6]
    assert p.stats()["state_evictions"] == 2


def test_reserve_on_a_block_that_has_one_counts_as_a_use():
    p = pool(2)
    snapshot(p, [1])
    snapshot(p, [2])
    assert p.reserve(1) is None
    p.acquire("row")
    assert list(p.snapshots) == [1]


def test_forget_drops_what_was_never_announced():
    p = pool()
    snapshot(p, [1], announce=False)
    snapshot(p, [2])
    p.forget([1, 2])
    assert list(p.snapshots) == [2]
    assert len(p.free) == 3


def checkpoint(p, chain, old=None):
    """A prefill's periodic checkpoint on the last block of ``chain``, as
    ``_plan_snapshots`` takes it: in the slot of its last one, ``old``."""
    slot = p.reserve(chain[-1], give_up=old)
    assert slot is not None
    p.store(chain[-1], slot, chain, chain[-2], range(BLOCK))
    return slot


def test_a_prefill_that_passes_five_multiples_evicts_one_snapshot_for_them():
    """No slot is free and four other sequences' snapshots stand. The
    first checkpoint takes the least recently used one's slot; each of the
    next four is written over the one before, which leaves without an
    event: the prefill holds one at every moment, ``evictions`` counts
    what ``_take`` took and ``replaced`` the rest."""
    p = pool(4)
    for h in (1, 2, 3, 4):
        snapshot(p, [h])
    p.drain()
    chain, old, slots = [10], None, set()
    for _ in range(5):
        chain += [chain[-1] + 1, chain[-1] + 2]
        slots.add(checkpoint(p, chain, old))
        old = chain[-1]
        assert sorted(p.snapshots) == [2, 3, 4, old]
    assert len(slots) == 1 and not p.free
    assert (p.evictions, p.replaced) == (1, 4)
    assert p.stats()["state_replaced"] == 4
    (removed,) = p.drain()
    assert removed.block_hashes == [1]
    p.announce([old])                     # the prefill ended: it lives on
    (stored,) = p.drain()
    assert stored.block_hashes == [old]
    assert p.lookup(chain, len(chain)) == (len(chain), slots.pop())


def test_a_checkpoint_is_given_up_only_while_nobody_was_told_of_it():
    """An announced snapshot is somebody's resume point: ``give_up`` on it
    takes a slot as any snapshot does. One that has left, or a block that
    has a snapshot already, hands over nothing either."""
    p = pool(3)
    snapshot(p, [1])
    snapshot(p, [5, 6])                    # announced
    assert checkpoint(p, [5, 6, 7], old=6) not in (
        p.snapshots[1].slot, p.snapshots[6].slot)
    assert (sorted(p.snapshots), p.evictions, p.replaced) == ([1, 6, 7], 0, 0)
    assert p.reserve(1, give_up=7) is None     # one stands on 1: 7 stays
    assert sorted(p.snapshots) == [1, 6, 7]
    p.lookup([1], 1)
    checkpoint(p, [5, 6, 7, 8, 9], old=4)      # 4 left long ago: the LRU goes
    assert (sorted(p.snapshots), p.evictions, p.replaced) == ([1, 7, 9], 1, 0)


def test_keep_protects_a_checkpoint_that_took_over_a_slot():
    p = pool(2)
    old = snapshot(p, [5], announce=False)
    snapshot(p, [1])
    assert checkpoint(p, [5, 6], old=5) == old
    p.lookup([1], 1)                       # 6 is the least recently used
    p.acquire("row", keep=6)
    assert list(p.snapshots) == [6]


def test_forget_frees_the_checkpoint_that_trails_an_aborted_prefill():
    p = pool(3)
    checkpoint(p, [5, 6])
    checkpoint(p, [5, 6, 7, 8], old=6)
    assert len(p.free) == 2
    p.forget([6, 8])                       # 6 is long gone
    assert not p.snapshots and len(p.free) == 3
    assert (p.evictions, p.replaced) == (0, 1)
    assert p.drain() == []


def stateful_config(**kw) -> LlamaConfig:
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=2,
        num_kv_heads=2, head_dim=8, intermediate_size=32, page_size=BLOCK,
        kv_lora_rank=8, qk_rope_head_dim=4, q_lora_rank=8,
        linear_layers=(0, 2),
        linear=LinearAttention(key_heads=1, value_heads=2, key_dim=8,
                               value_dim=8),
        state_slots=6, state_checkpoint_tokens=8, norm_offset=1.0,
        post_norms=True, attn_output_gate=True, swiglu_limit=10.0)
    return LlamaConfig(**{**base, **kw})


@pytest.mark.parametrize("change,reason", [
    (dict(ragged_attention=True), "ragged_attention"),
    (dict(max_batch=6), "state_slots 6 for max_batch 6"),
])
def test_engine_refuses_what_cannot_carry_a_state(change, reason):
    cfg = EngineConfig(model=stateful_config(), num_pages=32,
                       max_pages_per_seq=8, max_batch=2,
                       max_prefill_tokens=8)
    with pytest.raises(ValueError, match=reason):
        MiniEngine(dataclasses.replace(cfg, **change))


def test_engine_refuses_an_offload_spec_and_a_mesh(tmp_path):
    import jax
    from jax.sharding import Mesh
    import numpy as np

    from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

    mcfg = stateful_config()
    cfg = EngineConfig(model=mcfg, num_pages=32, max_pages_per_seq=8,
                       max_batch=2, max_prefill_tokens=8)
    spec = SharedStorageOffloadSpec(
        root=str(tmp_path), model_name="m", page_size=BLOCK,
        num_layers=1, kv_heads=1, head_dim=mcfg.kv_cache_head_dim,
        kv_streams=1)
    with pytest.raises(ValueError, match="offload spec"):
        MiniEngine(cfg, offload_spec=spec)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="mesh"):
        MiniEngine(cfg, mesh=mesh)


def test_an_admission_tells_the_index_in_one_batch():
    """A request whose pages evict blocks that snapshots stand on: the
    removals of both groups leave in one batch."""
    batches = []
    eng = MiniEngine(
        EngineConfig(model=stateful_config(state_slots=5), num_pages=14,
                     max_pages_per_seq=8, max_batch=2,
                     max_prefill_tokens=8),
        event_sink=lambda events: batches.append(list(events)))
    for i in range(3):                      # fill pages and snapshots
        eng.generate(f"r{i}", [1 + i] * 13, max_new_tokens=1)
    assert eng.state_pool.stats()["state_snapshots"] >= 2
    batches.clear()
    req = eng.enqueue("big", list(range(20, 44)), max_new_tokens=2)
    removed = [[e for e in b if isinstance(e, BlockRemovedEvent)]
               for b in batches]
    assert sum(1 for b in removed if b) == 1
    (batch,) = [b for b in removed if b]
    assert {e.group_idx for e in batch} == {0, 1}
    while not req.done:
        eng.step()
    assert eng.block_manager.pool_stats()["state_evictions"] >= 1


def serve(eng, rid, prompt):
    req = eng.enqueue(rid, prompt, max_new_tokens=1)
    while not req.done:
        eng.step()
    return req


def depths(eng) -> list:
    return sorted(len(s.chain) * BLOCK
                  for s in eng.state_pool.snapshots.values())


def checkpoint_engine() -> MiniEngine:
    return MiniEngine(EngineConfig(
        model=stateful_config(state_slots=8), num_pages=48,
        max_pages_per_seq=16, max_batch=2, max_prefill_tokens=8))


def test_a_multiple_that_is_the_last_boundary_is_no_periodic_checkpoint():
    """The probe's case at toy sizes (4,098 tokens against checkpoints
    every 4,096; here 10 against 8): the snapshot at 8 is the prompt's
    last block boundary, rule (a), and the repeated prompt resumes from
    it. A prompt that extends it writes its own checkpoints over one
    another and never over that one."""
    eng = checkpoint_engine()
    prompt = list(range(1, 11))
    first = serve(eng, "probe", prompt)
    assert first.checkpoint is None and depths(eng) == [8]
    assert serve(eng, "again", prompt).cached_len == 8
    longer = serve(eng, "longer", prompt + list(range(20, 49)))
    assert longer.cached_len == 8
    assert depths(eng) == [8, 32, 36]      # 16 and 24 gave their slot on
    assert eng.block_manager.pool_stats()["state_replaced"] == 2


def test_a_multiple_that_is_the_matched_end_is_no_periodic_checkpoint():
    """A prompt matches 16 tokens of pages and has a snapshot at 8 only:
    it is admitted at 8 and leaves one at 16, rule (c), though 16 is a
    multiple of 8 too. Its checkpoints at 24, 32 and 40 take one slot
    among them and leave the one at 16 alone."""
    eng = checkpoint_engine()
    first = list(range(1, 19))
    serve(eng, "first", first)
    assert depths(eng) == [8, 16]          # 16 is its last boundary
    pool = eng.state_pool
    pool._remove(next(h for h, s in pool.snapshots.items()
                      if len(s.chain) == 4))   # as an eviction would
    req = serve(eng, "second", first[:16] + list(range(30, 60)))
    assert (req.page_hit_blocks * BLOCK, req.cached_len) == (16, 8)
    assert depths(eng) == [8, 16, 40, 44]
    assert pool.replaced == 2 and pool.evictions == 0
    assert serve(eng, "third", first[:16] + [99] * 5).cached_len == 16


def test_an_aborted_prefill_leaves_no_checkpoint_behind():
    eng = checkpoint_engine()
    req = eng.enqueue("long", list(range(1, 60)), max_new_tokens=1)
    while req.prefill_pos < 32:
        eng.step()
    assert depths(eng) == [32]              # 8, 16 and 24 were written over
    assert eng.abort_request("long")
    assert not eng.state_pool.snapshots
    assert len(eng.state_pool.free) == eng.state_pool.slots
    assert eng.block_manager.pool_stats()["state_evictions"] == 0
