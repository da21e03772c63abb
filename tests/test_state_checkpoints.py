"""A prefill keeps one periodic checkpoint of the sequence state behind it
(``MiniEngine._plan_snapshots``, rule (b)): passing the next multiple of
``state_checkpoint_tokens``, it writes it over its last one, so a long
prompt costs the pool three slots at most and the other sequences' resume
points stay. Both models with linear layers, at the rehearsal's toy widths
on the CPU (pages of 16 and 32 tokens, checkpoints every 64, chunks of 64)."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from kvbench.harness import fleet as F, names  # noqa: E402
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine  # noqa: E402
from llmd_kv_cache_tpu.telemetry.engine_telemetry import (  # noqa: E402
    EngineTelemetryConfig)
from tests.test_telemetry import _recorded  # noqa: E402

# What a hit differs by from the cold prefill: the rounding of other
# chunk shapes (``tests/test_gated_deltanet.py``).
SAME = 0.02


@pytest.fixture(scope="module",
                params=["gigachat3.5-ep16-l5", "solar-open2-ep16-l8"])
def model(request):
    conf = names.config_for_run(names.benchmark(), request.param,
                                rehearse=True)
    cfg, params = F.build_model(conf, 11)
    return SimpleNamespace(cfg=cfg, params=params)


def engine(model, **kw) -> MiniEngine:
    return MiniEngine(EngineConfig(
        model=model.cfg, num_pages=256, max_pages_per_seq=32, max_batch=4,
        max_prefill_tokens=64, **kw), params=model.params)


def finish(eng, *reqs) -> list:
    """Step until ``reqs`` are done; each one's first logits."""
    logits = [None] * len(reqs)
    while not all(r.done for r in reqs):
        eng.step()
        for i, r in enumerate(reqs):
            if logits[i] is None and r.last_logits is not None:
                logits[i] = np.asarray(r.last_logits, np.float32)
    return logits


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def test_a_long_cold_prompt_leaves_the_other_sessions_resume_points(model):
    """Nine sessions' last boundaries stand in a pool of twelve slots:
    three are left, what a prefill may cost (its working row, its periodic
    checkpoint, its last boundary). A cold prompt of 430 tokens passes six
    multiples of 64: 128 to 384 each take the slot of the one before, so
    nobody's snapshot leaves, the chunks' ``step.snapshot`` phases say so,
    and every session's next turn is admitted at its last boundary. (With
    a snapshot kept at every multiple the prompt wanted eight slots and
    five sessions started again from 0.)"""
    eng = engine(model, telemetry=EngineTelemetryConfig())
    pool, page = eng.state_pool, model.cfg.page_size
    sessions = [prompt_of(40, 100 + i) for i in range(pool.slots - 3)]
    for i, history in enumerate(sessions):
        finish(eng, eng.enqueue(f"history{i}", history, 1))
    assert len(pool.free) == 3 and len(pool.snapshots) == len(sessions)
    theirs = set(pool.snapshots)
    seen = _recorded(eng._phases)
    long = eng.enqueue("long", prompt_of(430, 1), 1)
    assert long.cached_len == 0
    finish(eng, long)
    planned = [a for name, a, _ in seen if name == "step.snapshot"]
    assert [sum(a[k] for a in planned) for k in (
        "snapshots", "state_evicted", "replaced")] == [7, 0, 5]
    assert max(a["replaced"] for a in planned) == 1
    stats = eng.block_manager.pool_stats()
    assert (stats["state_evictions"], stats["state_replaced"]) == (0, 5)
    assert theirs <= set(pool.snapshots)
    assert sorted(len(pool.snapshots[h].chain) * page
                  for h in set(pool.snapshots) - theirs) == [384, 416]
    for i, history in enumerate(sessions):  # 47 tokens end in block 2 too
        turn = eng.enqueue(f"turn{i}", history + prompt_of(7, 200 + i), 1)
        assert turn.cached_len == 32, i
        finish(eng, turn)
    again = eng.enqueue("again", long.prompt, 1)
    assert again.cached_len == 416
    finish(eng, again)


def test_a_row_admitted_on_a_checkpoint_that_is_written_over_next(model):
    """A prefill's checkpoint at 128 is one chunk old when a second row,
    whose pages another request committed, is admitted on it; the
    prefill's next chunk passes 192 and takes the checkpoint's slot. The
    admission copied the state into the row's own slot in a program
    dispatched before that chunk, so the row decodes what a cold run of
    its prompt decodes."""
    doc = prompt_of(300, 3)
    fork = doc[:150] + prompt_of(30, 4)
    eng = engine(model)
    pool, page = eng.state_pool, model.cfg.page_size
    finish(eng, eng.enqueue("doc", doc, 1))
    for h in list(pool.snapshots):      # the pages stay, the snapshots go
        pool._remove(h)
    first = eng.enqueue("first", doc + prompt_of(20, 5), 1)
    assert (first.page_hit_blocks * page, first.cached_len) == (288, 0)
    at_128 = first.block_hashes[128 // page - 1]
    while first.checkpoint != at_128:
        eng.step()
    second = eng.enqueue("second", fork, 4)
    assert second.cached_len == 128
    slot = pool.snapshots[at_128].slot
    eng.step()                          # the first's chunk [128, 192)
    assert at_128 not in pool.snapshots and at_128 not in first.snapshots
    now_there = pool.snapshots[first.checkpoint]
    assert (len(now_there.chain) * page, now_there.slot) == (192, slot)
    _, hit = finish(eng, first, second)
    cold_eng = engine(model)
    cold = cold_eng.enqueue("cold", fork, 4)
    (logits,) = finish(cold_eng, cold)
    assert cold.cached_len == 0
    assert list(second.output) == list(cold.output)
    assert np.abs(hit - logits).max() / np.abs(logits).max() < SAME
