"""Every generator is a pure function of seed and parameters, offers every
seed the same amount of work, and takes any seed the driver may give."""

import pytest

from kvbench.generators import common
from kvbench.harness import names

MIXES = ["sessions", "short-control", "doc-reask",
         "sessions-fleet"]
BIG_SEED = 2 ** 31 + 12345


def make(mix, seed, toy=True, seconds=20.0):
    proposed = names.KVBENCH / "proposed" / f"{mix}.json"
    doc = (names.load_json(proposed, mix) if proposed.is_file()
           else names.traffic(mix))
    traffic = names.with_rehearsal(doc, toy)
    gen = names.generator(traffic["generator"])
    return traffic, gen.schedule(seed, traffic, 1000, seconds)


def shape(s):
    return [(a.due, a.client, a.max_new, a.extends, a.kind, tuple(a.prompt))
            for a in s.arrivals + s.setup]


@pytest.mark.parametrize("mix", MIXES)
def test_pure_function_of_seed(mix):
    assert shape(make(mix, BIG_SEED)[1]) == shape(make(mix, BIG_SEED)[1])
    assert shape(make(mix, BIG_SEED)[1]) != shape(make(mix, 7)[1])


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    """The structure (arrival times, lengths, which session or document,
    the order) is the traffic file's; the seed's are the token values."""
    _, a = make(mix, 1)
    _, b = make(mix, BIG_SEED)

    def structure(s):
        return [(x.due, x.client, x.max_new, x.extends, x.kind,
                 len(x.prompt)) for x in s.setup + s.arrivals]

    assert structure(a) == structure(b)
    assert [x.prompt for x in a.arrivals] != [x.prompt for x in b.arrivals]


def test_another_structure_seed_is_another_order():
    doc = names.with_rehearsal(names.traffic("sessions"), True)
    gen = names.generator(doc["generator"])
    a = gen.schedule(1, doc, 1000, 20.0)
    b = gen.schedule(1, {**doc, "structure_seed": 5}, 1000, 20.0)
    assert len(a.arrivals) == len(b.arrivals)
    assert sorted(x.max_new for x in a.arrivals) == sorted(
        x.max_new for x in b.arrivals)
    assert [x.max_new for x in a.arrivals] != [x.max_new for x in b.arrivals]


@pytest.mark.parametrize("mix", ["sessions", "doc-reask"])
def test_open_loop_covers_the_window(mix):
    traffic, s = make(mix, 3, seconds=20.0)
    due = [a.due for a in s.arrivals]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    assert len(due) == round(traffic["rate"] * 20.0)


def test_sessions_extend_earlier_prompts():
    traffic, s = make("sessions", 5)
    assert s.setup and all(a.max_new == 1 for a in s.setup)
    heads = set()
    for a in s.setup + s.arrivals:
        head = tuple(a.prompt[:16])
        if a.due is not None:
            assert a.extends == (head in heads), a.kind
        heads.add(head)
    turns = [a for a in s.arrivals if a.kind == "turn"]
    assert turns and all(a.extends for a in turns)
    # A turn repeats the whole of an earlier prompt of its session.
    earlier = [tuple(a.prompt) for a in s.setup]
    for a in s.arrivals:
        if a.kind == "turn":
            assert any(tuple(a.prompt[:len(e)]) == e for e in earlier)
        earlier.append(tuple(a.prompt))
    limit = traffic["params"]["max_context"]
    assert all(len(a.prompt) + a.max_new <= limit for a in s.arrivals)


def test_doc_reask_reuse_distance():
    """Between two asks of one document lie asks of the other live ones."""
    traffic, s = make("doc-reask", 9, seconds=30.0)
    p = traffic["params"]
    assert len(s.setup) == p["setup_docs"]
    seen = {}
    for i, a in enumerate(s.setup + s.arrivals):
        doc = tuple(a.prompt[:p["doc_len"][0]])
        if doc in seen:
            assert i - seen[doc] >= p["setup_docs"] - 1
        seen[doc] = i
    asks = {}
    for a in s.setup + s.arrivals:
        doc = tuple(a.prompt[:p["doc_len"][0]])
        asks[doc] = asks.get(doc, 0) + 1
    assert max(asks.values()) <= p["asks_per_doc"]


def test_short_is_unshared_and_closed():
    traffic, s = make("short-control", 2)
    assert traffic["loop"] == "closed"
    assert not any(a.extends for a in s.arrivals)
    assert {a.client for a in s.arrivals} == set(range(traffic["clients"]))
    heads = {tuple(a.prompt[:8]) for a in s.arrivals}
    assert len(heads) > 0.9 * len(s.arrivals)


def test_quantile_sets_and_apportion():
    q = common.quantile_set(10, 100, 200, "uniform")
    assert q.min() >= 100 and q.max() <= 200 and len(q) == 10
    lq = common.quantile_set(1000, 32, 128, "loguniform")
    assert 60 < float(lq.mean()) < 75  # log-uniform mean is below 80
    c = common.apportion(100, common.zipf_weights(8, 1.0))
    assert c.sum() == 100 and list(c) == sorted(c, reverse=True)
    rng = common.rng_for(BIG_SEED, 1)
    b = common.burst_offsets(rng, 100, 10.0, (8, 16), 0.2)
    assert len(b) == 100 and b.min() >= 0 and b.max() < 10.0
