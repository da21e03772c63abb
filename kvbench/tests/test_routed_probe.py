"""``correct`` for a model that routes. A fixture configuration whose layers
route tokens over experts (``fixtures/routed-toy.json``, its float32
reference with ``alternatives_at`` and ``MARGIN``, its counts: files alone)
through the probe and through ``run.py``'s own ``main``:

(i) a near-tie built on purpose passes today's probe whichever way the hair
falls, and fails the probe of PR 29 (``fixtures/correct_pr29.py``) one way;
(ii) a program that leaves out one routed expert, the scaling factor or the
shared expert fails against every alternative, under both probes;
(iii) the probe alone over 200 seeds: no fault, 1.2 alternatives a position;
(iv) a reference that admits more than 8 answers at a position is a fault;
(v) for the two accepted configurations (no ``alternatives_at``) every
number of the report equals PR 29's to the last bit.
"""

import ast
import contextlib
import dataclasses
import io
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import correct, fleet, names

FIXTURES = Path(__file__).with_name("fixtures")
PROMPT, DECODE = 32, 3
LAST = PROMPT - 1
# The routed layer and the seed the near-tie is built in: at this seed the
# last prompt position has no near-tie of its own in any routed layer.
LAYER, SEED = 1, 2 ** 31 + 7
HAIR = 1e-6


def routed_conf(replicas: int = 1) -> dict:
    conf = names.as_run(names.load_json(FIXTURES / "routed-toy.json",
                                        "the fixture configuration"), True)
    conf["kvbench"]["replicas"] = replicas
    return conf


@pytest.fixture(scope="module")
def ref():
    return names.reference(routed_conf())


@pytest.fixture(scope="module")
def pr29():
    return names.load_module(FIXTURES / "correct_pr29.py",
                             "the probe as PR 29 had it")


def probe_prompt(cfg, seed: int) -> list:
    """The prompt ``correct.probe`` draws from the seed."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 99])
    return rng.integers(1, cfg.vocab_size, PROMPT).tolist()


def run_probe(probe, reference, params, cfg, seed, served=None,
              served_cfg=None, conf=None):
    """One probe on a fresh fleet. ``served`` / ``served_cfg``: what the
    program serves where that is not what the reference is handed (a wrong
    program)."""
    conf = conf or routed_conf()
    n = int(conf["kvbench"]["replicas"])
    fl = fleet.build_fleet(conf, served_cfg or cfg,
                           params if served is None else served,
                           [None] * n, None, force_pallas=False)
    fl.cfg = cfg
    with contextlib.redirect_stdout(io.StringIO()):
        return probe(fl, params, reference, seed, PROMPT, DECODE)


def one_answer(reference, row: int = 0):
    """``reference`` as a module that admits one answer: ``logits_at``
    alone, or (``row`` 1) the last prompt position's second alternative in
    its place."""
    def logits_at(params, cfg, tokens, positions):
        if not row:
            return reference.logits_at(params, cfg, tokens, positions)
        alts = reference.alternatives_at(params, cfg, tokens, positions)
        return np.stack([a[row if p == LAST else 0]
                         for a, p in zip(alts, positions)])

    return SimpleNamespace(logits_at=logits_at, TOLERANCE=reference.TOLERANCE)


# -- the router's admitted choices ---------------------------------------------


def test_admitted_sets_are_those_within_the_margin(ref):
    values = np.array([0.9, 0.5, 0.4995, 0.1, 0.4991, 0.0], np.float32)
    assert ref.admitted(values, 2, 0.0) == [(0, 1)]
    assert ref.admitted(values, 2, 1e-3) == [(0, 1), (0, 2), (0, 4)]
    assert ref.admitted(values, 2, 1e-4) == [(0, 1)]
    # Equal values: the lower index first, as lax.top_k; the other admitted.
    tie = np.array([0.3, 0.7, 0.7, 0.7], np.float32)
    got = ref.admitted(tie, 2, 1e-6)
    assert got[0] == (1, 2) and sorted(got) == [(1, 2), (1, 3), (2, 3)]
    assert ref.admitted(tie, 4, 1.0) == [(0, 1, 2, 3)]


def test_logits_at_is_row_0_and_matches_the_served_router(ref):
    """Row 0 of every position is ``logits_at``'s row to the bit, and the
    reference's own choice is the one ``llama._moe_deepseek`` makes."""
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.models import llama

    cfg, params = fleet.build_model(routed_conf(), SEED)
    tokens = probe_prompt(cfg, SEED)
    positions = [5, LAST]
    plain = ref.logits_at(params, cfg, tokens, positions)
    alts = ref.alternatives_at(params, cfg, tokens, positions)
    assert [a.dtype for a in alts] == [np.float32] * 2
    for row, a in zip(plain, alts):
        assert np.array_equal(a[0], row)
    # The served layer in float32 on the reference's own hidden state.
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    layer = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                   params["layers"][LAYER])
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 6, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        served = llama._moe_deepseek(h, layer, f32)[0]
        scores = np.asarray(jax.nn.sigmoid(h[0] @ layer["router"]))
        every = np.asarray(jax.vmap(
            lambda g, u, d: ref._swiglu(h[0], g, u, d))(
                layer["w_gate"], layer["w_up"], layer["w_down"]))
        shared = np.asarray(ref._swiglu(h[0], layer["w_gate_sh"],
                                        layer["w_up_sh"],
                                        layer["w_down_sh"]))
    for p in range(6):
        took = list(ref.choices(scores[p], np.zeros(16, np.float32),
                                cfg.moe_router, 4, 0.0)[0])
        w = scores[p, took] / scores[p, took].sum() * cfg.moe_router[4]
        mine = (w[:, None] * every[took, p]).sum(0) + shared[p]
        assert np.abs(mine - np.asarray(served[p])).max() < 1e-4 * np.abs(
            mine).max()


# -- (i) the near-tie, (ii) wrong programs -------------------------------------


def near_tie(ref, sign: int):
    """Weights whose router, at the probe's last prompt position in the
    first routed layer, scores the 4th and the 5th expert of the kept
    groups a hair apart: a bias on the 5th closes the gap the float32
    reference sees to ``sign * HAIR``. The program computes the same scores
    from a hidden state rounded to bfloat16, some 1e-4 away, so which of
    the two it takes does not follow the hair."""
    import jax.numpy as jnp

    cfg, params = fleet.build_model(routed_conf(), SEED)
    tokens = probe_prompt(cfg, SEED)
    seen = []
    plain = ref.choices

    def record(scores, bias, *rest):
        seen.append(scores.copy())
        return plain(scores, bias, *rest)

    ref.choices = record
    try:
        ref.logits_at(params, cfg, tokens, [LAST])
    finally:
        ref.choices = plain
    scores = seen[LAST]              # the first routed layer, in order
    _kind, n_group, topk_group, _norm, _factor = cfg.moe_router
    per = cfg.num_experts // n_group
    group = np.sort(scores.reshape(n_group, per), axis=1)[:, -2:].sum(1)
    kept = np.isin(np.arange(cfg.num_experts) // per,
                   np.argsort(-group, kind="stable")[:topk_group])
    order = [int(e) for e in np.argsort(-scores, kind="stable") if kept[e]]
    fourth, fifth = order[3], order[4]
    bias = np.zeros(cfg.num_experts, np.float32)
    bias[fifth] = np.float32(scores[fourth] - scores[fifth]) + np.float32(
        sign * HAIR)
    layers = list(params["layers"])
    layers[LAYER] = {**layers[LAYER], "router_bias": jnp.asarray(bias)}
    return cfg, {**params, "layers": layers}, (fourth, fifth)


@pytest.fixture(scope="module")
def tie_reports(ref, pr29):
    """For the hair falling either way: today's probe with the fixture's
    reference, with ``alternatives_at`` taken out of it, with the second
    alternative in the first's place, and PR 29's probe."""
    out = {}
    for sign in (+1, -1):
        cfg, params, pair = near_tie(ref, sign)
        out[sign] = {
            "pair": pair,
            "full": run_probe(correct.probe, ref, params, cfg, SEED),
            "first": run_probe(correct.probe, one_answer(ref), params, cfg,
                               SEED),
            "second": run_probe(correct.probe, one_answer(ref, 1), params,
                                cfg, SEED),
            "pr29": run_probe(pr29.probe, ref, params, cfg, SEED),
        }
    return out


def test_near_tie_passes_whichever_way_the_hair_falls(tie_reports):
    for sign, got in tie_reports.items():
        full = got["full"]
        assert full["ok"], (sign, full)
        assert full["alternatives"][0] == 2, full
        assert full["prefill_rel_err"] < full["tolerance"] / 2
    # The program's choice does not follow the hair, the reference's own
    # does: swapping the hair's sign swaps which alternative is the near one.
    chosen = {s: got["full"]["chosen"]["prefill"]
              for s, got in tie_reports.items()}
    assert sorted(chosen.values()) == [0, 1], chosen


def test_program_lies_within_tolerance_of_exactly_one_alternative(
        tie_reports):
    for sign, got in tie_reports.items():
        near = got["full"]["chosen"]["prefill"]
        one, other = ("first", "second") if near == 0 else ("second", "first")
        assert got[one]["ok"], (sign, got[one])
        assert not got[other]["ok"], (sign, got[other])
        # A whole expert's weight apart, not a rounding.
        assert got[other]["prefill_rel_err"] > 3 * got[other]["tolerance"]


def test_one_answer_fails_the_near_tie_one_way(tie_reports):
    """Today's fault shown: with ``alternatives_at`` removed from the
    module, and under PR 29's probe, the same program fails in exactly one
    of the two cases."""
    for kind in ("first", "pr29"):
        ok = sorted(got[kind]["ok"] for got in tie_reports.values())
        assert ok == [False, True], (kind, ok)
    for got in tie_reports.values():
        assert got["first"]["ok"] == got["pr29"]["ok"]
        if not got["pr29"]["ok"]:
            assert "prefill logits differ" in got["pr29"]["faults"][0]


def wrong_programs(cfg, params, expert: int) -> dict:
    """name -> (served params, served config): the same model with the
    term of routed expert ``expert`` left out, without the scaling factor,
    without the shared expert."""
    import jax.numpy as jnp

    def with_layers(change):
        return {**params, "layers": [
            change(layer) if "router" in layer else layer
            for layer in params["layers"]]}

    kind, n_group, topk_group, norm, _factor = cfg.moe_router
    return {
        "one routed expert's term left out":
            (with_layers(lambda layer: {
                **layer, "w_down": layer["w_down"].at[expert].set(0)}), cfg),
        "the scaling factor left out":
            (params, dataclasses.replace(
                cfg, moe_router=(kind, n_group, topk_group, norm, 1.0))),
        "the shared expert left out":
            (with_layers(lambda layer: {
                **layer, "w_down_sh": jnp.zeros_like(layer["w_down_sh"])}),
             cfg),
    }


@pytest.mark.parametrize("wrong", ["one routed expert's term left out",
                                   "the scaling factor left out",
                                   "the shared expert left out"])
def test_wrong_program_fails_against_every_alternative(ref, pr29, wrong):
    cfg, params, _pair = near_tie(ref, +1)
    tokens = probe_prompt(cfg, SEED)
    _, ties, _ = ref._forward(params, cfg, tokens, [LAST])
    both = set(ties[LAYER][LAST][0]) & set(ties[LAYER][LAST][1])
    assert len(ties[LAYER][LAST]) == 2 and len(both) == 3
    # An expert that both alternatives take at the last prompt position.
    served, served_cfg = wrong_programs(cfg, params, min(both))[wrong]
    for probe in (correct.probe, pr29.probe):
        report = run_probe(probe, ref, params, cfg, SEED, served=served,
                           served_cfg=served_cfg)
        assert not report["ok"], (wrong, report)
        # The nearest alternative is out of tolerance, so every one is.
        assert report["prefill_rel_err"] > report["tolerance"], report


# -- (iii) the probe alone over 200 seeds --------------------------------------


def test_probe_over_200_seeds_has_no_fault_and_few_alternatives(ref):
    """Model and reference, not a whole run. Also what ``MARGIN`` was set
    from: how far rounding the activations to bfloat16 moves the gaps that
    decide a choice."""
    conf = routed_conf()
    counts, faults, moved = [], [], []
    for seed in range(1000, 1200):
        cfg, params = fleet.build_model(conf, seed)
        report = run_probe(correct.probe, ref, params, cfg, seed, conf=conf)
        counts += report["alternatives"]
        if not report["ok"]:
            faults.append((seed, report["faults"]))
        tokens = probe_prompt(cfg, seed) + [1] * DECODE
        moved += ref.margin_readings(params, cfg, tokens,
                                     range(LAST, LAST + DECODE + 1))
    mean = float(np.mean(counts))
    expert, group = (np.percentile([m[i] for m in moved], 99)
                     for i in (0, 1))
    print(f"mean alternatives a position {mean:.4f} over {len(counts)} "
          f"positions, largest {max(counts)}; a deciding gap's move under "
          f"bf16 activations, median / 99th percentile: expert "
          f"{np.median([m[0] for m in moved]):.2e} / {expert:.2e}, group "
          f"{np.median([m[1] for m in moved]):.2e} / {group:.2e}; MARGIN "
          f"{ref.MARGIN:.1e}")
    assert not faults, faults
    assert mean < 1.5 and max(counts) <= correct.MAX_ALTERNATIVES
    # MARGIN covers what was measured, and no more than a few times it.
    assert expert < ref.MARGIN < 4 * expert
    assert group < 2 * ref.MARGIN


def test_an_earlier_positions_choice_moves_the_last_logits_little(ref):
    """Why earlier positions are not branched: another expert at one of
    them reaches the last position as one key among the attended."""
    worst_earlier, least_own = 0.0, 1.0
    for seed in range(1000, 1008):
        cfg, params = fleet.build_model(routed_conf(), seed)
        tokens = probe_prompt(cfg, seed)
        base, ties, _ = ref._forward(params, cfg, tokens, [3, 17, LAST])
        scale = float(np.abs(base[-1]).max())
        for p in (3, 17, LAST):
            took = list(ties[LAYER][p][0])
            spare = next(e for e in range(cfg.num_experts)
                         if e not in took)
            other = tuple(sorted(took[1:] + [spare]))
            moved, _, _ = ref._forward(params, cfg, tokens, [LAST],
                                       {LAYER: (p, other)})
            rel = float(np.abs(moved[0] - base[-1]).max() / scale)
            if p == LAST:
                least_own = min(least_own, rel)
            else:
                worst_earlier = max(worst_earlier, rel)
    print(f"another expert at an earlier position moves the last logits by "
          f"at most {worst_earlier:.2e}; at the position itself by at "
          f"least {least_own:.2e}")
    assert worst_earlier < ref.TOLERANCE / 4 < ref.TOLERANCE * 3 < least_own


# -- (iv) the cap ---------------------------------------------------------------


def test_more_than_eight_alternatives_is_a_fault(ref):
    def alternatives_at(params, cfg, tokens, positions):
        rows = ref.logits_at(params, cfg, tokens, positions)
        return [np.repeat(r[None], correct.MAX_ALTERNATIVES + 1, axis=0)
                for r in rows]

    greedy = SimpleNamespace(logits_at=ref.logits_at, TOLERANCE=ref.TOLERANCE,
                             alternatives_at=alternatives_at)
    cfg, params = fleet.build_model(routed_conf(), 1001)
    report = run_probe(correct.probe, greedy, params, cfg, 1001)
    assert not report["ok"]
    assert report["alternatives"] == [9] * (DECODE + 1)
    assert any("admits 9 answers" in f for f in report["faults"])
    assert len(report["faults"]) == 1      # every comparison itself passed


# -- (v) a reference that admits one answer: nothing moved ----------------------


def test_comparisons_equal_pr29s_arithmetic_to_the_bit():
    rng = np.random.default_rng(5)
    for _ in range(50):
        ref_row = rng.normal(size=256).astype(np.float32) * 7
        got = (ref_row + rng.normal(size=256) * 0.05).astype(np.float32)
        token = int(rng.integers(256))
        scale = float(np.abs(ref_row).max())
        assert correct.nearest(ref_row[None], got) == (
            0, float(np.abs(got - ref_row).max() / scale))
        assert correct.least_short(ref_row[None], token) == (
            0, float((ref_row.max() - ref_row[token])
                     / np.abs(ref_row).max()))


@pytest.mark.parametrize("config", ["qwen3-1.7b", "mistral-7b-l16"])
def test_accepted_configurations_report_is_unchanged(pr29, config):
    conf = names.config_for_run(names.benchmark(), config, True)
    reference = names.reference(conf)
    assert not hasattr(reference, "alternatives_at")
    seed = 2 ** 31 + 11
    cfg, params = fleet.build_model(conf, seed)
    new = run_probe(correct.probe, reference, params, cfg, seed, conf=conf)
    old = run_probe(pr29.probe, reference, params, cfg, seed, conf=conf)
    assert new["ok"] and old["ok"]
    assert new.pop("alternatives") == [1] * (DECODE + 1)
    assert new.pop("chosen") == {"prefill": 0, "tokens": [0] * (DECODE + 1),
                                 "hit": 0, "pod-1": 0}
    assert json.dumps(new) == json.dumps(old)      # every digit


# -- the fixture walks a run ----------------------------------------------------


@pytest.fixture(scope="module")
def walk():
    """The fixture cell through ``run.py``'s own ``main`` (the Pallas
    kernels interpreted), as ``test_configuration_modules.py`` walks the
    latent fixture."""
    from kvbench import run as run_py

    bench = dict(names.benchmark())
    bench["configs"] = [{
        "name": "routed-toy", "reduced": [], "why": "a test fixture",
        "source": "none", "file": "kvbench/tests/fixtures/routed-toy.json"}]
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if names.traffic(w["traffic"])["loop"] == "closed")
    bench["workloads"] = [{"name": "routed-toy.walk", "config": "routed-toy",
                           "traffic": traffic, "chips": 1, "why": "walk"}]
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run_py.main(["--workload", "routed-toy.walk", "--seed",
                          str(2 ** 31 + 31), "--seconds", "6", "--trace",
                          "1", "--rehearse"], bench=bench)
    assert rc == 0, out.getvalue()[-3000:] + err.getvalue()[-3000:]
    return bench, out.getvalue().splitlines()


def test_routed_fixture_walks_a_run(walk):
    bench, lines = walk
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    per_layer = names.cell_metrics(bench, "routed-toy.walk", True)
    assert set(last["metrics"]) == {m["name"] for m in per_layer}
    assert last["metrics"]["step_host_ms_p50"]["value"] > 0
    assert not [ln for ln in lines if "NOT correct" in ln]
    (probe,) = [ln for ln in lines if ln.startswith("[kvbench] probe vs")]
    report = ast.literal_eval(probe.split("reference: ", 1)[1])
    assert report["ok"] and len(report["alternatives"]) == DECODE + 1
    assert set(report["chosen"]) == {"prefill", "tokens", "hit", "pod-1"}
    (model,) = [ln for ln in lines if ln.startswith("[kvbench] model: ")]
    assert "cache=1x1x96" in model
