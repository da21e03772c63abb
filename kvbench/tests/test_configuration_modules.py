"""A configuration names its own reference and its own counts: the loader's
cases, the defaults' guard, and a fixture configuration with a latent cache
(``fixtures/``: files alone) walked through set-up, probe, window, readers
and ``check_line`` the way ``--rehearse`` walks a cell."""

import ast
import contextlib
import io
import json
from pathlib import Path

import pytest

from kvbench.harness import fleet, names
from kvbench.harness.loop import Run
from kvbench.run import SPANS
from kvbench.trace import opcount, reduce as R

FIXTURES = Path(__file__).with_name("fixtures")
TRACE = Path(__file__).with_name("fixture.xplane.pb")
LOADERS = {
    "reference": (names.reference, "reference.py", "latent_reference.py",
                  "logits_at"),
    "counts": (names.counts, "trace/opcount.py", "latent_counts.py",
               "prefill_flops"),
}


def latent_conf() -> dict:
    return names.as_run(names.load_json(FIXTURES / "latent-toy.json",
                                        "the fixture configuration"), True)


# -- the loader ---------------------------------------------------------------


@pytest.mark.parametrize("key", LOADERS)
def test_named_module_is_the_configurations_file(key):
    load, _, named, attr = LOADERS[key]
    mod = load(latent_conf())
    assert Path(mod.__file__) == FIXTURES / named and hasattr(mod, attr)


@pytest.mark.parametrize("key", LOADERS)
@pytest.mark.parametrize("config", ["qwen3-1.7b", "mistral-7b-l16"])
def test_default_module_where_none_is_named(key, config):
    load, default, _, attr = LOADERS[key]
    conf = names.config_for_run(names.benchmark(), config, False)
    assert key not in conf["kvbench"]
    mod = load(conf)
    assert Path(mod.__file__) == names.KVBENCH / default
    assert hasattr(mod, attr)


@pytest.mark.parametrize("key", LOADERS)
def test_missing_file_names_the_path(key):
    conf = latent_conf()
    conf["kvbench"][key] = f"{key}s/no-such-model.py"
    with pytest.raises(names.MissingFile) as err:
        LOADERS[key][0](conf)
    assert str(names.KVBENCH / f"{key}s" / "no-such-model.py") in str(
        err.value)


@pytest.mark.parametrize("key", LOADERS)
def test_module_without_the_interface_is_named(key):
    """Each fixture module lacks what the other key needs."""
    load, _, _, attr = LOADERS[key]
    other = LOADERS["counts" if key == "reference" else "reference"][2]
    conf = latent_conf()
    conf["kvbench"][key] = f"tests/fixtures/{other}"
    with pytest.raises(AttributeError) as err:
        load(conf)
    assert attr in str(err.value) and other in str(err.value)


@pytest.mark.parametrize("key", LOADERS)
def test_a_file_outside_the_benchmark_is_refused(key):
    conf = latent_conf()
    conf["kvbench"][key] = "../chip_smoke.py"
    with pytest.raises(ValueError, match="a file under"):
        LOADERS[key][0](conf)


def test_set_up_stops_at_a_missing_file_before_it_builds_anything():
    from kvbench.harness.prepare import prepare

    conf = latent_conf()
    conf["kvbench"]["counts"] = "counts/not-written-yet.py"
    with pytest.raises(names.MissingFile, match="counts/not-written-yet.py"):
        prepare({"chips": 1}, conf, {}, None, 1, 1.0, True, 0.0)


# -- the defaults refuse what they would count wrong --------------------------


@pytest.mark.parametrize("config", ["qwen3-1.7b", "mistral-7b-l16"])
def test_plain_counts_take_the_accepted_configurations(config):
    cfg = fleet.model_config(
        names.config_for_run(names.benchmark(), config, False))
    assert opcount.prefill_flops(cfg, 0, 16) > 16 * opcount.head_flops(cfg)
    assert opcount.decode_attention_bytes(cfg, 10) == (
        2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 2 * 10)


def test_plain_counts_and_plain_reference_refuse_a_latent():
    import dataclasses

    from kvbench import reference

    cfg = fleet.model_config(latent_conf())
    dense = fleet.model_config(
        names.config_for_run(names.benchmark(), "qwen3-1.7b", True))
    refused = [cfg, dataclasses.replace(dense, num_experts=4),
               dataclasses.replace(dense, sliding_window=8, swa_layers=(0,)),
               dataclasses.replace(
                   dense, rope_scaling=("llama3", 8.0, 1.0, 4.0, 8192.0))]
    assert refused[2].is_hybrid
    for bad in refused:
        with pytest.raises(NotImplementedError, match="its own counts"):
            opcount.prefill_flops(bad, 0, 16)
        with pytest.raises(NotImplementedError, match="its own counts"):
            opcount.decode_attention_bytes(bad, 10)
        with pytest.raises(NotImplementedError, match="its own reference"):
            reference.logits_at({}, bad, [1, 2], [1])


# -- the fixture configuration ------------------------------------------------


@pytest.mark.parametrize("q_lora_rank", [48, None])
def test_fixture_converts_with_and_without_q_lora(q_lora_rank):
    conf = latent_conf()
    conf["q_lora_rank"] = q_lora_rank
    cfg = fleet.model_config(conf)
    assert cfg.is_mla and not cfg.num_experts
    assert fleet.cache_payload(cfg) == (1, 1, 64 + 32)
    dense = fleet.model_config(
        names.config_for_run(names.benchmark(), "qwen3-1.7b", False))
    assert fleet.cache_payload(dense) == (2, 8, 128)


def test_fixture_counts_are_one_stream():
    cfg = fleet.model_config(latent_conf())
    counts = names.counts(latent_conf())
    assert counts.decode_attention_bytes(cfg, 1000) == 2 * 96 * 2 * 1000
    # 2 layers x (wq 128*2*96 + down 128*96 + up 2*2*64*64 + wo 128*128
    # + mlp 3*128*256) MACs x 2.
    assert counts.flops_per_token(cfg) == 2 * 2 * (
        128 * 2 * 96 + 128 * 96 + 2 * 2 * 64 * 64 + 128 * 128
        + 3 * 128 * 256)
    # Three tokens attend 1 + 2 + 3 keys: QK^T over 96, PV over 64, 2 heads.
    assert counts.prefill_flops(cfg, 0, 3) == (
        3 * counts.flops_per_token(cfg) + 2 * 2 * 2 * (96 + 64) * 6
        + 2 * 128 * 256)


@pytest.fixture(scope="module")
def walk():
    """The fixture cell through ``run.py``'s own ``main``: the real
    contract's metrics, the accepted closed-loop mix at its rehearsal
    sizes, the fixture's configuration."""
    from kvbench import run as run_py

    bench = dict(names.benchmark())
    bench["configs"] = [{
        "name": "latent-toy", "reduced": [], "why": "a test fixture",
        "source": "none", "file": "kvbench/tests/fixtures/latent-toy.json"}]
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if names.traffic(w["traffic"])["loop"] == "closed")
    bench["workloads"] = [{"name": "latent-toy.walk", "config": "latent-toy",
                           "traffic": traffic, "chips": 1, "why": "walk"}]
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run_py.main(["--workload", "latent-toy.walk", "--seed",
                          str(2 ** 31 + 29), "--seconds", "6", "--trace",
                          "1", "--rehearse"], bench=bench)
    assert rc == 0, out.getvalue()[-3000:] + err.getvalue()[-3000:]
    return bench, out.getvalue().splitlines()


def logged(lines, head):
    found = [ln for ln in lines if ln.startswith(f"[kvbench] {head}")]
    assert len(found) == 1, (head, found)
    return found[0][len(f"[kvbench] {head}"):]


def test_fixture_walks_a_run(walk):
    bench, lines = walk
    last = json.loads(lines[-1])        # check_line let it through
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    cell = bench["workloads"][0]["name"]
    per_layer = names.cell_metrics(bench, cell, True)
    assert set(last["metrics"]) == {m["name"] for m in per_layer}
    everything = json.loads(logged(
        lines, "all metrics of this run (the last line holds this mode's): "))
    assert set(everything) == {
        m["name"] for m in names.cell_metrics(bench, cell, False) + per_layer}
    assert "itl_mean_ms" in everything      # a gap is judged in every cell
    assert not [ln for ln in lines if "NOT correct" in ln]
    assert "cache=1x1x96" in logged(lines, "model: ")
    assert "0.375 KiB KV/token" in logged(lines, "model: ")


def test_fixture_probe_went_through_its_own_reference(walk):
    _, lines = walk
    probe = ast.literal_eval(logged(lines, "probe vs float32 reference: "))
    tolerance = names.reference(latent_conf()).TOLERANCE
    assert probe["ok"] and probe["tolerance"] == tolerance
    for key in ("prefill_rel_err", "hit_rel_err", "other_replicas_rel_err",
                "decode_worst_shortfall"):
        assert probe[key] < tolerance / 2, key
    assert probe["hit_cached_len"] >= 16


# -- the readers on the trace recorded on the chip -----------------------------


def recorded_run(conf, counts) -> Run:
    run = Run(seconds=1.0)
    run.trace = R.reduce(R.load(str(TRACE), SPANS), 1, SPANS)
    run.cfg = fleet.model_config(conf)
    run.counts = counts
    run.peaks = opcount.peaks("TPU v5 lite")
    return run


@pytest.mark.skipif(not TRACE.is_file(), reason="no recorded fixture")
def test_decode_roofline_divides_by_the_configurations_own_bytes():
    """On one recorded slice the kernel's seconds and the keys are the
    same, so two configurations' readings differ as their bytes a key do."""
    conf = latent_conf()
    latent = recorded_run(conf, names.counts(conf))
    reader = names.metric("attn_decode_roofline")
    events = [e for e in latent.trace.ops["/device:TPU:0"]
              if e.name.startswith("pallas_paged_decode_attention")
              and "forward_decode_pallas" in e.stats.get("program", "")]
    seconds = sum(e.dur for e in events) * 1e-9
    keys = sum(int(w["decode_ctx"]) for w in latent.trace.work)
    assert seconds > 0 and keys > 0
    one_stream = 2 * 96 * 2 * keys        # layers x lanes x bf16 x keys
    assert reader.compute(latent) == pytest.approx(
        100.0 * one_stream / 819e9 / seconds, rel=1e-12)
    # Counted as dense GQA (2 streams x 2 kv heads x 64) it would read
    # 2 * 2 * 64 / 96 times as much; the plain counts refuse instead.
    with pytest.raises(NotImplementedError):
        reader.compute(recorded_run(conf, opcount))
    gqa = names.config_for_run(names.benchmark(), "qwen3-1.7b", True)
    dense = recorded_run(gqa, names.counts(gqa))
    assert reader.compute(dense) / reader.compute(latent) == pytest.approx(
        2 * dense.cfg.num_kv_heads * dense.cfg.head_dim / 96)


@pytest.mark.skipif(not TRACE.is_file(), reason="no recorded fixture")
@pytest.mark.parametrize("config,toy,roofline,mfu", [
    ("qwen3-1.7b", True, 1.9017825496984382, 0.3391284771591151),
    ("qwen3-1.7b", False, 212.99964556622507, 1102.8301396048755),
    ("mistral-7b-l16", True, 1.9017825496984382, 0.3391284771591151),
    ("mistral-7b-l16", False, 121.71408318070004, 2703.2417129071296),
])
def test_readers_unchanged_on_the_recorded_trace(config, toy, roofline, mfu):
    """What ``attn_decode_roofline`` and ``prefill_mfu`` returned at PR 28
    (they called ``trace/opcount.py`` themselves), to the bit. The slice is
    a toy engine's: at published widths the values are arithmetic, not
    shares of anything."""
    conf = names.config_for_run(names.benchmark(), config, toy)
    run = recorded_run(conf, names.counts(conf))
    assert names.metric("attn_decode_roofline").compute(run) == roofline
    assert names.metric("prefill_mfu").compute(run) == mfu
