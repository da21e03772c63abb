"""``granite-4.0-h-small-ep2-l10``'s own files: the configuration (every
published width, ``reduced`` exactly the changed keys, the cut's
arithmetic), its reference (plain, float32, a token at a time, nothing of
the program), its counts (the recurrence's work from the definition) and
the three readers (a number from what the program carries, nothing from a
program that carries none)."""

import ast
import json
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import fleet, names

CONFIG = "granite-4.0-h-small-ep2-l10"
CELL = "granite-4.0-h-small-ep2-l10.chat-bursts"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("mamba2_scan_roofline", "mamba2_step_roofline",
           "mamba2_step_share")


@pytest.fixture(scope="module")
def granite_conf():
    return names.config_for_run(names.benchmark(), CONFIG, False)


@pytest.fixture(scope="module")
def granite_cfg(granite_conf):
    return fleet.model_config(granite_conf)


# -- the configuration --------------------------------------------------------

# The catalog row's ``config`` (``granite-4.0-h-small``), as published.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
LAYER_TYPES = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]


def test_the_file_keeps_every_published_width(granite_conf):
    """Every key of the catalog row's ``config`` is in the file under its
    name and with its value, but the five keys ``reduced`` names; those
    five are exactly the keys that differ."""
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    conf = granite_conf
    differ = {k for k, v in PUBLISHED.items() if k not in conf
              or conf[k] != v}
    if conf["layer_types"] != LAYER_TYPES:
        differ.add("layer_types")
    assert differ == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size", "tie_word_embeddings"}
    assert conf["layer_types"] == LAYER_TYPES[:10]
    assert (conf["num_hidden_layers"], conf["num_local_experts"],
            conf["vocab_size"], conf["tie_word_embeddings"]) == (
                10, 36, 50176, False)
    assert conf["layer_share"] == {"chips": 2, "rank": 0,
                                   "n_routed_experts": 72}
    assert set(conf["kvbench"]["reduced"]) == set(entry["reduced"])
    assert conf["kvbench"]["source"] == entry["source"]
    for said in ("head_width", "expert_width", "time_step_limit",
                 "scan_blocking", "state_dtype", "weights",
                 "embed_init_scale", "page_size", "state_slots",
                 "state_checkpoint_tokens", "kv_bytes_per_token"):
        assert conf["kvbench"]["assumed"][said]
    assert "2 chips share each layer" in conf["kvbench"]["deployment"]


def test_the_published_keys_are_the_catalogs():
    """Where the catalog is at hand: the table above is its row."""
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    assert {**PUBLISHED, "layer_types": LAYER_TYPES} == row["config"]
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]


def test_the_cell_is_the_issues(granite_conf):
    bench = names.benchmark()
    cell = names.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-bursts", 1)
    mix = names.traffic("chat-bursts")
    assert (mix["generator"], mix["loop"], mix["router"]) == (
        "sessions", "open", "kv")
    assert mix["arrivals"] == {"law": "bursts", "size": [4, 8],
                               "within_s": 0.25}
    assert mix["params"] == {
        "system_prompts": 8, "system_len": [1024, 2048], "sessions": 64,
        "zipf_system": 1.0, "zipf_session": 1.0, "user_len": [32, 384],
        "assistant_len": [64, 256], "max_new": [64, 192],
        "max_context": 8192, "history_turns": [2, 12]}
    assert (mix["warm_fraction"], mix["tail_fraction"], mix["trace_seconds"],
            mix["trace_steps"]) == (0.15, 0.1, 5, 400)
    kv = granite_conf["kvbench"]
    assert (kv["replicas"], kv["placement"]) == (2, "one_chip")
    assert kv["engine"]["max_batch"] == 16
    assert kv["engine"]["max_pages_per_seq"] * 64 >= 8192
    assert kv["probe"] == {"prompt_tokens": 4098, "decode_tokens": 8}


def test_the_bursts_arrive_together(granite_conf, granite_cfg):
    """The mix's arrivals: at the cell's rate over the window every request
    is offered, in groups of 4 to 8 inside a quarter of a second."""
    mix = names.with_rehearsal(names.traffic("chat-bursts"), False)
    sched = names.generator("sessions").schedule(
        3_000_000_007, mix, granite_cfg.vocab_size, 50.0)
    due = np.array([a.due for a in sched.arrivals])
    assert len(due) == round(mix["rate"] * 50.0)
    groups = np.split(due, np.nonzero(np.diff(due) > 0.25)[0] + 1)
    assert np.median([len(g) for g in groups]) >= 4
    assert max(len(a.prompt) + a.max_new for a in sched.arrivals) <= 8192
    assert len(sched.setup) == 64


def test_the_arithmetic_of_the_cut(granite_cfg):
    """4.963 B parameters (9.93 GB in bf16), 4,096 B of pages a token and
    38.2 MB of state a sequence: ISSUE 57's numbers, from the shapes."""
    import jax

    from llmd_kv_cache_tpu.models import llama

    cfg = granite_cfg
    shapes = jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e9 - 4.963) < 0.0005
    mamba = sum(x.size for k, x in shapes["layers"][0].items()
                if k not in ("w_gate", "w_up", "w_down"))
    attends = sum(x.size for k, x in shapes["layers"][5].items()
                  if k not in ("w_gate", "w_up", "w_down"))
    assert round(mamba / 1e6, 2) == 121.46 and round(attends / 1e6, 2) == 61.12
    streams, heads, width = fleet.cache_payload(cfg)
    assert streams * heads * width * 2 * len(cfg.page_layers) == 4096
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(cfg))
    slot = (recurrent.size // recurrent.shape[1] * 4
            + conv.size // conv.shape[1] * 2)
    assert slot == 9 * (4_194_304 + 50_688) and round(slot / 1e6, 1) == 38.2
    assert slot // 4096 == 9327


# -- the counts ---------------------------------------------------------------


def test_the_recurrences_work_is_counted_from_the_definition(granite_conf,
                                                             granite_cfg):
    counts, cfg = names.counts(granite_conf), granite_cfg
    state = 128 * 64 * 128
    assert counts.mamba2_scan_flops(cfg, 1) == 5 * state * 9
    assert counts.mamba2_scan_flops(cfg, 512) == (
        512 * counts.mamba2_scan_flops(cfg, 1))
    # x, B, C and the step in, y out: 2 B a value.
    assert counts.mamba2_scan_bytes(cfg, 1) == 9 * 2 * (8448 + 128 + 8192)
    assert counts.mamba2_step_bytes(cfg, 1) == 9 * 2 * (4_194_304 + 50_688)
    assert counts.mamba2_step_bytes(cfg, 16) == (
        16 * counts.mamba2_step_bytes(cfg, 1))
    assert counts.decode_attention_bytes(cfg, 1) == 4096
    # At these widths the scan's floor is its bytes (0.37 us a token at
    # 819 GB/s), not its operations (0.24 us at 197 TFLOP/s).
    assert (counts.mamba2_scan_bytes(cfg, 512) / 819e9
            > counts.mamba2_scan_flops(cfg, 512) / 197e12)


def test_a_chunks_flops_grow_with_its_tokens_and_its_keys(granite_conf,
                                                          granite_cfg):
    counts, cfg = names.counts(granite_conf), granite_cfg
    base = counts.prefill_flops(cfg, 0, 512)
    assert counts.prefill_flops(cfg, 0, 0) == 0
    assert counts.prefill_flops(cfg, 4096, 512) > base > 0
    # 2 x the parameters a token multiplies: the mixers, the always-on MLP
    # and the router whole, and its 10 x 36 / 72 experts here.
    want = 2 * (9 * (121.46e6 - 18.87e6 - 0.29e6) + (61.12e6 - 18.87e6
                                                     - 0.29e6)
                + 10 * (0.295e6 + 18.87e6 + 5 * 9.437e6))
    assert abs(counts.flops_per_token(cfg) / want - 1) < 0.01
    attention = 4.0 * 32 * 128 * 512 * 4096
    assert counts.prefill_flops(cfg, 4096, 512) - base == attention


# -- the reference ------------------------------------------------------------


def test_the_reference_imports_nothing_of_the_program(granite_conf):
    path = names.KVBENCH / granite_conf["kvbench"]["reference"]
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "heapq", "itertools", "jax", "numpy",
                        "types"}
    ref = names.reference(granite_conf)
    assert 0 < ref.TOLERANCE < 0.2 and 0 < ref.MARGIN < 0.1
    assert ref.LIMIT == 8 and ref.REACH >= 3


def test_the_references_recurrence_is_the_definition(granite_conf):
    """Its scan over tokens against a loop in float64, the state kept a
    head as ``[P, N]`` from zero; a state rounded to bfloat16 between
    tokens reads otherwise."""
    import jax.numpy as jnp

    ref = names.reference(granite_conf)
    rng = np.random.default_rng(3)
    s, h, p, n = 24, 2, 4, 8
    x = rng.normal(size=(s, h, p))
    b, c = rng.normal(size=(s, n)), rng.normal(size=(s, n))
    d = rng.uniform(0.01, 0.5, size=(s, h))
    a, skip = -rng.uniform(1, 16, size=(h,)), rng.normal(size=(h,))
    S = np.zeros((h, p, n))
    want = []
    for t in range(s):
        S = (np.exp(d[t] * a)[:, None, None] * S
             + (d[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        want.append(S @ c[t] + skip[:, None] * x[t])
    args = [jnp.asarray(v, jnp.float32) for v in (x, b, c, d, a, skip)]
    got = ref._recurrence(*args, jnp.zeros((), jnp.float32))
    np.testing.assert_allclose(got, np.stack(want), atol=1e-5)
    low = ref._recurrence(*args, jnp.zeros((), jnp.bfloat16))
    assert 1e-4 < np.abs(np.asarray(low) - np.stack(want)).max() < 0.2


def test_the_references_router_is_the_softmax_over_the_chosen(granite_conf):
    """Top k of the logits (equal logits: the lower index), weights the
    softmax over those k; a near-tie within the margin is a second
    answer, and a choice that differs only in another chip's experts
    stands once."""
    ref = names.reference(granite_conf)
    logits = np.array([2.0, 0.5, 1.0, 1.0, -1.0, 0.99], np.float32)
    assert ref.admitted(logits, 3, 0.0) == [(0, 2, 3)]
    sets = ref.admitted(logits, 3, 0.02)
    assert sets[0] == (0, 2, 3) and set(sets[1:]) == {(0, 2, 5), (0, 3, 5)}
    here = ref.choices(logits, 3, 0.02, (0, 3))
    assert here[0][1] == (0, 2, 3) and len(here) == 2   # (0, 2) and (0,)


# -- the readers --------------------------------------------------------------


def event(name, dur, **stats):
    return SimpleNamespace(name=name, start=0, dur=dur, stats=stats)


def traced(conf, cfg, ops, dispatches, modules=()):
    trace = SimpleNamespace(planes=[0], ops={0: ops},
                            modules={0: list(modules)},
                            events={"step.dispatch": dispatches})
    return SimpleNamespace(
        trace=trace, cfg=cfg, counts=names.counts(conf),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_cell_reports_its_three_metrics_and_every_listless_one():
    bench = names.benchmark()
    reported = {m["name"] for m in names.cell_metrics(bench, CELL, True)}
    assert set(READERS) <= reported
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= reported
    assert not {"gdn_scan_roofline", "kda_step_roofline"} & reported
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
            mod = names.metric(m["name"])
            assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
                m["unit"], m["source"], m["layer"], m["moves"])


def test_the_scans_share_is_the_definitions_work_over_the_kernels_time(
        granite_conf, granite_cfg):
    reader = names.metric("mamba2_scan_roofline")
    least = 1000 * 9 * 2 * (8448 + 128 + 8192) / 819e9    # bytes: the larger
    run = traced(granite_conf, granite_cfg, [
        event("mamba2_scan.3", 2_000_000,
              program="jit_forward_prefill_pallas"),
        event("mamba2_scan.3", 9_000_000,
              program="jit_forward_decode_pallas"),
        event("fusion.1", 5_000_000, program="jit_forward_prefill_pallas")],
        [event("step.dispatch", 10, scan_tokens=600, prefill_pos=0),
         event("step.dispatch", 10, scan_tokens=400, prefill_pos=600),
         event("step.dispatch", 10, state_rows=3)])
    assert reader.compute(run) == pytest.approx(100 * least / 2e-3)
    assert 0 < reader.compute(run) <= 100


def test_the_steps_share_and_roofline_read_the_kernel_in_the_decode_program(
        granite_conf, granite_cfg):
    ops = [event("mamba2_step.7", 400_000,
                 program="jit_forward_decode_pallas"),
           event("mamba2_step.8", 400_000,
                 program="jit_forward_decode_pallas"),
           event("mamba2_step.8", 700_000,
                 program="jit_forward_prefill_pallas")]
    run = traced(granite_conf, granite_cfg, ops,
                 [event("step.dispatch", 10, state_rows=2),
                  event("step.dispatch", 10, state_rows=1)],
                 [event("jit_forward_decode_pallas", 3_000_000),
                  event("jit_forward_decode_pallas", 1_000_000),
                  event("jit_forward_prefill_pallas", 9_000_000)])
    assert names.metric("mamba2_step_roofline").compute(run) == (
        pytest.approx(100 * 3 * 9 * 2 * (4_194_304 + 50_688) / 819e9
                      / 0.8e-3))
    assert names.metric("mamba2_step_share").compute(run) == (
        pytest.approx(100 * 0.8 / 4.0))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_kernels_reports_nothing(granite_conf,
                                                       granite_cfg, name):
    """The parent commit, another model's kernels, or no trace at all."""
    reader = names.metric(name)
    other = traced(granite_conf, granite_cfg, [
        event("kda_scan.1", 5, program="jit_forward_prefill_pallas"),
        event("gdn_step.1", 5, program="jit_forward_decode_pallas")],
        [event("step.dispatch", 10, scan_tokens=512, state_rows=2)],
        [event("jit_forward_decode_pallas", 50)])
    assert reader.compute(other) is None
    untraced = SimpleNamespace(trace=None, cfg=granite_cfg,
                               counts=other.counts, peaks=other.peaks)
    assert reader.compute(untraced) is None
