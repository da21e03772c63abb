"""``falcon-h1-34b-l9``'s own files: the configuration (every published
width, ``reduced`` exactly the changed keys, the cut's arithmetic), its
reference (plain, float32, a token at a time, nothing of the program), its
counts (the recurrence's work from the definition, nine layers of pages)
and the three readers of the two mixers' kernels together (a number from
what the program carries, nothing from a program that carries none)."""

import ast
import json
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import fleet, names

CONFIG = "falcon-h1-34b-l9"
CELL = "falcon-h1-34b-l9.long-answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("mixer_pair_decode_roofline", "mixer_pair_step_share",
           "mixer_pair_chunk_share")


@pytest.fixture(scope="module")
def falcon_conf():
    return names.config_for_run(names.benchmark(), CONFIG, False)


@pytest.fixture(scope="module")
def falcon_cfg(falcon_conf):
    return fleet.model_config(falcon_conf)


# -- the configuration --------------------------------------------------------

# The catalog row's ``config`` (``Falcon-H1-34B-Instruct``), as published.
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


def test_the_file_keeps_every_published_width(falcon_conf):
    """Every key of the catalog row's ``config`` is in the file under its
    name and with its value, but the two keys ``reduced`` names; those two
    are exactly the keys that differ."""
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    conf = falcon_conf
    differ = {k for k, v in PUBLISHED.items() if k not in conf
              or conf[k] != v}
    assert differ == set(entry["reduced"]) == {"num_hidden_layers",
                                               "vocab_size"}
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (9, 32640)
    assert 72 == 8 * 9 and 261120 == 8 * 32640
    assert set(conf["kvbench"]["reduced"]) == set(entry["reduced"])
    assert conf["kvbench"]["source"] == entry["source"]
    for said in ("gated_norm", "mup_columns", "time_step_limit",
                 "multipliers_where", "scan_blocking", "state_dtype", "rope",
                 "weights", "page_size", "state_slots",
                 "state_checkpoint_tokens", "probe", "kv_bytes_per_token"):
        assert conf["kvbench"]["assumed"][said]
    assert "DEPARTURE" in conf["kvbench"]["assumed"]["multipliers_where"]
    for said in ("v5e-8", "8 pipeline stages", "chip 0", "4,205,319,008"):
        assert said in conf["kvbench"]["deployment"]


def test_the_published_keys_are_the_catalogs():
    """Where the catalog is at hand: the table above is its row."""
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
    assert PUBLISHED == row["config"]
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]


def test_the_cell_is_the_issues(falcon_conf):
    bench = names.benchmark()
    cell = names.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-answers", 1)
    mix = names.traffic("long-answers")
    assert (mix["generator"], mix["loop"], mix["router"],
            mix["structure_seed"]) == ("sessions", "open", "kv", 61)
    assert mix["arrivals"] == {"law": "poisson"}
    assert mix["params"] == {
        "system_prompts": 4, "system_len": [512, 1024], "sessions": 48,
        "zipf_system": 1.0, "zipf_session": 0.4, "user_len": [64, 512],
        "assistant_len": [256, 768], "max_new": [256, 768],
        "max_context": 6144, "history_turns": [0, 4]}
    assert (mix["warm_fraction"], mix["tail_fraction"], mix["trace_seconds"],
            mix["trace_steps"]) == (0.3, 0.1, 5, 400)
    kv = falcon_conf["kvbench"]
    assert (kv["replicas"], kv["placement"]) == (2, "one_chip")
    assert kv["engine"]["max_batch"] == 12
    assert falcon_conf["state_slots"] > kv["engine"]["max_batch"]
    assert kv["engine"]["max_pages_per_seq"] * 64 >= 6144 + 768
    assert kv["probe"] == {"prompt_tokens": 4098, "decode_tokens": 8}


def test_the_answers_are_long_and_the_asks_short(falcon_conf, falcon_cfg):
    """The mix as a window offers it: every request inside the context,
    answers of hundreds of tokens, the ids from the vocabulary's slice."""
    mix = names.with_rehearsal(names.traffic("long-answers"), False)
    sched = names.generator("sessions").schedule(
        3_000_000_007, mix, falcon_cfg.vocab_size, 50.0)
    assert len(sched.arrivals) == round(mix["rate"] * 50.0)
    assert max(len(a.prompt) + a.max_new for a in sched.arrivals) <= 6144 + 768
    assert np.mean([a.max_new for a in sched.arrivals]) > 400
    assert max(max(a.prompt) for a in sched.arrivals) < 32640


def test_the_arithmetic_of_the_cut(falcon_cfg):
    """430,120,032 parameters a layer and 4,205,319,008 on this chip (8.41
    GB in bf16), 18,432 B of pages a token and 38,025,216 B of state a
    sequence: ISSUE 61's numbers, from the shapes."""
    import jax

    from llmd_kv_cache_tpu.models import llama

    cfg = falcon_cfg
    shapes = jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0))
    layer = shapes["layers"][0]

    def size(*keys):
        return sum(layer[k].size for k in keys)

    assert size("wq", "wk", "wv", "wo") == 31_457_280
    assert size("w_in", "w_ssm_out", "conv_w", "conv_b", "o_norm", "A_log",
                "D", "dt_bias") == 68_351_072
    assert size("w_gate", "w_up", "w_down") == 330_301_440
    assert size("attn_norm", "mlp_norm") == 10_240
    assert sum(x.size for x in layer.values()) == 430_120_032
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 9 * 430_120_032 + 2 * 167_116_800 + 5_120 == 4_205_319_008
    assert round(2 * n / 1e9, 2) == 8.41
    # The whole model: 72 such layers and the whole vocabulary twice.
    assert round((72 * 430_120_032 + 2 * 261_120 * 5120) / 1e9, 2) == 33.64
    streams, heads, width = fleet.cache_payload(cfg)
    assert streams * heads * width * 2 * len(cfg.page_layers) == 18_432
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(cfg))
    slot = (recurrent.size // recurrent.shape[1] * 4
            + conv.size // conv.shape[1] * 2)
    assert slot == 9 * (4_194_304 + 30_720) == 38_025_216
    assert slot // 18_432 == 2063


# -- the counts ---------------------------------------------------------------


def test_the_recurrences_work_is_counted_from_the_definition(falcon_conf,
                                                             falcon_cfg):
    counts, cfg = names.counts(falcon_conf), falcon_cfg
    state = 32 * 128 * 256
    assert counts.mamba2_scan_flops(cfg, 1) == 5 * state * 9
    assert counts.mamba2_scan_flops(cfg, 512) == (
        512 * counts.mamba2_scan_flops(cfg, 1))
    # x, B, C and the step in, y out: 2 B a value.
    assert counts.mamba2_scan_bytes(cfg, 1) == 9 * 2 * (5120 + 32 + 4096)
    assert counts.mamba2_step_bytes(cfg, 1) == 2 * 38_025_216
    assert counts.mamba2_step_bytes(cfg, 12) == (
        12 * counts.mamba2_step_bytes(cfg, 1))
    assert counts.decode_attention_bytes(cfg, 1) == 18_432
    # A row of 3 k tokens and its state weigh alike in a decode step's bytes.
    assert 0.5 < (counts.decode_attention_bytes(cfg, 3000)
                  / counts.mamba2_step_bytes(cfg, 1)) < 1.0


def test_a_chunks_flops_grow_with_its_tokens_and_its_keys(falcon_conf,
                                                          falcon_cfg):
    counts, cfg = names.counts(falcon_conf), falcon_cfg
    base = counts.prefill_flops(cfg, 0, 512)
    assert counts.prefill_flops(cfg, 0, 0) == 0
    assert counts.prefill_flops(cfg, 4096, 512) > base > 0
    # 2 x the parameters a token multiplies (every matrix of a layer; the
    # conv's taps once a channel) in nine layers.
    matrices = 430_120_032 - 10_240 - 4_096 - 96 - 5_120
    assert counts.flops_per_token(cfg) == 2.0 * 9 * matrices
    attention = 9 * 4.0 * 20 * 128 * 512 * 4096
    assert counts.prefill_flops(cfg, 4096, 512) - base == attention


# -- the reference ------------------------------------------------------------


def test_the_reference_imports_nothing_of_the_program(falcon_conf):
    path = names.KVBENCH / falcon_conf["kvbench"]["reference"]
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "jax", "numpy", "types"}
    ref = names.reference(falcon_conf)
    assert 0 < ref.TOLERANCE < 0.2
    assert not hasattr(ref, "alternatives_at")        # no router


def test_the_references_recurrence_is_the_definition(falcon_conf):
    """Its scan over tokens against a loop in float64, the state kept a
    head as ``[P, N]`` from zero; a state rounded to bfloat16 between
    tokens reads otherwise."""
    import jax.numpy as jnp

    ref = names.reference(falcon_conf)
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


def test_the_references_keys_are_scaled_before_they_are_rotated(falcon_conf):
    """RoPE pairs a head's halves and commutes with the keys' scalar; a key
    at position 0 is left as it was."""
    import jax.numpy as jnp

    ref = names.reference(falcon_conf)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(6, 2, 8)),
                    jnp.float32)
    out = np.asarray(ref._rope(x, 1e4))
    np.testing.assert_allclose(out[0], x[0], atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    angle = 3 * 1e4 ** (-1 / 4)                  # position 3, pair (1, 5)
    np.testing.assert_allclose(
        out[3, 0, 1], x[3, 0, 1] * np.cos(angle) - x[3, 0, 5] * np.sin(angle),
        rtol=1e-4)
    np.testing.assert_allclose(ref._rope(0.5 * x, 1e4), 0.5 * out, atol=1e-6)
    assert ref._rope(x, 0.0) is x


# -- the readers --------------------------------------------------------------


def event(name, dur, **stats):
    return SimpleNamespace(name=name, start=0, dur=dur, stats=stats)


def traced(conf, cfg, ops, dispatches, modules=(), work=()):
    trace = SimpleNamespace(planes=[0], ops={0: ops},
                            modules={0: list(modules)}, work=list(work),
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
    assert not {"mamba2_step_roofline", "gdn_scan_roofline"} & reported
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
            mod = names.metric(m["name"])
            assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
                m["unit"], m["source"], m["layer"], m["moves"])


DECODE, PREFILL = "jit_forward_decode_pallas", "jit_forward_prefill_pallas"


def test_the_pairs_roofline_counts_both_kernels_bytes_over_both_times(
        falcon_conf, falcon_cfg):
    ops = [event("mamba2_step.7", 300_000, program=DECODE),
           event("pallas_paged_decode_attention.2", 500_000, program=DECODE),
           event("mamba2_step.7", 900_000, program=PREFILL),
           event("fusion.4", 700_000, program=DECODE)]
    run = traced(falcon_conf, falcon_cfg, ops,
                 [event("step.dispatch", 10, state_rows=2),
                  event("step.dispatch", 10, state_rows=1),
                  event("step.dispatch", 10, scan_tokens=512)],
                 work=[{"decode_ctx": 5000}, {"decode_ctx": 3000},
                       {"prefill_tokens": 512}])
    need = 3 * 2 * 38_025_216 + 8000 * 18_432
    got = names.metric("mixer_pair_decode_roofline").compute(run)
    assert got == pytest.approx(100 * need / 819e9 / 0.8e-3)
    assert 0 < got <= 100


def test_the_pairs_shares_read_both_kernels_inside_their_program(
        falcon_conf, falcon_cfg):
    ops = [event("mamba2_step.7", 300_000, program=DECODE),
           event("pallas_paged_decode_attention.2", 500_000, program=DECODE),
           event("mamba2_scan.1", 2_000_000, program=PREFILL),
           event("pallas_paged_prefill_attention.1", 1_000_000,
                 program=PREFILL),
           event("mamba2_scan.1", 9_000_000, program=DECODE)]
    run = traced(falcon_conf, falcon_cfg, ops, [],
                 [event(DECODE, 3_000_000), event(DECODE, 1_000_000),
                  event(PREFILL, 12_000_000)])
    assert names.metric("mixer_pair_step_share").compute(run) == (
        pytest.approx(100 * 0.8 / 4.0))
    assert names.metric("mixer_pair_chunk_share").compute(run) == (
        pytest.approx(100 * 3.0 / 12.0))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_both_kernels_reports_nothing(falcon_conf,
                                                        falcon_cfg, name):
    """The parent commit, a model with one of the two mixers a layer, or no
    trace at all."""
    reader = names.metric(name)
    other = traced(falcon_conf, falcon_cfg, [
        event("pallas_paged_prefill_attention.1", 5, program=PREFILL),
        event("pallas_paged_decode_attention.1", 5, program=DECODE),
        event("gdn_step.1", 5, program=DECODE)],
        [event("step.dispatch", 10, scan_tokens=512, state_rows=2)],
        [event(DECODE, 50), event(PREFILL, 50)],
        work=[{"decode_ctx": 100}])
    assert reader.compute(other) is None
    untraced = SimpleNamespace(trace=None, cfg=falcon_cfg,
                               counts=other.counts, peaks=other.peaks)
    assert reader.compute(untraced) is None
