"""``mellum2-12b-ep4``'s own files: the configuration (every published
width and all 28 layers, ``reduced`` exactly the changed keys, the cut's
arithmetic), its reference (plain, float32, nothing of the program), its
counts (both pools' keys), the five readers of a window pool beside a global
one (a number from what the program carries, nothing from a program that
carries none) and the cell's walk at toy widths, untraced and traced."""

import ast
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import fleet, names

CONFIG = "mellum2-12b-ep4"
CELL = "mellum2-12b-ep4.short-and-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("window_hit_share", "window_evictions_per_s",
           "window_step_ms_p50", "attn_pools_decode_roofline",
           "attn_pools_step_share")


@pytest.fixture(scope="module")
def mellum_conf():
    return names.config_for_run(names.benchmark(), CONFIG, False)


@pytest.fixture(scope="module")
def mellum_cfg(mellum_conf):
    return fleet.model_config(mellum_conf)


# -- the configuration --------------------------------------------------------

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
# The catalog row's ``config`` (``Mellum2-12B-A2.5B-Instruct``), as published.
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168, "layer_types": KINDS * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_the_file_keeps_every_published_width_and_every_layer(mellum_conf):
    """Every key of the catalog row's ``config`` is in the file under its
    name and with its value, but the two keys ``reduced`` names; those two
    are exactly the keys that differ. The depth is NOT cut."""
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    conf = mellum_conf
    differ = {k for k, v in PUBLISHED.items() if k not in conf
              or conf[k] != v}
    assert differ == set(entry["reduced"]) == {"num_experts", "vocab_size"}
    assert (conf["num_experts"], conf["vocab_size"]) == (16, 24576)
    assert 64 == 4 * 16 and 98304 == 4 * 24576
    assert conf["layer_share"] == {"chips": 4, "rank": 0,
                                   "n_routed_experts": 64}
    assert conf["num_hidden_layers"] == 28
    assert "28" in conf["kvbench"]["not_reduced"]
    assert set(conf["kvbench"]["reduced"]) == set(entry["reduced"])
    assert conf["kvbench"]["source"] == entry["source"]
    for said in ("qk_norm", "prediction_module", "intermediate_size",
                 "router", "rope", "window_convention", "page_size",
                 "window_pages", "weights", "probe", "kv_bytes_per_token"):
        assert conf["kvbench"]["assumed"][said]
    assert "KiB KV/token" in conf["kvbench"]["assumed"]["kv_bytes_per_token"]
    for said in ("v5e-4", "no pipeline", "chip 0", "3,486,588,160",
                 "6.97 GB", "13.90 GB"):
        assert said in conf["kvbench"]["deployment"]


def test_the_published_keys_are_the_catalogs():
    """Where the catalog is at hand: the table above is its row."""
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert PUBLISHED == row["config"]
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]


def test_the_cell_is_the_issues(mellum_conf):
    bench = names.benchmark()
    cell = names.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "short-and-long", 1)
    mix = names.traffic("short-and-long")
    assert (mix["generator"], mix["loop"], mix["router"],
            mix["structure_seed"]) == ("sessions", "open", "kv", 63)
    assert "arrivals" not in mix                    # Poisson, the default
    assert mix["params"] == {
        "system_prompts": 4, "system_len": [256, 768], "sessions": 32,
        "zipf_system": 1.0, "zipf_session": 0.8, "user_len": [64, 2048],
        "assistant_len": [64, 1024], "max_new": [64, 256],
        "max_context": 32768, "history_turns": [0, 24]}
    assert (mix["warm_fraction"], mix["tail_fraction"],
            mix["trace_seconds"]) == (0.15, 0.2, 10)
    # A step program of 28 layers is some 4,400 device ops: the slice ends
    # after 400 steps (about 4 s), or the profiler's stop outlasts the run.
    assert mix["trace_steps"] == 400
    kv = mellum_conf["kvbench"]
    assert (kv["replicas"], kv["placement"], kv["storage"]) == (
        2, "one_chip", None)
    assert kv["engine"] == {"page_size": 64, "num_pages": 2048,
                            "max_pages_per_seq": 520, "max_batch": 16,
                            "max_prefill_tokens": 512}
    assert kv["engine"]["max_pages_per_seq"] * 64 >= 32768 + 256
    assert kv["probe"] == {"prompt_tokens": 4098, "decode_tokens": 8}
    toy = names.config_for_run(bench, CONFIG, True)
    assert toy["sliding_window"] == 2 * toy["kvbench"]["engine"]["page_size"]
    assert set(toy["layer_types"]) == {"sliding_attention", "full_attention"}


def test_short_and_deep_turns_arrive_in_one_queue(mellum_conf, mellum_cfg):
    """The mix as a window offers it: requests whose whole context is
    inside the window beside requests many windows deep, every one inside
    the context, the ids from the vocabulary's slice."""
    mix = names.with_rehearsal(names.traffic("short-and-long"), False)
    sched = names.generator("sessions").schedule(
        3_000_000_007, mix, mellum_cfg.vocab_size, 50.0)
    assert len(sched.arrivals) == round(mix["rate"] * 50.0)
    lens = np.array([len(a.prompt) for a in sched.arrivals]
                    + [len(a.prompt) for a in sched.setup])
    assert lens.max() + 256 <= 32768 + 256 and lens.max() > 16 * 1024
    assert lens.min() < 2 * 1024
    assert max(max(a.prompt) for a in sched.arrivals) < 24576


def test_the_arithmetic_of_the_cut(mellum_cfg):
    """The numbers the file and PERF.md state, from the shapes."""
    import jax

    from llmd_kv_cache_tpu.models.llama import (init_kv_cache_hybrid,
                                                init_params)

    cfg = mellum_cfg
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == 3_486_588_160
    assert sum(x.size * x.dtype.itemsize for x in leaves) == 6_973_453_312
    pools = jax.eval_shape(lambda: init_kv_cache_hybrid(cfg, 2048, 576))
    sizes = [p.size * 2 for p in pools]
    assert sizes == [939_524_096] * 2 + [792_723_456] * 2
    assert sizes[0] * 2 // 2048 == 917_504          # a global page
    assert sizes[2] * 2 // 576 == 2_752_512         # a window page
    static = 6_973_453_312 + 2 * sum(sizes)
    assert 13.85e9 < static < 13.95e9


# -- the counts ---------------------------------------------------------------


def test_both_pools_keys_are_counted(mellum_conf, mellum_cfg):
    counts, cfg = names.counts(mellum_conf), mellum_cfg
    assert counts.pools_decode_attention_bytes(cfg, 1, 0) == 7 * 2048
    assert counts.pools_decode_attention_bytes(cfg, 0, 1) == 21 * 2048
    row = counts.pools_decode_attention_bytes(cfg, 24 * 1024, 1024)
    assert row == (7 * 24 + 21) * 1024 * 2048
    # The list-less roofline's count is handed the window-capped keys: a
    # floor, under the two pools' need wherever a row is past the window.
    assert counts.decode_attention_bytes(cfg, 1024) == 28 * 1024 * 2048
    assert counts.decode_attention_bytes(cfg, 1024) < row
    assert counts.decode_attention_bytes(cfg, 500) == (
        counts.pools_decode_attention_bytes(cfg, 500, 500))
    assert counts.moe_flops(cfg, 1) == 6 * 2304 * 896
    assert counts.moe_weight_bytes(cfg, 1) == 3 * 2304 * 896 * 2


def test_a_chunks_flops_grow_with_its_tokens_and_only_the_full_layers_keys(
        mellum_conf, mellum_cfg):
    from kvbench.trace.opcount import head_flops, keys_attended

    counts, cfg = names.counts(mellum_conf), mellum_cfg
    assert counts.prefill_flops(cfg, 0, 0) == 0.0
    per_token = 2 * 28 * (2304 * (32 + 8) * 128 + 32 * 128 * 2304
                          + 2304 * 64 + 3 * 2304 * 896 * 8 * 16 / 64)
    assert counts.flops_per_token(cfg) == per_token
    pair = 4 * 32 * 128
    deep = counts.prefill_flops(cfg, 8192, 512)
    assert deep == pytest.approx(
        512 * per_token + head_flops(cfg) + pair * (
            21 * 1024 * 512 + 7 * keys_attended(8192, 512)))
    deeper = counts.prefill_flops(cfg, 16384, 512)
    assert deeper - deep == pytest.approx(pair * 7 * 8192 * 512)
    assert counts.prefill_flops(cfg, 0, 512) < counts.prefill_flops(
        cfg, 0, 1024)


# -- the reference ------------------------------------------------------------


def test_the_reference_imports_nothing_of_the_program(mellum_conf):
    path = names.KVBENCH / mellum_conf["kvbench"]["reference"]
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "jax", "numpy", "types", "heapq",
                        "itertools", "math"}
    ref = names.reference(mellum_conf)
    assert 0 < ref.TOLERANCE < 0.2 and 0 < ref.MARGIN < 0.2
    assert ref.LIMIT == 8 and hasattr(ref, "alternatives_at")


def test_the_references_window_and_yarn_are_the_definitions(mellum_conf):
    """A query at ``i`` of a window layer sees ``window`` keys, itself the
    last; yarn keeps the fast dims, divides the slow ones by the factor and
    scales cos and sin."""
    import jax.numpy as jnp

    ref = names.reference(mellum_conf)
    s, window = 12, 4
    q = jnp.ones((s, 1, 1, 8))
    k = jnp.ones((s, 1, 8))
    v = jnp.eye(s)[:, None, :]                       # a key's own index
    seen = np.asarray(ref._attend_block(q, k, v, 0, window))[:, 0, 0] > 0
    for i in range(s):
        assert list(np.flatnonzero(seen[i])) == list(
            range(max(0, i - window + 1), i + 1))
    every = np.asarray(ref._attend_block(q, k, v, 0, 0))[:, 0, 0] > 0
    assert every.sum() == s * (s + 1) // 2
    plain, one = ref.rope_frequencies(128, 500000.0, ())
    freqs, att = ref.rope_frequencies(
        128, 500000.0, ("yarn", 16.0, 32.0, 1.0, 8192.0, 1.2772588722239782))
    assert one == 1.0 and att == 1.2772588722239782
    assert np.allclose(freqs[:19], plain[:19])        # extrapolated
    assert np.allclose(freqs[35:], plain[35:] / 16)   # interpolated
    assert np.all(np.diff(freqs / plain)[18:34] < 0)  # the ramp between


def test_the_references_router_is_the_softmax_over_the_chosen(mellum_conf):
    toy = names.config_for_run(names.benchmark(), CONFIG, True)
    ref = names.reference(toy)
    logits = np.array([0.3, 2.0, -1.0, 1.9, 0.0, 1.0, 0.99, -3.0])
    assert ref.admitted(logits, 3, 0.0) == [(1, 3, 5)]
    assert ref.admitted(logits, 3, 0.05) == [(1, 3, 5), (1, 3, 6)]
    # Held 0-3: both choices give this chip experts 1 and 3.
    assert [c[1] for c in ref.choices(logits, 3, 0.05, (0, 4))] == [(1, 3, 5)]
    assert [c[1] for c in ref.choices(logits, 3, 0.05, (4, 4))] == [
        (1, 3, 5), (1, 3, 6)]


# -- the readers --------------------------------------------------------------


def event(name, dur, **stats):
    return SimpleNamespace(name=name, start=0, dur=dur, stats=stats)


def traced(conf, cfg, ops=(), dispatches=(), modules=(), lookups=(),
           windows=(), work=()):
    trace = SimpleNamespace(
        planes=[0], ops={0: list(ops)}, modules={0: list(modules)},
        work=list(work), events={"step.dispatch": list(dispatches),
                                 "enqueue.lookup": list(lookups),
                                 "step.window": list(windows)})
    return SimpleNamespace(
        trace=trace, cfg=cfg, counts=names.counts(conf), seconds=50.0,
        pool_before={}, pool_after={},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_cell_reports_its_five_metrics_and_every_listless_one():
    bench = names.benchmark()
    reported = {m["name"] for m in names.cell_metrics(bench, CELL, True)}
    assert set(READERS) <= reported
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= reported
    assert not {"moe_dispatch_roofline", "state_hit_share"} & reported
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
            mod = names.metric(m["name"])
            assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
                m["unit"], m["source"], m["layer"], m["moves"])


DECODE, PREFILL = "jit_forward_decode_pallas", "jit_forward_prefill_pallas"


def test_the_pools_roofline_counts_both_pools_over_the_decode_kernels_time(
        mellum_conf, mellum_cfg):
    ops = [event("pallas_paged_decode_attention.2", 500_000, program=DECODE),
           event("pallas_paged_decode_attention.9", 300_000, program=DECODE),
           event("pallas_paged_prefill_attention.1", 900_000,
                 program=PREFILL),
           event("gmm.4", 700_000, program=DECODE)]
    run = traced(
        mellum_conf, mellum_cfg, ops,
        [event("step.dispatch", 10, full_keys=20_000, window_keys=2_000),
         event("step.dispatch", 10, full_keys=10_000, window_keys=1_000),
         event("step.dispatch", 10, full_keys=9_000, window_keys=1_535,
               prefill_pos=8_488)],
        modules=[event(DECODE, 3_000_000), event(DECODE, 1_000_000),
                 event(PREFILL, 12_000_000)],
        work=[{"decode_ctx": 3_000}])
    need = (7 * 30_000 + 21 * 3_000) * 2048
    got = names.metric("attn_pools_decode_roofline").compute(run)
    assert got == pytest.approx(100 * need / 819e9 / 0.8e-3)
    assert 0 < got <= 100
    # The list-less reader over the same seconds and the capped keys.
    floor = names.metric("attn_decode_roofline").compute(run)
    assert floor == pytest.approx(100 * 28 * 3_000 * 2048 / 819e9 / 0.8e-3)
    assert floor < got
    assert names.metric("attn_pools_step_share").compute(run) == (
        pytest.approx(100 * 0.8 / 4.0))


def test_the_window_pools_counters_are_read_where_the_engine_leaves_them(
        mellum_conf, mellum_cfg):
    run = traced(
        mellum_conf, mellum_cfg,
        lookups=[event("enqueue.lookup", 5, page_hit_tokens=4096,
                       window_hit_tokens=4096),
                 event("enqueue.lookup", 5, page_hit_tokens=8192,
                       window_hit_tokens=0),
                 event("enqueue.lookup", 5, page_hit_tokens=0,
                       window_hit_tokens=0)],
        windows=[event("step.window", 2_000_000, pod="pod-0", step=4,
                       ensured=1),
                 event("step.window", 1_000_000, pod="pod-0", step=4,
                       reclaimed=2),
                 event("step.window", 5_000_000, pod="pod-1", step=4,
                       ensured=8),
                 event("step.window", 1_000_000, pod="pod-0", step=5,
                       reclaimed=1)])
    assert names.metric("window_hit_share").compute(run) == pytest.approx(
        100 * 4096 / 12288)
    assert names.metric("window_step_ms_p50").compute(run) == 3.0
    run.pool_before = {"pod-0": {"evictions": 1, "window_evictions": 10},
                       "pod-1": {"evictions": 1, "window_evictions": 0}}
    run.pool_after = {"pod-0": {"evictions": 9, "window_evictions": 110},
                      "pod-1": {"evictions": 9, "window_evictions": 50}}
    assert names.metric("window_evictions_per_s").compute(run) == 3.0
    nothing_matched = traced(mellum_conf, mellum_cfg, lookups=[
        event("enqueue.lookup", 5, page_hit_tokens=0, window_hit_tokens=0)])
    assert names.metric("window_hit_share").compute(nothing_matched) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_program_with_one_pool_reports_nothing(mellum_conf, mellum_cfg,
                                                 name):
    """The parent commit, a model with one pool, or no trace at all: the
    kernel, the program and the phases are there, the counters are not."""
    reader = names.metric(name)
    other = traced(
        mellum_conf, mellum_cfg,
        [event("pallas_paged_decode_attention.1", 5, program=DECODE)],
        [event("step.dispatch", 10, rows=2, state_rows=2),
         event("step.dispatch", 10, prefill_pos=0, expanded_keys=0)],
        [event(DECODE, 50), event(PREFILL, 50)],
        lookups=[event("enqueue.lookup", 5, blocks=4, hit_blocks=2,
                       page_hit_tokens=128, state_hit_tokens=64)],
        work=[{"decode_ctx": 100}])
    other.pool_before = other.pool_after = {"pod-0": {"evictions": 3}}
    assert reader.compute(other) is None
    untraced = SimpleNamespace(
        trace=None, cfg=mellum_cfg, counts=other.counts, peaks=other.peaks,
        seconds=50.0, pool_before=other.pool_before,
        pool_after=other.pool_after)
    assert reader.compute(untraced) is None
    # A chunk's two sums alone are no decode step's.
    chunk_only = traced(mellum_conf, mellum_cfg, [
        event("pallas_paged_decode_attention.1", 5, program=DECODE)], [
        event("step.dispatch", 10, prefill_pos=0, full_keys=512,
              window_keys=512)], [event(DECODE, 50)])
    if name.startswith("attn_pools"):
        assert reader.compute(chunk_only) is None


# -- the walk -----------------------------------------------------------------


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_rehearsal_walks_the_cell(trace):
    """``kvbench/run.py --rehearse``: correct, nothing failed, both pools
    through the interpreted kernels; traced, every per-layer metric the
    cell owes is in the line (``check_line`` stops a run that lacks one),
    the five new ones among them, with decode steps launched ahead."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "kvbench/run.py", "--workload", CELL, "--seed",
         "2900000563", "--seconds", "8", "--trace", trace, "--rehearse"],
        capture_output=True, text=True, timeout=500,
        cwd=names.KVBENCH.parent, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["serving_faults"] == [0.0, 0.0]
    if trace == "0":
        assert set(line["metrics"]) == {"ttft_p50_ms", "itl_mean_ms",
                                        "out_tok_s", "setup_s"}
        return
    owed = {m["name"] for m in names.cell_metrics(names.benchmark(), CELL,
                                                  True)}
    assert owed <= set(line["metrics"])
    assert set(READERS) <= set(line["metrics"])
    assert line["metrics"]["window_hit_share"]["value"] > 0.0
    assert line["metrics"]["window_step_ms_p50"]["value"] > 0.0
    assert line["metrics"]["launched_ahead_share"]["value"] > 0.0
