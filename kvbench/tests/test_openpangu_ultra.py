"""``openpangu-ultra-ep32-l5``'s own files: the configuration's arithmetic
from the shapes, its counts, its reference (plain, float32, nothing of the
program; few alternatives a position), the three readers (a number from what
the program carries, nothing from a program that carries none), and the
configuration walked through a traced rehearsal with every per-layer reader
of its cell returning a number."""

import ast
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import fleet, names

CONFIG = "openpangu-ultra-ep32-l5"
CELL = "openpangu-ultra-ep32-l5.agent-loops-32k"
NEW = {"spec_accept_share", "mtp_draft_share", "mla_verify_roofline"}


@pytest.fixture(scope="module")
def conf():
    return names.config_for_run(names.benchmark(), CONFIG, False)


@pytest.fixture(scope="module")
def cfg(conf):
    return fleet.model_config(conf)


# -- the configuration --------------------------------------------------------


def test_the_file_keeps_every_published_width(conf):
    """Every number of the catalog row's ``config`` is in the file under its
    key, but the four keys ``reduced`` names; the module's key stays 1."""
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    published = {
        "first_k_dense_replace": 3, "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "v_head_dim": 128,
        "vocab_size": 153600}
    differ = {k for k, v in published.items() if conf.get(k) != v}
    assert differ == set(entry["reduced"]) == set(conf["kvbench"]["reduced"])
    assert conf["model_type"] == "pangu_ultra_moe" and conf["sandwich_norm"]
    assert conf["layer_share"] == {"chips": 32, "rank": 0,
                                   "n_routed_experts": 256}
    assert conf["kvbench"]["source"] == entry["source"]
    assert "4,150,425,600" in conf["kvbench"]["deployment"]


def test_the_arithmetic_of_the_cut(cfg):
    """4,150,425,600 parameters and 7,680 B of pages a token: ISSUE 53's
    numbers, from the shapes (every matrix and norm; the five routers'
    correction biases, 256 float32 each, are not counted there)."""
    import jax

    from llmd_kv_cache_tpu.models import llama

    shapes = jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n = sum(x.size for path, x in flat
            if "router_bias" not in jax.tree_util.keystr(path))
    assert n == 4_150_425_600
    assert shapes["mtp"]["w_eh"].shape == (2 * 7680, 7680)
    assert shapes["mtp"]["layer"]["w_gate"].shape == (8, 7680, 2048)
    assert shapes["layers"][1]["router"].shape == (7680, 256)
    streams, heads, width = fleet.cache_payload(cfg)
    assert (streams, heads, width) == (1, 1, 640)
    assert streams * heads * width * 2 * len(cfg.page_layers) == 7680
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 3000))
    assert k.shape == (6, 3000, 1, 64, 640) and v.size == 0


# -- the counts ---------------------------------------------------------------


def test_the_counts_follow_the_shapes(conf, cfg):
    counts = names.counts(conf)
    assert counts.decode_attention_bytes(cfg, 1) == 7680
    # 256 query rows a key over 576 + 512 lanes, six layers.
    assert counts.verify_attention_flops(cfg, 1, 2) == 6 * 256 * 1088 * 2
    assert counts.verify_attention_flops(cfg, 1000, 2) == (
        1000 * counts.verify_attention_flops(cfg, 1, 2))
    # Compute-bound where one position a row is bandwidth-bound.
    flops_per_byte = (counts.verify_attention_flops(cfg, 1, 2)
                      / counts.decode_attention_bytes(cfg, 1))
    assert flops_per_byte == pytest.approx(435.2)
    assert counts.verify_attention_flops(cfg, 1, 1) / 7680 < 197e12 / 819e9
    base = counts.prefill_flops(cfg, 0, 512)
    assert counts.prefill_flops(cfg, 0, 0) == 0
    assert counts.prefill_flops(cfg, 16384, 512) > base > 0
    # 2 x the parameters a token multiplies: six attentions, the dense
    # feed-forward, five shared experts and gates, W_eh, and its 8 x 8 / 256
    # experts here; neither vocabulary matrix (a chunk samples one position).
    assert 3.9e9 < counts.flops_per_token(cfg) < 4.2e9
    attention = 2.0 * 128 * (128 + 64 + 128) * 6 * 512 * 16384
    assert counts.prefill_flops(cfg, 16384, 512) - base == pytest.approx(
        attention, rel=1e-9)


# -- the reference ------------------------------------------------------------


def test_the_reference_imports_nothing_of_the_program(conf):
    path = names.KVBENCH / conf["kvbench"]["reference"]
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "heapq", "itertools", "jax", "numpy",
                        "types"}
    ref = names.reference(conf)
    assert 0 < ref.TOLERANCE < 0.2 and 0 < ref.MARGIN < 0.05
    assert callable(ref.draft_logits_at) and callable(ref.alternatives_at)


def test_few_alternatives_a_position_at_toy_widths():
    """``alternatives_at`` over the rehearsal's model: row 0 is
    ``logits_at``'s, and no position has more answers than the probe
    admits."""
    import jax

    from llmd_kv_cache_tpu.models import llama

    conf = names.config_for_run(names.benchmark(), CONFIG, True)
    cfg = fleet.model_config(conf)
    ref = names.reference(conf)
    params = llama.init_params(jax.random.PRNGKey(11), cfg)
    tokens = np.random.default_rng(2).integers(1, cfg.vocab_size, 48).tolist()
    at = list(range(40, 48))
    alts = ref.alternatives_at(params, cfg, tokens, at)
    own = ref.logits_at(params, cfg, tokens, at)
    assert [len(a) for a in alts] and max(len(a) for a in alts) < 8
    for a, row in zip(alts, own):
        np.testing.assert_array_equal(a[0], row)
    drafts = ref.draft_logits_at(params, cfg, tokens, at[:-1])
    assert drafts.shape == (7, cfg.vocab_size)
    assert np.abs(drafts - own[:-1]).max() > 1e-3  # another layer's logits
    with pytest.raises(ValueError, match="next token"):
        ref.draft_logits_at(params, cfg, tokens, [47])


# -- the readers --------------------------------------------------------------


def event(name, dur, start=0, **stats):
    return SimpleNamespace(name=name, start=start, dur=dur, end=start + dur,
                           stats=stats)


def traced(conf, cfg, ops=(), modules=(), dispatches=(), fetches=(),
           work=()):
    trace = SimpleNamespace(
        planes=[0], ops={0: list(ops)}, modules={0: list(modules)},
        events={"step.dispatch": list(dispatches),
                "step.fetch": list(fetches)}, work=list(work))
    return SimpleNamespace(
        trace=trace, cfg=cfg, counts=names.counts(conf),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_cell_reports_its_three_metrics():
    reported = {m["name"] for m in names.cell_metrics(names.benchmark(),
                                                      CELL, True)}
    assert NEW <= reported
    assert not reported & {"dsa_select_share", "gdn_scan_roofline",
                           "kda_scan_roofline", "held_assignment_share"}


def test_acceptance_is_accepted_over_verified(conf, cfg):
    reader = names.metric("spec_accept_share")
    run = traced(conf, cfg, fetches=[
        event("step.fetch", 5, spec_drafted=8, spec_accepted=1),
        event("step.fetch", 5, spec_drafted=8, spec_accepted=0),
        event("step.fetch", 5, launch=3)])
    assert reader.compute(run) == pytest.approx(100 / 16)
    none = traced(conf, cfg, fetches=[
        event("step.fetch", 5, spec_drafted=4, spec_accepted=0)])
    assert reader.compute(none) == 0.0
    parent = traced(conf, cfg, fetches=[event("step.fetch", 5, launch=3)])
    assert reader.compute(parent) is None


def test_the_drafters_share_is_what_runs_behind_the_acceptance(conf, cfg):
    reader = names.metric("mtp_draft_share")
    program = "jit_forward_decode_pallas"
    ops = [event("fusion.1", 600, 100, program=program),
           event("pallas_paged_decode_attention.2", 200, 700,
                 program=program),
           event("mtp_accept.1", 10, 900, program=program),
           event("fusion.9", 150, 910, program=program),
           event("pallas_paged_decode_attention.7", 40, 1060,
                 program=program),
           # a chunk's ops and another run without the marker count nothing
           event("fusion.1", 999, 2000, program="jit_forward_prefill_pallas"),
           event("fusion.1", 500, 5000, program=program)]
    modules = [event(program + "(1)", 1100, 50),
               event(program + "(1)", 600, 4990)]
    run = traced(conf, cfg, ops, modules)
    assert reader.compute(run) == pytest.approx(100 * 190 / 1000)
    assert reader.compute(traced(conf, cfg, ops[:2], modules)) is None
    untraced = SimpleNamespace(trace=None, cfg=cfg)
    assert reader.compute(untraced) is None


def test_the_verify_kernels_share_takes_the_larger_bound(conf, cfg):
    reader = names.metric("mla_verify_roofline")
    program = "jit_forward_decode_pallas"
    ops = [event("pallas_paged_decode_attention.2", 2_000_000,
                 program=program),
           event("pallas_paged_decode_attention.3", 2_000_000,
                 program=program),
           event("fusion.1", 5_000_000, program=program)]
    run = traced(conf, cfg, ops,
                 dispatches=[event("step.dispatch", 5, spec_drafted=8)],
                 work=[{"decode_ctx": 100_000}, {"decode_ctx": 36_000}])
    need = 136_000 * 6 * 256 * 1088 * 2 / 197e12      # its FLOPs: the larger
    assert need > 136_000 * 7680 / 819e9
    assert reader.compute(run) == pytest.approx(100 * need / 4e-3)
    assert 0 < reader.compute(run) < 100
    # A program that verifies nothing (the parent, another model).
    plain = traced(conf, cfg, ops,
                   dispatches=[event("step.dispatch", 5, rows=8)],
                   work=[{"decode_ctx": 100_000}])
    assert reader.compute(plain) is None
    assert reader.compute(SimpleNamespace(trace=None, cfg=cfg,
                                          counts=run.counts)) is None


# -- the walk -----------------------------------------------------------------


def test_a_traced_rehearsal_reports_every_reader_of_the_cell():
    """``kvbench/run.py --rehearse --trace 1``: correct, nothing failed, and
    every per-layer metric the cell owes is in the line (``check_line``
    stops a run that lacks one), the three new ones among them, with the
    speculative step launched ahead."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "kvbench/run.py", "--workload", CELL, "--seed",
         "2900000553", "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=400,
        cwd=names.KVBENCH.parent, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    owed = {m["name"] for m in names.cell_metrics(names.benchmark(), CELL,
                                                  True)}
    assert owed <= set(line["metrics"]) | {"ttft_p50_ms", "itl_mean_ms",
                                           "out_tok_s", "setup_s",
                                           "itl_p95_ms"}
    assert NEW <= set(line["metrics"])
    assert line["metrics"]["spec_accept_share"]["value"] >= 0.0
    assert line["metrics"]["launched_ahead_share"]["value"] > 0.0
    assert line["metrics"]["programs_per_step"]["value"] < 1.5
