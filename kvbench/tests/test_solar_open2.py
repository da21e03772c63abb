"""``solar-open2-ep16-l8``'s own files: its reference (plain, float32, a
token at a time, nothing of the program), its counts (the recurrence's work
from the definition, the configuration's arithmetic) and the three readers
(a number from what the program carries, nothing from a program that
carries none)."""

import ast
from types import SimpleNamespace

import numpy as np
import pytest

from kvbench.harness import fleet, names

CONFIG = "solar-open2-ep16-l8"
CELL = "solar-open2-ep16-l8.sessions-64k"


@pytest.fixture(scope="module")
def conf():
    return names.config_for_run(names.benchmark(), CONFIG, False)


@pytest.fixture(scope="module")
def cfg(conf):
    return fleet.model_config(conf)


# -- the configuration --------------------------------------------------------


def test_the_file_keeps_every_published_width(conf):
    """Every number of the catalog row's ``config`` is in the file under its
    key, but the four keys ``reduced`` names."""
    entry = next(c for c in names.benchmark()["configs"]
                 if c["name"] == CONFIG)
    published = {
        "partial_rotary_factor": 1, "hidden_size": 4096,
        "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "gqa_interval": 3, "n_routed_experts": 320, "n_shared_experts": 1,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    differ = {k for k, v in published.items() if conf.get(k) != v}
    assert differ == set(entry["reduced"]) - {"gqa_layers"}
    assert conf["gqa_layers"] == [0, 4]
    assert conf["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert set(conf["kvbench"]["reduced"]) == set(entry["reduced"])
    assert conf["kvbench"]["source"] == entry["source"]


def test_the_arithmetic_of_the_cut(cfg):
    """3.90 B parameters (7.80 GB in bf16), 8,192 B of pages a token and
    26.05 MB of state a sequence: ISSUE 48's numbers, from the shapes."""
    import jax

    from llmd_kv_cache_tpu.models import llama

    shapes = jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e9 - 3.90) < 0.005
    streams, heads, width = fleet.cache_payload(cfg)
    assert streams * heads * width * 2 * len(cfg.page_layers) == 8192
    recurrent, conv = jax.eval_shape(lambda: llama.init_state_pool(cfg))
    slot = (recurrent.size // recurrent.shape[1] * 4
            + conv.size // conv.shape[1] * 2)
    assert slot == 6 * (4_194_304 + 147_456) and round(slot / 1e6, 2) == 26.05


# -- the counts ---------------------------------------------------------------


def test_the_recurrences_work_is_counted_from_the_definition(conf, cfg):
    counts = names.counts(conf)
    assert counts.kda_scan_flops(cfg, 1) == 7 * 128 * 128 * 64 * 6
    assert counts.kda_scan_flops(cfg, 512) == 512 * counts.kda_scan_flops(
        cfg, 1)
    # q, k, the decay a key channel and v in, o out: 2 B a value.
    assert counts.kda_scan_bytes(cfg, 1) == 6 * 2 * 5 * 64 * 128
    assert counts.kda_step_bytes(cfg, 1) == 6 * 4_194_304 * 2
    assert counts.kda_step_bytes(cfg, 8) == 8 * counts.kda_step_bytes(cfg, 1)
    assert counts.decode_attention_bytes(cfg, 1) == 8192
    # At these widths the scan's floor is its bytes (0.60 us a token at
    # 819 GB/s), not its operations (0.22 us at 197 TFLOP/s).
    assert (counts.kda_scan_bytes(cfg, 512) / 819e9
            > counts.kda_scan_flops(cfg, 512) / 197e12)


def test_a_chunks_flops_grow_with_its_tokens_and_its_keys(conf, cfg):
    counts = names.counts(conf)
    base = counts.prefill_flops(cfg, 0, 512)
    assert counts.prefill_flops(cfg, 0, 0) == 0
    assert counts.prefill_flops(cfg, 32768, 512) > base > 0
    per_token = counts.flops_per_token(cfg)
    # 2 x the parameters a token multiplies: the mixers, the shared expert
    # and gate whole, and its 8 x 20 / 320 experts here.
    assert 1.9e9 < per_token < 3.0e9
    attention = 4.0 * 64 * 128 * 2 * 512 * 32768
    assert counts.prefill_flops(cfg, 32768, 512) - base == attention


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


def test_the_references_recurrence_is_the_definition(conf):
    """Its scan over tokens against a loop in float64, the state kept a
    head as ``[key_dim, value_dim]``; a state rounded to bfloat16 between
    tokens and one decay a head read otherwise."""
    import jax.numpy as jnp

    ref = names.reference(conf)
    rng = np.random.default_rng(3)
    s, h, dk, dv = 24, 2, 8, 4
    k = rng.normal(size=(s, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(s, h, dk))
    v = rng.normal(size=(s, h, dv))
    alpha = rng.uniform(0.2, 1.0, size=(s, h, dk))
    beta = rng.uniform(0, 2, size=(s, h))
    S = np.zeros((h, dk, dv))
    want = []
    for t in range(s):
        for i in range(h):
            S[i] = alpha[t, i][:, None] * S[i]
            S[i] += np.outer(k[t, i], beta[t, i] * (v[t, i] - k[t, i] @ S[i]))
        want.append(np.einsum("hkv,hk->hv", S, q[t]))
    args = [jnp.asarray(a, jnp.float32) for a in (q, k, v, alpha, beta)]
    got = ref._recurrence(*args, jnp.zeros((), jnp.float32))
    np.testing.assert_allclose(got, np.stack(want), atol=1e-5)
    low = ref._recurrence(*args, jnp.zeros((), jnp.bfloat16))
    assert 1e-4 < np.abs(np.asarray(low) - np.stack(want)).max() < 0.2
    args[3] = jnp.broadcast_to(args[3].mean(-1, keepdims=True),
                               args[3].shape)
    other = ref._recurrence(*args, jnp.zeros((), jnp.float32))
    assert np.abs(np.asarray(other) - np.stack(want)).max() > 1e-2


# -- the readers --------------------------------------------------------------


def event(name, dur, **stats):
    return SimpleNamespace(name=name, start=0, dur=dur, stats=stats)


def traced(conf, cfg, ops, dispatches):
    trace = SimpleNamespace(planes=[0], ops={0: ops},
                            events={"step.dispatch": dispatches})
    return SimpleNamespace(
        trace=trace, cfg=cfg, counts=names.counts(conf),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_cell_reports_its_three_metrics():
    reported = {m["name"] for m in names.cell_metrics(names.benchmark(),
                                                      CELL, True)}
    assert {"kda_scan_roofline", "kda_step_roofline",
            "state_orphaned_per_s"} <= reported
    assert "gdn_scan_roofline" not in reported


def test_the_scans_share_is_the_definitions_work_over_the_kernels_time(
        conf, cfg):
    reader = names.metric("kda_scan_roofline")
    least = 1000 * 6 * 2 * 5 * 64 * 128 / 819e9     # its bytes: the larger
    run = traced(conf, cfg, [
        event("kda_scan.3", 2_000_000, program="jit_forward_prefill_pallas"),
        event("kda_scan.3", 9_000_000, program="jit_forward_decode_pallas"),
        event("fusion.1", 5_000_000, program="jit_forward_prefill_pallas")],
        [event("step.dispatch", 10, scan_tokens=600, prefill_pos=0),
         event("step.dispatch", 10, scan_tokens=400, prefill_pos=600),
         event("step.dispatch", 10, state_rows=3)])
    assert reader.compute(run) == pytest.approx(100 * least / 2e-3)
    assert 0 < reader.compute(run) <= 100


def test_the_steps_share_is_the_states_bytes_over_the_kernels_time(conf,
                                                                   cfg):
    reader = names.metric("kda_step_roofline")
    run = traced(conf, cfg, [
        event("kda_step.7", 400_000, program="jit_forward_decode_pallas"),
        event("kda_step.8", 400_000, program="jit_forward_decode_pallas")],
        [event("step.dispatch", 10, state_rows=2),
         event("step.dispatch", 10, state_rows=1)])
    assert reader.compute(run) == pytest.approx(
        100 * 3 * 6 * 4_194_304 * 2 / 819e9 / 0.8e-3)


@pytest.mark.parametrize("name", ["kda_scan_roofline", "kda_step_roofline"])
def test_a_program_without_the_kernels_reports_nothing(conf, cfg, name):
    """The parent commit, or a slice that holds no chunk or no step."""
    reader = names.metric(name)
    other = traced(conf, cfg, [
        event("gdn_scan.1", 5, program="jit_forward_prefill_pallas"),
        event("gdn_step.1", 5, program="jit_forward_decode_pallas")],
        [event("step.dispatch", 10, scan_tokens=512, state_rows=2)])
    assert reader.compute(other) is None
    untraced = SimpleNamespace(trace=None, cfg=cfg, counts=other.counts,
                               peaks=other.peaks)
    assert reader.compute(untraced) is None


def test_orphaned_snapshots_are_counted_over_the_window():
    reader = names.metric("state_orphaned_per_s")
    run = SimpleNamespace(
        seconds=10.0,
        pool_before={"a": {"state_orphaned": 3}, "b": {"state_orphaned": 0}},
        pool_after={"a": {"state_orphaned": 10}, "b": {"state_orphaned": 5}})
    assert reader.compute(run) == pytest.approx(1.2)
    parent = SimpleNamespace(seconds=10.0, pool_before={"a": {}},
                             pool_after={"a": {"state_evictions": 4}})
    assert reader.compute(parent) is None
