"""The per-head prefill kernel of a latent-attention layer
(``ops.pallas_latent_prefill``) in interpret mode against a plain per-head
``jax.numpy`` reference at toy widths, and the rule that picks it."""

import json
import math
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops.pallas_latent_prefill import (
    KERNEL_PER_HEAD_PREFILL, pallas_per_head_prefill_attention,
    per_head_expanded_keys, per_head_min_queries)
from llmd_kv_cache_tpu.ops.pallas_paged_attention import _NEG_INF

CONFIGS = pathlib.Path(__file__).parent.parent / "kvbench" / "configs"
RANK, ROPE, PAD, PAGE, PAGES_PER_SEQ = 16, 8, 8, 4, 160


def build_case(ctx, new, q_seq, heads=4, nope=8, v_dim=8, layers=None,
               layer=0, selection=0, seed=0, dtype=jnp.float32):
    """One row's pages (a stacked cache where ``layers`` is set), its
    chunk's queries padded to ``q_seq`` and the heads' up-projections;
    ``selection`` > 0 also draws a bias that keeps that many of a query's
    causal keys (the latest always, as a selection does)."""
    rng = np.random.default_rng(seed)
    width = RANK + ROPE + PAD
    total = ctx + new
    num_pages = 1 + PAGES_PER_SEQ
    rows = rng.normal(size=(PAGES_PER_SEQ * PAGE, width)).astype(np.float32)
    rows[:, RANK + ROPE:] = 0.0
    rows[total:] = 0.0
    pages = np.zeros((layers or 1, num_pages, 1, PAGE, width), np.float32)
    pages[layer if layers else 0, 1:, 0] = rows.reshape(
        PAGES_PER_SEQ, PAGE, width)
    if layers:
        # Another layer's pages must not be read.
        pages[(layer + 1) % layers, 1:] = 7.0
    case = {
        "q_nope": rng.normal(size=(1, q_seq, heads, nope)),
        "q_rope": rng.normal(size=(1, q_seq, heads, ROPE)),
        "w_uk": rng.normal(size=(heads, RANK, nope)) * RANK ** -0.5,
        "w_uv": rng.normal(size=(heads, RANK, v_dim)) * RANK ** -0.5,
    }
    case = {k: jnp.asarray(v, dtype) for k, v in case.items()}
    case["latent_pages"] = jnp.asarray(pages if layers else pages[0], dtype)
    case["page_table"] = jnp.asarray(
        1 + np.arange(PAGES_PER_SEQ)[None, :], jnp.int32)
    case["ctx_lens"] = jnp.asarray([ctx], jnp.int32)
    case["total_lens"] = jnp.asarray([total], jnp.int32)
    bias = None
    if selection:
        bias = np.full((1, q_seq, PAGES_PER_SEQ * PAGE), _NEG_INF, np.float32)
        for i in range(q_seq):
            reach = min(ctx + i, total - 1) + 1
            kept = rng.choice(reach, size=min(selection, reach),
                              replace=False)
            bias[0, i, kept] = 0.0
            bias[0, i, reach - 1] = 0.0
        bias = jnp.asarray(bias)
    return case, bias, jnp.asarray(rows, dtype)


def reference(case, bias, rows, scale):
    """Per head, in float32: keys and values expanded from the latent
    rows, causal softmax over the keys a query sees and keeps."""
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    q_nope, q_rope = f32(case["q_nope"])[0], f32(case["q_rope"])[0]
    w_uk, w_uv, rows = f32(case["w_uk"]), f32(case["w_uv"]), f32(rows)
    ctx, total = int(case["ctx_lens"][0]), int(case["total_lens"][0])
    c_kv, k_rope = rows[:, :RANK], rows[:, RANK:RANK + ROPE]
    k = np.einsum("tr,hrd->htd", c_kv, w_uk)
    v = np.einsum("tr,hrv->htv", c_kv, w_uv)
    s = (np.einsum("shd,htd->hst", q_nope, k)
         + np.einsum("shd,td->hst", q_rope, k_rope)) * scale
    pos = np.arange(rows.shape[0])[None, :]
    q_pos = ctx + np.arange(q_nope.shape[0])[:, None]
    seen = (pos <= q_pos) & (pos < total)
    if bias is not None:
        seen &= np.asarray(bias)[0] > 0.5 * _NEG_INF
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hst,htv->shv", p, v)


CASES = {
    # ctx, new, q_seq, then build_case's keywords
    "from-nothing": (0, 256, 256, {}),
    "behind-a-context": (100, 256, 256, {}),
    "padded-last-chunk": (37, 200, 256, {}),
    "512-queries": (64, 500, 512, {}),
    "stacked-cache": (64, 256, 256, {"layers": 3, "layer": 1}),
    "wider-values": (20, 256, 256, {"v_dim": 16}),
    "selection": (100, 256, 256, {"selection": 24}),
    "selection-padded-512": (90, 400, 512, {"selection": 40}),
    "selection-stacked": (0, 250, 256,
                          {"selection": 24, "layers": 2, "layer": 1}),
}


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("head_group", [None, 2])
def test_the_kernel_is_the_per_head_reference(name, head_group):
    ctx, new, q_seq, kw = CASES[name]
    layer = kw.get("layer") if kw.get("layers") else None
    case, bias, rows = build_case(ctx, new, q_seq, **kw)
    scale = (8 + ROPE) ** -0.5 * 1.3
    out = pallas_per_head_prefill_attention(
        **case, scale=scale, layer_idx=layer, bias=bias,
        head_group=head_group, pages_per_block=32, interpret=True)
    assert out.shape == (1, q_seq, 4, kw.get("v_dim", 8))
    assert np.isfinite(np.asarray(out)).all()  # the padded queries too
    np.testing.assert_allclose(
        np.asarray(out[0, :new]), reference(case, bias, rows, scale)[:new],
        rtol=2e-4, atol=2e-4)


def test_the_default_superblock_and_bfloat16():
    """The defaults (1024 keys a superblock, here the row's 640 whole; all
    the heads one group) at the served dtype: within bfloat16's rounding
    of the float32 reference on the same bfloat16 inputs."""
    case, bias, rows = build_case(300, 256, 256, selection=64,
                                  dtype=jnp.bfloat16)
    scale = (8 + ROPE) ** -0.5
    out = pallas_per_head_prefill_attention(
        **case, scale=scale, bias=bias, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = reference(case, bias, rows, scale)
    err = np.abs(np.asarray(out[0], np.float32) - ref).max()
    assert err < 0.05 * np.abs(ref).max(), err


def test_a_query_that_keeps_nothing_in_a_superblock_adds_nothing():
    """A selection that lies wholly in the second superblock: the first
    must leave no trace in the softmax state."""
    case, _, rows = build_case(200, 256, 256)
    bias = np.full((1, 256, PAGES_PER_SEQ * PAGE), _NEG_INF, np.float32)
    bias[:, :, 150:190] = 0.0
    bias = jnp.asarray(bias)
    out = pallas_per_head_prefill_attention(
        **case, scale=0.25, bias=bias, pages_per_block=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out[0]), reference(case, bias, rows, 0.25),
        rtol=2e-4, atol=2e-4)


def test_the_kernels_name_is_its_wrappers():
    assert (pallas_per_head_prefill_attention.__name__
            == KERNEL_PER_HEAD_PREFILL)


@pytest.mark.parametrize("config", ["deepseek-v3.2-exp-ep16-l5",
                                    "gigachat3.5-ep16-l5"])
def test_break_even_at_published_widths(config):
    """191 queries at both latent configurations' widths: the engine's
    chunks of 256 and 512 go per head, 128 and below stay absorbed."""
    doc = json.loads((CONFIGS / f"{config}.json").read_text())
    rank, rope = doc["kv_lora_rank"], doc["qk_rope_head_dim"]
    width = -(-(rank + rope) // 128) * 128
    least = per_head_min_queries(width, rank, doc["qk_nope_head_dim"],
                                 doc["v_head_dim"])
    assert least == 191
    assert 256 >= least > 128


def test_break_even_is_never_where_the_absorbed_form_is_cheaper():
    # Heads as wide as the page: nothing is saved a query.
    assert per_head_min_queries(128, 64, 128, 128) == math.inf


def test_expanded_keys_are_whole_superblocks():
    assert per_head_expanded_keys(1, 64, 528) == 1024
    assert per_head_expanded_keys(1024, 64, 528) == 1024
    assert per_head_expanded_keys(25_088, 64, 528) == 25_600
    # A row shorter than a superblock is one superblock of its own size.
    assert per_head_expanded_keys(100, 16, 8) == 128
