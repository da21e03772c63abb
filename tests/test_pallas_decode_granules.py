"""The decode kernels' live granules (``_live_granules``): a row's copies
and folds follow its own keys, against the XLA reference. Pallas
interpreter mode on the CPU backend.

At pages of 16 a granule is 8 pages (128 keys) and the default superblock
64 pages, so these lengths sit on every edge of the guard: one page, a
page boundary, a granule boundary, a superblock boundary, and a row of
three rounds whose last is cut short.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
    pallas_paged_decode_attention,
)

GRANULE_CTX = (1, 15, 16, 127, 128, 129, 1023, 1024, 1025, 2500)
GRANULE_PS = 16


def granule_case(ctx_lens, kv_heads=2, q_heads=4, head_dim=16, seed=11,
                 width=160):
    """Rows of ``ctx_lens`` keys over distinct, shuffled pages of 16."""
    rng = np.random.default_rng(seed)
    batch = len(ctx_lens)
    pages = [-(-c // GRANULE_PS) for c in ctx_lens]
    num_pages = sum(pages) + 1
    order = 1 + rng.permutation(num_pages - 1)
    table = np.zeros((batch, width), np.int32)
    at = 0
    for b, n in enumerate(pages):
        table[b, :n] = order[at:at + n]
        at += n
    shape = (num_pages, kv_heads, GRANULE_PS, head_dim)
    k_cache = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, q_heads, head_dim)), jnp.float32)
    return (q, k_cache, v_cache, jnp.asarray(table),
            jnp.asarray(ctx_lens, jnp.int32))


# name -> (kv_heads, keyword arguments of the kernel)
GRANULE_ARMS = {
    "merged": (2, {}),
    "rows4": (2, dict(batch_rows=4)),
    "per_head": (2, dict(merge_heads=False)),
    "shared_copy": (1, dict(shared_kv=True, shared_stream="copy")),
    "shared_reuse": (1, dict(shared_kv=True, shared_stream="reuse")),
    "shared_copy_merged": (2, dict(shared_kv=True, shared_stream="copy")),
    "kpb4": (2, dict(pages_per_block=4)),  # the granule is the superblock
    "kpb12": (2, dict(pages_per_block=12)),  # no whole number of granules
    "kpb16": (2, dict(pages_per_block=16)),  # two granules a round
    "kpb16_per_head": (2, dict(pages_per_block=16, merge_heads=False)),
}


@pytest.mark.parametrize("window,sinks",
                         [(None, None), (300, None), (1500, 20)])
@pytest.mark.parametrize("arm", list(GRANULE_ARMS))
def test_live_granules_match_reference(arm, window, sinks):
    """Every edge of the live-granule guard in one batch, against the XLA
    reference: whatever the superblock, the grid, the stream or the
    window, a row attends its own keys and nothing else."""
    kv_heads, kw = GRANULE_ARMS[arm]
    q, k_cache, v_cache, table, ctx_lens = granule_case(
        GRANULE_CTX, kv_heads=kv_heads)
    if kw.get("shared_kv"):
        v_cache = k_cache
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, sliding_window=window,
        sinks=sinks, interpret=True, **kw)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens, sliding_window=window, attention_sinks=sinks)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arm", ["merged", "per_head", "rows2", "kpb16"])
def test_dead_granules_are_not_folded(arm):
    """A short row runs after long rows whose V pages hold ``inf``: the
    staging scratch past the short row's one live granule still holds
    them, and a fold of it through a mask would give 0 x inf. The short
    rows come out finite and equal to the reference."""
    kw = {"merged": {}, "per_head": dict(merge_heads=False),
          "rows2": dict(batch_rows=2),
          "kpb16": dict(pages_per_block=16)}[arm]
    ctx = (1025, 1030, 20, 129)
    q, k_cache, v_cache, table, ctx_lens = granule_case(ctx, width=72)
    long_pages = np.asarray(table)[:2].ravel()
    v_cache = v_cache.at[long_pages[long_pages > 0]].set(jnp.inf)
    out = pallas_paged_decode_attention(
        q, k_cache, v_cache, table, ctx_lens, interpret=True, **kw)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, table, (ctx_lens - 1)[:, None],
        ctx_lens)[:, 0]
    assert not np.isfinite(np.asarray(out[:2])).any()  # the test's premise
    assert np.isfinite(np.asarray(out[2:])).all()
    np.testing.assert_allclose(np.asarray(out[2:]), np.asarray(ref[2:]),
                               rtol=2e-5, atol=2e-5)
