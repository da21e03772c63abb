"""The kernels that serve ``deepseek-v3.2-exp-ep16-l5`` and the recurrence
kernels of ``gigachat3.5-ep16-l5``, compiled for a TPU v5e at the
configurations' real widths, with no chip: the TPU's compiler is
installed here and compiles for a chip that is described and not attached.
It refuses what the interpreter lets through (a slice off the tiling, more
fast memory than a kernel may use). Nothing runs: a compile that passes is
not a chip run.

The topology is described inside a fixture and never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. All of these tests stay in this one file for the same reason.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llmd_kv_cache_tpu.models import llama
from llmd_kv_cache_tpu.ops import sparse_index
from llmd_kv_cache_tpu.ops.pallas_latent_prefill import (
    pallas_per_head_prefill_attention)
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
    pallas_paged_decode_attention, pallas_paged_prefill_attention)

# The configuration's engines: 5 layers, 2500 pages of 64 tokens a pool,
# 528 pages a row at most, 8 decoding rows, chunks of 512.
LAYERS, PAGES, PAGE, ROWS, ROW_PAGES, CHUNK = 5, 2500, 64, 8, 528, 512
KEYS = ROW_PAGES * PAGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shaped(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def compiles(fn, *args) -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,q_seq", [(ROWS, 1), (1, CHUNK)],
                         ids=["decode", "prefill"])
def test_index_scores(shaped, rows, q_seq):
    compiles(sparse_index.dsa_index_scores,
             shaped((rows, q_seq, 64, 128)),
             shaped((rows, q_seq, 64), jnp.float32),
             shaped((rows, KEYS, 128)), shaped((rows,), jnp.int32))


def test_index_scores_paged(shaped):
    """A decode step's scores from the pool itself: 528 page ids a row as
    scalars, a round of 16 pages of index keys twice in fast memory, a
    row's 33,792 scores as one block."""
    compiles(sparse_index.dsa_index_scores_paged,
             shaped((ROWS, 1, 64, 128)), shaped((ROWS, 1, 64), jnp.float32),
             shaped((LAYERS, PAGES, 1, PAGE, 128)), shaped((), jnp.int32),
             shaped((ROWS, ROW_PAGES), jnp.int32), shaped((ROWS,), jnp.int32))


def test_keep_bias(shaped):
    """A chunk's thresholds: 16 queries x 33 blocks of 1024 scores a
    program in fast memory, twice (as they land, as they are counted)."""
    compiles(functools.partial(sparse_index.dsa_keep_bias, topk=2048),
             shaped((1, CHUNK, KEYS), jnp.float32),
             shaped((1, CHUNK), jnp.int32), shaped((1,), jnp.int32))


def test_select_by_count(shaped):
    """A decode step's selection: a row's 33 blocks of 1024 scores in
    fast memory as they land and ordered, the prefix sums of what is kept
    beside them, the triangle over its 384 chunks, 16 tiles of slots."""
    compiles(functools.partial(sparse_index.topk_by_count, topk=2048),
             shaped((ROWS, KEYS), jnp.float32), shaped((ROWS,), jnp.int32))


def test_gather_by_product(shaped):
    """A decode step's gather of the chosen latents: a round of 16 pages
    of a row twice in fast memory, the row's 2048 slots as they fill, every
    slot's position along a line, 528 page ids a row as scalars."""
    compiles(sparse_index.gather_by_product,
             shaped((LAYERS, PAGES, 1, PAGE, 640)), shaped((), jnp.int32),
             shaped((ROWS, ROW_PAGES), jnp.int32),
             shaped((ROWS, 2048), jnp.int32), shaped((ROWS,), jnp.int32))


def test_masked_latent_prefill(shaped):
    """128 query heads on one 640-lane latent head, 16 query rows a
    program, a selection bias over every key of the row."""
    def prefill(q, k, v, table, ctx, total, bias):
        return pallas_paged_prefill_attention(
            q, k, v, table, ctx, total, q_tile=16, shared_kv=True,
            layer_idx=2, bias=bias)

    compiles(prefill, shaped((1, CHUNK, 128, 640)),
             shaped((LAYERS, PAGES, 1, PAGE, 640)),
             shaped((LAYERS, PAGES, 1, PAGE, 640)),
             shaped((1, ROW_PAGES), jnp.int32), shaped((1,), jnp.int32),
             shaped((1,), jnp.int32),
             shaped((1, CHUNK, KEYS), jnp.float32))


@pytest.mark.parametrize("heads,q_seq,selects", [
    (128, CHUNK, True), (128, 256, True), (64, CHUNK, False),
    (64, 256, False)], ids=["selecting-512", "selecting-256", "gated-512",
                            "gated-256"])
def test_per_head_latent_prefill(shaped, heads, q_seq, selects):
    """A chunk's queries whole and 16 heads a program: their blocks, a
    superblock of 1024 latents twice, its slice of the selection twice and
    the heads' softmax state in fast memory, at both latent
    configurations' head counts and the engine's two buckets that go per
    head."""
    def prefill(q_nope, q_rope, w_uk, w_uv, pages, table, ctx, total,
                *bias):
        return pallas_per_head_prefill_attention(
            q_nope, q_rope, w_uk, w_uv, pages, table, ctx, total,
            scale=192 ** -0.5, layer_idx=2, bias=bias[0] if bias else None)

    compiles(prefill, shaped((1, q_seq, heads, 128)),
             shaped((1, q_seq, heads, 64)), shaped((heads, 512, 128)),
             shaped((heads, 512, 128)),
             shaped((LAYERS, PAGES, 1, PAGE, 640)),
             shaped((1, ROW_PAGES), jnp.int32), shaped((1,), jnp.int32),
             shaped((1,), jnp.int32),
             *([shaped((1, q_seq, KEYS), jnp.float32)] if selects else []))


@pytest.mark.parametrize("gathered", [False, True],
                         ids=["the-pool", "the-chosen"])
def test_latent_decode(shaped, gathered):
    """The decode kernel over the latent pool (rows that attend every key)
    and over the pool of a step's chosen latents (2048 a row)."""
    pool = ((ROWS * 2048 // PAGE, 1, PAGE, 640) if gathered
            else (LAYERS, PAGES, 1, PAGE, 640))
    pages = 2048 // PAGE if gathered else ROW_PAGES
    compiles(functools.partial(pallas_paged_decode_attention, shared_kv=True,
                               layer_idx=None if gathered else 2),
             shaped((ROWS, 128, 640)), shaped(pool), shaped(pool),
             shaped((ROWS, pages), jnp.int32), shaped((ROWS,), jnp.int32))


@pytest.mark.parametrize("first_key", [0, 1], ids=["main", "module"])
def test_latent_verify_decode(shaped, first_key):
    """The decode kernel verifying a draft (``openpangu-ultra-ep32-l5``): a
    row's two positions fold in beside the 128 heads, 256 query rows over
    the one latent head, in a pool of six layers; the prediction module's
    layer attends from slot 1 on."""
    pool = (LAYERS + 1, 3000, 1, PAGE, 640)
    compiles(functools.partial(pallas_paged_decode_attention, shared_kv=True,
                               layer_idx=2, first_key=first_key),
             shaped((ROWS, 2, 128, 640)), shaped(pool), shaped(pool),
             shaped((ROWS, ROW_PAGES), jnp.int32), shaped((ROWS,), jnp.int32))


def test_draft_acceptance(shaped):
    """The acceptance kernel between the main model's part of a
    speculative step and the module's: a few scalars in SMEM."""
    from llmd_kv_cache_tpu.ops.draft_accept import mtp_accept

    compiles(mtp_accept, shaped((2, ROWS), jnp.int32),
             shaped((ROWS,), jnp.int32), shaped((ROWS,), jnp.int32))


@pytest.mark.parametrize("m,k,n,held", [
    pytest.param(4096, 7168, 2048, 16, id="up-of-a-chunk"),
    pytest.param(128, 2048, 7168, 16, id="down-of-a-step"),
    pytest.param(256, 4096, 768, 36, id="granite-up-of-a-step"),
    pytest.param(5120, 4096, 768, 36, id="granite-up-of-a-chunk"),
    pytest.param(256, 768, 4096, 36, id="granite-down-of-a-step"),
    pytest.param(4096, 4096, 1280, 20, id="solar-up-of-a-chunk"),
    pytest.param(128, 1280, 4096, 20, id="solar-down-of-a-step"),
    pytest.param(128, 2048, 7680, 8, id="openpangu-down-of-a-step"),
    pytest.param(4096, 2304, 896, 16, id="mellum-up-of-a-chunk"),
    pytest.param(128, 896, 2304, 16, id="mellum-down-of-a-step"),
])
def test_grouped_matmul(shaped, m, k, n, held):
    """The tiles ``llama.gmm_tiling`` gives each configuration's experts:
    one that Mosaic refuses (lanes, scoped VMEM) fails here, not on the
    chip."""
    compiles(lambda x, w, sizes: llama._grouped_matmul(
        x, w, sizes, {"interpret": False}),
        shaped((m, k)), shaped((held, k, n)), shaped((held,), jnp.int32))


# gigachat3.5-ep16-l5: 32 key heads serving 64 value heads of 128 x 128, 4
# linear layers, 64 state slots and the spare one, blocks of a page.
GDN_LAYERS, GDN_SLOTS, GDN_KEY_HEADS, GDN_VALUE_HEADS, GDN_DIM = (
    4, 65, 32, 64, 128)


def test_delta_rule_scan(shaped):
    """A chunk of 512 tokens in blocks of 64: the state carried in fast
    memory from block to block, the in-block inverse as matrix products."""
    from llmd_kv_cache_tpu.ops.gated_deltanet import gdn_scan

    f32 = jnp.float32
    compiles(functools.partial(gdn_scan, block=PAGE, kernel=True),
             shaped((CHUNK, GDN_KEY_HEADS, GDN_DIM), f32),
             shaped((CHUNK, GDN_KEY_HEADS, GDN_DIM), f32),
             shaped((CHUNK, GDN_VALUE_HEADS, GDN_DIM)),
             shaped((CHUNK, GDN_VALUE_HEADS), f32),
             shaped((CHUNK, GDN_VALUE_HEADS), f32),
             shaped((GDN_VALUE_HEADS, GDN_DIM, GDN_DIM), f32),
             shaped((), jnp.int32))


def test_recurrence_step(shaped):
    """8 rows' states updated in place in the pool, under their slots."""
    from llmd_kv_cache_tpu.ops.gated_deltanet import gdn_step

    f32 = jnp.float32
    compiles(functools.partial(gdn_step, kernel=True),
             shaped((GDN_LAYERS, GDN_SLOTS, GDN_VALUE_HEADS, GDN_DIM,
                     GDN_DIM), f32),
             shaped((), jnp.int32), shaped((ROWS,), jnp.int32),
             shaped((ROWS, GDN_KEY_HEADS, GDN_DIM), f32),
             shaped((ROWS, GDN_KEY_HEADS, GDN_DIM), f32),
             shaped((ROWS, GDN_VALUE_HEADS, GDN_DIM)),
             shaped((ROWS, GDN_VALUE_HEADS), f32),
             shaped((ROWS, GDN_VALUE_HEADS), f32))


KDA_LAYERS, KDA_SLOTS, KDA_HEADS = 6, 41, 64


def test_channelwise_delta_rule_scan(shaped):
    """``solar-open2-ep16-l8``: a chunk of 512 tokens in blocks of 64, 64
    heads with a decay for every key channel: the pairwise decays over
    three levels of blocking, as matrix products under masks."""
    from llmd_kv_cache_tpu.ops.gated_deltanet import kda_scan

    f32 = jnp.float32
    compiles(functools.partial(kda_scan, block=PAGE, kernel=True),
             shaped((CHUNK, KDA_HEADS, GDN_DIM), f32),
             shaped((CHUNK, KDA_HEADS, GDN_DIM), f32),
             shaped((CHUNK, KDA_HEADS, GDN_DIM)),
             shaped((CHUNK, KDA_HEADS, GDN_DIM), f32),
             shaped((CHUNK, KDA_HEADS), f32),
             shaped((KDA_HEADS, GDN_DIM, GDN_DIM), f32),
             shaped((), jnp.int32))


def test_channelwise_recurrence_step(shaped):
    """8 rows' states decayed a key channel and updated in place."""
    from llmd_kv_cache_tpu.ops.gated_deltanet import kda_step

    f32 = jnp.float32
    compiles(functools.partial(kda_step, kernel=True),
             shaped((KDA_LAYERS, KDA_SLOTS, KDA_HEADS, GDN_DIM, GDN_DIM),
                    f32),
             shaped((), jnp.int32), shaped((ROWS,), jnp.int32),
             shaped((ROWS, KDA_HEADS, GDN_DIM), f32),
             shaped((ROWS, KDA_HEADS, GDN_DIM), f32),
             shaped((ROWS, KDA_HEADS, GDN_DIM)),
             shaped((ROWS, KDA_HEADS, GDN_DIM), f32),
             shaped((ROWS, KDA_HEADS), f32))


# ``granite-4.0-h-small-ep2-l10``: 9 Mamba-2 layers, 41 slots, 128 heads of
# 64 channels over a state of 128 (two heads a tile of 128 lanes), 16 rows.
M2_LAYERS, M2_SLOTS, M2_HEADS, M2_HEAD, M2_STATE, M2_ROWS = (
    9, 41, 128, 64, 128, 16)


def test_mamba2_scan(shaped):
    """A chunk of 512 tokens in blocks of 64: a tile's two heads' pairs
    under their decay masks and the carried state, as matrix products."""
    from llmd_kv_cache_tpu.ops.mamba2 import mamba2_scan, state_shape

    f32 = jnp.float32
    compiles(functools.partial(mamba2_scan, block=PAGE, kernel=True),
             shaped((CHUNK, M2_HEADS, M2_HEAD)),
             shaped((CHUNK, M2_STATE)), shaped((CHUNK, M2_STATE)),
             shaped((CHUNK, M2_HEADS), f32), shaped((M2_HEADS,), f32),
             shaped((M2_HEADS,), f32),
             shaped(state_shape(M2_HEADS, M2_HEAD, M2_STATE), f32),
             shaped((), jnp.int32))


def test_mamba2_step(shaped):
    """16 rows' states decayed a head and written to in place."""
    from llmd_kv_cache_tpu.ops.mamba2 import mamba2_step, state_shape

    f32 = jnp.float32
    compiles(functools.partial(mamba2_step, kernel=True),
             shaped((M2_LAYERS, M2_SLOTS,
                     *state_shape(M2_HEADS, M2_HEAD, M2_STATE)), f32),
             shaped((), jnp.int32), shaped((M2_ROWS,), jnp.int32),
             shaped((M2_ROWS, M2_HEADS, M2_HEAD)),
             shaped((M2_ROWS, M2_STATE)), shaped((M2_ROWS, M2_STATE)),
             shaped((M2_ROWS, M2_HEADS), f32), shaped((M2_HEADS,), f32),
             shaped((M2_HEADS,), f32))


# The recurrence of ``falcon-h1-34b-l9``'s mixers: 32 heads of 128 channels
# (one head a tile) over a state of 256, two groups of B and C, 12 rows.
FH_LAYERS, FH_SLOTS, FH_HEADS, FH_HEAD, FH_STATE, FH_GROUPS, FH_ROWS = (
    9, 38, 32, 128, 256, 2, 12)


def test_mamba2_scan_of_two_groups_at_one_head_a_tile(shaped):
    """A chunk of 512 tokens in blocks of 64 over tiles ``[256, 128]``, a
    tile reading its group's B, C and ``C B^T``."""
    from llmd_kv_cache_tpu.ops.mamba2 import mamba2_scan, state_shape

    f32 = jnp.float32
    assert state_shape(FH_HEADS, FH_HEAD, FH_STATE) == (32, 256, 128)
    compiles(functools.partial(mamba2_scan, block=PAGE, kernel=True),
             shaped((CHUNK, FH_HEADS, FH_HEAD)),
             shaped((CHUNK, FH_GROUPS, FH_STATE)),
             shaped((CHUNK, FH_GROUPS, FH_STATE)),
             shaped((CHUNK, FH_HEADS), f32), shaped((FH_HEADS,), f32),
             shaped((FH_HEADS,), f32),
             shaped(state_shape(FH_HEADS, FH_HEAD, FH_STATE), f32),
             shaped((), jnp.int32))


def test_mamba2_step_of_two_groups_at_one_head_a_tile(shaped):
    """12 rows' states, a group's 16 tiles a grid step (2 MiB in, 2 out)."""
    from llmd_kv_cache_tpu.ops.mamba2 import mamba2_step, state_shape

    f32 = jnp.float32
    compiles(functools.partial(mamba2_step, kernel=True),
             shaped((FH_LAYERS, FH_SLOTS,
                     *state_shape(FH_HEADS, FH_HEAD, FH_STATE)), f32),
             shaped((), jnp.int32), shaped((FH_ROWS,), jnp.int32),
             shaped((FH_ROWS, FH_HEADS, FH_HEAD)),
             shaped((FH_ROWS, FH_GROUPS, FH_STATE)),
             shaped((FH_ROWS, FH_GROUPS, FH_STATE)),
             shaped((FH_ROWS, FH_HEADS), f32), shaped((FH_HEADS,), f32),
             shaped((FH_HEADS,), f32))


# ``mellum2-12b-ep4``: 32 query and 4 key/value heads of 128; the window
# pool's 21 layers of 576 pages and the global pool's 7 of 2048, 520 pages
# a row, 16 decoding rows, a window of 1024.
@pytest.mark.parametrize("layers,pages,window", [(21, 576, 1024),
                                                 (7, 2048, None)],
                         ids=["window-pool", "global-pool"])
@pytest.mark.parametrize("q_seq", [1, CHUNK], ids=["decode", "prefill"])
def test_two_pool_gqa_attention(shaped, layers, pages, window, q_seq):
    """The paged kernels as ``llama.forward_decode_pallas_pools`` and
    ``forward_prefill_pallas_pools`` call them, a pool at a time."""
    pool = shaped((layers, pages, 4, PAGE, 128))
    layer = shaped((), jnp.int32)

    def decode(q, k, v, table, lens, li):
        return pallas_paged_decode_attention(
            q, k, v, table, lens, sliding_window=window, layer_idx=li)

    def prefill(q, k, v, table, ctx, total, li):
        return pallas_paged_prefill_attention(
            q, k, v, table, ctx, total,
            q_tile=llama._prefill_q_tile(llama.LlamaConfig(
                num_heads=32, num_kv_heads=4, head_dim=128), q_seq),
            sliding_window=window, layer_idx=li)

    if q_seq == 1:
        compiles(decode, shaped((16, 32, 128)), pool, pool,
                 shaped((16, 520), jnp.int32), shaped((16,), jnp.int32),
                 layer)
    else:
        compiles(prefill, shaped((1, q_seq, 32, 128)), pool, pool,
                 shaped((1, 520), jnp.int32), shaped((1,), jnp.int32),
                 shaped((1,), jnp.int32), layer)
