"""The routed experts' grouped matmul: how ``llama.gmm_tiling`` cuts an
expert's matrix for megablox ``gmm`` (a pure function of the shapes, held
here at the five routed configurations' published widths), and the
interpreted kernel under that rule against ``_experts_dense`` at toy widths
that are no powers of two.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.models import llama

MIB = 1 << 20
# configuration: (hidden, expert width, rows of a decode step, of a chunk
# of 512): max_batch x positions x top-k padded to the rows' tile, 512 x top-k.
WIDTHS = {
    "deepseek-v3.2-exp-ep16-l5": (7168, 2048, 128, 4096),
    "gigachat3.5-ep16-l5": (7168, 2048, 128, 4096),
    "solar-open2-ep16-l8": (4096, 1280, 128, 4096),
    "openpangu-ultra-ep32-l5": (7680, 2048, 128, 4096),
    "granite-4.0-h-small-ep2-l10": (4096, 768, 256, 5120),
    "mellum2-12b-ep4": (2304, 896, 128, 4096),
}
CASES = [pytest.param(m, k, n, id=f"{name}-{matrix}-{what}")
         for name, (hidden, width, step, chunk) in WIDTHS.items()
         for matrix, k, n in (("up", hidden, width), ("down", width, hidden))
         for what, m in (("step", step), ("chunk", chunk))]


def old_rule(k, n):
    return 128, math.gcd(k, 512), math.gcd(n, 1024)


def grid_steps(k, n, tiling):
    return -(-k // tiling[1]) * -(-n // tiling[2])


class TestTiling:
    @pytest.mark.parametrize("m,k,n", CASES)
    def test_tiles_fit_the_matrix_the_lanes_and_the_budget(self, m, k, n):
        tm, tk, tn = llama.gmm_tiling(m, k, n, 2)
        assert tm % 16 == 0 and m % tm == 0  # bfloat16 rows: 16 sublanes
        assert tk % 128 == 0 and k % tk == 0
        assert tn % 128 == 0 and n % tn == 0
        assert llama._GMM_VMEM_BYTES <= 12 * MIB  # of Mosaic's 16 scoped
        assert llama.gmm_vmem_bytes(tm, tk, tn, 2) <= llama._GMM_VMEM_BYTES
        assert k * n * 2 >= MIB and tk * tn * 2 >= MIB
        assert grid_steps(k, n, (tm, tk, tn)) <= grid_steps(
            k, n, old_rule(k, n))

    @pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096), (4096, 1280),
                                     (1280, 4096), (2048, 7680)])
    def test_fewer_grid_steps_where_the_old_rule_cut_small(self, k, n):
        """granite's and Solar's widths (and openPangu's down matrix) went
        out in pieces of 256-512 KB: 12-60 grid steps a matrix."""
        new = grid_steps(k, n, llama.gmm_tiling(128, k, n, 2))
        assert new <= 10 and new * 2 <= grid_steps(k, n, old_rule(k, n))

    @pytest.mark.parametrize("k,n,tiling", [
        (768, 4096, (128, 768, 2048)), (1280, 4096, (128, 1280, 1024)),
        (4096, 768, (128, 4096, 384)), (4096, 1280, (128, 4096, 256)),
        (2048, 7168, (128, 2048, 512)), (2048, 7680, (128, 2048, 768))])
    def test_k_goes_whole_where_a_piece_twice_the_rows_tile_fits(
            self, k, n, tiling):
        """What the sweep on the chip chose (PERF.md §6, PR 58): one pass
        over ``k`` a piece, so no accumulator pass."""
        assert llama.gmm_tiling(256, k, n, 2) == tiling

    @pytest.mark.parametrize("k,n,tiling", [
        (7168, 2048, (128, 512, 2048)), (7680, 2048, (128, 768, 2048))])
    def test_else_n_goes_whole(self, k, n, tiling):
        """``k`` whole would leave 128 lanes of ``n`` a piece: the rows'
        tile read again for every one of 16 tiles."""
        assert llama.gmm_tiling(4096, k, n, 2) == tiling

    @pytest.mark.parametrize("k,n", [(64, 32), (200, 384), (256, 100)])
    def test_an_axis_that_is_no_multiple_of_the_lanes_goes_whole(self, k, n):
        _, tk, tn = llama.gmm_tiling(128, k, n, 4)
        assert (k % 128 == 0 or tk == k) and (n % 128 == 0 or tn == n)
        assert k % tk == 0 and n % tn == 0

    @pytest.mark.parametrize("itemsize", [1, 2, 4])
    def test_the_item_size_counts(self, itemsize):
        tm, tk, tn = llama.gmm_tiling(128, 7168, 2048, itemsize)
        assert tk * tn * itemsize <= llama._GMM_PIECE_BYTES
        assert llama.gmm_vmem_bytes(tm, tk, tn, itemsize) <= (
            llama._GMM_VMEM_BYTES)

    def test_a_matrix_too_large_for_one_piece_is_cut(self):
        _, tk, tn = llama.gmm_tiling(128, 1024, 896, 4)
        assert (tk, tn) != (1024, 896) and 1024 % tk == 0 and 896 % tn == 0


def layer_of(key, held, hidden, width):
    k_gate, k_up, k_down = jax.random.split(key, 3)
    return {"w_gate": 0.1 * jax.random.normal(k_gate, (held, hidden, width)),
            "w_up": 0.1 * jax.random.normal(k_up, (held, hidden, width)),
            "w_down": 0.1 * jax.random.normal(k_down, (held, width, hidden))}


def assignments(sizes, top_k, elsewhere):
    """``idx [T, top_k]`` whose assignments fall to expert ``e`` ``sizes[e]``
    times; what is left of ``T x top_k`` goes to ``elsewhere`` (not held)."""
    flat = np.repeat(np.arange(len(sizes)), sizes)
    total = -(-len(flat) // top_k) * top_k
    flat = np.concatenate([flat, np.full(total - len(flat), elsewhere)])
    return jnp.asarray(np.random.default_rng(3).permutation(flat).reshape(
        -1, top_k), jnp.int32)


KERNEL = {"interpret": True}


class TestInterpretedKernelAgainstTheDenseForm:
    @pytest.mark.parametrize("hidden,width", [(256, 384), (384, 256)])
    @pytest.mark.parametrize("sizes", [
        pytest.param((100, 0, 60, 32), id="straddles-a-tile-and-one-empty"),
        pytest.param((0, 0, 0, 130), id="one-group-over-two-tiles"),
        pytest.param((1, 1, 1, 1), id="a-row-each"),
        pytest.param((0, 0, 0, 0), id="no-expert-held"),
    ])
    def test_experts_grouped(self, hidden, width, sizes):
        """192 or fewer assignments in 256 rows: what is past the groups,
        and what falls to an expert that is not held, adds nothing."""
        first, top_k = 2, 2
        lyr = layer_of(jax.random.PRNGKey(1), len(sizes), hidden, width)
        idx = assignments(np.asarray(sizes), top_k, elsewhere=first + 9)
        idx = jnp.where(idx < len(sizes), idx + first, idx)
        idx = jnp.concatenate(
            [idx, jnp.full((96 - idx.shape[0], top_k), 0, jnp.int32)])
        x = jax.random.normal(jax.random.PRNGKey(2), (96, hidden))
        w = jax.random.uniform(jax.random.PRNGKey(4), idx.shape)
        counters = {}
        got = llama._experts_grouped(x, lyr, idx, w, first, None, KERNEL,
                                     counters)
        want = llama._experts_dense(x, lyr, idx, w, first)
        assert int(counters["assignments_held"]) == sum(sizes)
        assert int(counters["experts_touched"]) == sum(s > 0 for s in sizes)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        if not sum(sizes):
            assert not np.asarray(got).any()

    def test_padded_tokens_are_left_out(self):
        lyr = layer_of(jax.random.PRNGKey(5), 3, 256, 384)
        idx = assignments(np.asarray((40, 50, 38)), 2, elsewhere=7)
        x = jax.random.normal(jax.random.PRNGKey(6), (idx.shape[0], 256))
        w = jnp.ones(idx.shape)
        valid = jnp.arange(idx.shape[0]) < 20
        got = llama._experts_grouped(x, lyr, idx, w, 0, valid, KERNEL, None)
        want = llama._experts_dense(x, lyr, idx, w, 0)
        np.testing.assert_allclose(got[:20], want[:20], rtol=2e-4, atol=2e-4)
        assert not np.asarray(got[20:]).any()

    @pytest.mark.parametrize("k,n", [(1024, 896), (896, 1024)])
    def test_a_matrix_cut_in_pieces_is_ragged_dot(self, k, n):
        """float32 at these widths is over one piece: the pieces' partial
        sums add up to the whole product."""
        tiling = llama.gmm_tiling(128, k, n, 4)
        assert (k // tiling[1]) * (n // tiling[2]) > 1
        lhs = jax.random.normal(jax.random.PRNGKey(7), (128, k))
        rhs = 0.05 * jax.random.normal(jax.random.PRNGKey(8), (3, k, n))
        sizes = jnp.asarray([50, 0, 41], jnp.int32)
        got = llama._grouped_matmul(lhs, rhs, sizes, KERNEL)
        want = llama._grouped_matmul(lhs, rhs, sizes, None)
        np.testing.assert_allclose(got[:91], want[:91], rtol=2e-4, atol=2e-4)
