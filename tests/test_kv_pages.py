"""Writing a step's K/V rows into the ``[layers, pages, ...]`` stack in
place against the form it replaces, kept here as the oracle: take the
layer out, scatter the rows into the copy, put the layer back."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import step_programs as sp

from llmd_kv_cache_tpu.models import llama
from llmd_kv_cache_tpu.ops.kv_pages import (
    GARBAGE_PAGE,
    page_writes,
    scatter_kv_pages,
    scatter_kv_pages_ragged,
    write_kv_pages,
)

LAYERS, PAGES, KVH, PAGE, HD = 3, 9, 2, 4, 8
FP8 = jnp.float8_e4m3fn


def rows_oracle(layer, new_kv, page_table, positions, valid, row_of=None):
    """One layer with each token's row scattered straight to ``(page, :,
    slot, :)``: the write as it was before pages were the unit."""
    page_size = layer.shape[-2]
    logical = jnp.minimum(positions // page_size, page_table.shape[1] - 1)
    if row_of is None:
        page = jnp.take_along_axis(page_table, logical, axis=1)
    else:
        page = page_table[jnp.clip(row_of, 0, page_table.shape[0] - 1),
                          logical]
    page = jnp.where(valid, page, GARBAGE_PAGE).reshape(-1)
    slot = jnp.where(valid, positions % page_size, 0).reshape(-1)
    vals = new_kv.astype(layer.dtype).reshape((-1,) + new_kv.shape[-2:])
    return layer.at[page, :, slot, :].set(vals, mode="drop")


def stack_oracle(stack, layer_idx, *args, **kw):
    return stack.at[layer_idx].set(rows_oracle(stack[layer_idx], *args, **kw))


def filled(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def same(a, b):
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))


def padded_case():
    """Three rows of a [3, 6] chunk: unaligned over three pages; a short
    row with an invalid tail; a row that is all padding."""
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 0]], jnp.int32)
    ctx = jnp.asarray([3, 4, 0], jnp.int32)
    new = jnp.asarray([6, 2, 0], jnp.int32)
    positions = ctx[:, None] + jnp.arange(6)[None, :]
    valid = jnp.arange(6)[None, :] < new[:, None]
    return table, positions, valid


def ragged_case():
    """A flat axis of 12: a chunk of 5, two decode rows, an empty row, and
    padding behind the last row."""
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 0], [0, 0, 0]],
                        jnp.int32)
    row_starts = jnp.asarray([0, 5, 6, 7, 7], jnp.int32)
    ctx = jnp.asarray([2, 7, 4, 0], jnp.int32)
    flat = jnp.arange(12)
    row_of = jnp.clip(jnp.searchsorted(row_starts, flat, side="right") - 1,
                      0, 3)
    positions = ctx[row_of] + flat - row_starts[row_of]
    valid = flat < row_starts[-1]
    return table, row_of, positions, valid


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
class TestStackFormEqualsPerLayerForm:
    def test_padded(self, dtype):
        table, positions, valid = padded_case()
        stack = filled((LAYERS, PAGES, KVH, PAGE, HD), dtype)
        want = got = per_layer = stack
        writes = page_writes(PAGE, table, positions, valid)
        for lj in range(LAYERS):
            new_kv = filled((3, 6, KVH, HD), jnp.bfloat16, seed=lj + 1)
            want = stack_oracle(want, lj, new_kv, table, positions, valid)
            got = write_kv_pages(got, writes, new_kv, layer_idx=lj)
            per_layer = per_layer.at[lj].set(scatter_kv_pages(
                stack[lj], new_kv, table, positions, valid))
            same(scatter_kv_pages(stack, new_kv, table, positions, valid,
                                  layer_idx=lj),
                 stack_oracle(stack, lj, new_kv, table, positions, valid))
        same(got, want)
        same(per_layer, want)
        assert got.dtype == dtype

    def test_ragged(self, dtype):
        table, row_of, positions, valid = ragged_case()
        stack = filled((LAYERS, PAGES, KVH, PAGE, HD), dtype)
        want = got = per_layer = stack
        writes = page_writes(PAGE, table, positions, valid, row_of)
        for lj in range(LAYERS):
            new_kv = filled((12, KVH, HD), jnp.bfloat16, seed=lj + 1)
            want = stack_oracle(want, lj, new_kv, table, positions, valid,
                                row_of=row_of)
            got = write_kv_pages(got, writes, new_kv, layer_idx=lj)
            per_layer = per_layer.at[lj].set(scatter_kv_pages_ragged(
                stack[lj], new_kv, table, row_of, positions, valid))
            same(scatter_kv_pages_ragged(stack, new_kv, table, row_of,
                                         positions, valid, layer_idx=lj),
                 stack_oracle(stack, lj, new_kv, table, positions, valid,
                              row_of=row_of))
        same(got, want)
        same(per_layer, want)


class TestWhereRowsLand:
    def test_invalid_rows_land_in_the_garbage_page_only(self):
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        positions = jnp.asarray([[0, 1, 2], [5, 6, 7]], jnp.int32)
        valid = jnp.zeros((2, 3), bool)
        stack = filled((LAYERS, PAGES, KVH, PAGE, HD), jnp.bfloat16)
        new_kv = filled((2, 3, KVH, HD), jnp.bfloat16, seed=1)
        got = scatter_kv_pages(stack, new_kv, table, positions, valid,
                               layer_idx=1)
        same(got, stack_oracle(stack, 1, new_kv, table, positions, valid))
        changed = np.argwhere(np.asarray(got != stack))
        # Layer 1, page 0, slot 0: the last invalid token's row.
        assert {tuple(c[[0, 1, 3]]) for c in changed} == {(1, GARBAGE_PAGE, 0)}
        same(got[1, GARBAGE_PAGE, :, 0], new_kv[1, 2])

    def test_position_past_the_table_is_clamped_and_page_past_the_pool_dropped(
            self):
        # Row 0's padding sits past its one-page table; row 1's table names
        # a page the pool does not have.
        table = jnp.asarray([[2], [PAGES + 3]], jnp.int32)
        positions = jnp.asarray([[2, 3, 4, 5], [0, 1, 2, 3]], jnp.int32)
        valid = jnp.asarray([[True, True, False, False], [True] * 4])
        stack = filled((LAYERS, PAGES, KVH, PAGE, HD), jnp.bfloat16)
        new_kv = filled((2, 4, KVH, HD), jnp.bfloat16, seed=1)
        got = scatter_kv_pages(stack, new_kv, table, positions, valid,
                               layer_idx=2)
        same(got, stack_oracle(stack, 2, new_kv, table, positions, valid))
        changed = np.argwhere(np.asarray(got != stack))
        assert {tuple(c[[0, 1, 3]]) for c in changed} == {
            (2, 2, 2), (2, 2, 3), (2, GARBAGE_PAGE, 0)}

    def test_of_duplicate_targets_the_last_wins(self):
        # Both rows own page 5 and write its slots 1 and 2.
        table = jnp.asarray([[5], [5]], jnp.int32)
        positions = jnp.asarray([[1, 2], [1, 2]], jnp.int32)
        valid = jnp.ones((2, 2), bool)
        stack = filled((LAYERS, PAGES, KVH, PAGE, HD), jnp.bfloat16)
        new_kv = filled((2, 2, KVH, HD), jnp.bfloat16, seed=1)
        got = scatter_kv_pages(stack, new_kv, table, positions, valid,
                               layer_idx=0)
        same(got, stack_oracle(stack, 0, new_kv, table, positions, valid))
        same(got[0, 5, :, 1], new_kv[1, 0])
        same(got[0, 5, :, 2], new_kv[1, 1])

    def test_two_groups_with_different_page_counts(self):
        tables = (jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
                  jnp.asarray([[3, 1, 2], [4, 0, 0]], jnp.int32))
        positions = jnp.asarray([[3, 4, 5, 6], [0, 1, 2, 3]], jnp.int32)
        valid = jnp.asarray([[True] * 4, [True, True, True, False]])
        for g, (layers, pages) in enumerate(((2, PAGES), (3, 5))):
            stack = filled((layers, pages, KVH, PAGE, HD), jnp.bfloat16, g)
            writes = page_writes(PAGE, tables[g], positions, valid)
            want = got = stack
            for lj in range(layers):
                new_kv = filled((2, 4, KVH, HD), jnp.bfloat16, seed=10 + lj)
                got = write_kv_pages(got, writes, new_kv, layer_idx=lj)
                want = stack_oracle(want, lj, new_kv, tables[g], positions,
                                    valid)
            same(got, want)


def _oracle_page_writes(page_size, page_table, positions, valid, row_of=None):
    """In place of a plan, what ``rows_oracle`` needs to write by itself."""
    return (page_table, positions, valid, row_of)


def _oracle_write_kv_pages(cache, writes, new_kv, layer_idx=None):
    """The old ``write_layer``: a layer out, rows in, the layer back."""
    page_table, positions, valid, row_of = writes
    return stack_oracle(cache, layer_idx, new_kv, page_table, positions,
                        valid, row_of=row_of)


@pytest.mark.parametrize("name", list(sp.PROGRAMS))
def test_program_equals_take_scatter_put_back(name):
    """Two steps of each program: logits and every
    pool equal to the same program writing by the old form. MLA's width-0
    V stack comes back as it went in."""
    prog = sp.PROGRAMS[name]
    params = llama.init_params(jax.random.PRNGKey(0), prog.cfg)
    static = dict(prog.static, interpret=True) if prog.pallas else prog.static

    def two_steps(fn):
        pools, outs = sp.init_pools(prog.cfg), []
        for step in range(2):
            out, *pools = fn(*prog.args(params, prog.cfg, pools, step),
                             **static)
            outs.append(out)
        return outs, pools

    got_outs, got_pools = two_steps(prog.fn)
    # A fresh function under a fresh jit: the patched trace must not be
    # served from (or left in) the program's own cache.
    with mock.patch.object(llama, "page_writes", _oracle_page_writes), \
            mock.patch.object(llama, "write_kv_pages", _oracle_write_kv_pages):
        want_outs, want_pools = two_steps(jax.jit(
            lambda *a, **kw: prog.fn.__wrapped__(*a, **kw),
            static_argnums=(1,), static_argnames=tuple(static)))
    for got, want in zip(got_outs + got_pools, want_outs + want_pools):
        assert got.shape == want.shape and got.dtype == want.dtype
        same(got, want)
    start = sp.init_pools(prog.cfg)
    assert any(bool((p != q).any()) for p, q in zip(start, got_pools))
    if prog.cfg.is_mla:
        assert got_pools[1].shape[-1] == 0
