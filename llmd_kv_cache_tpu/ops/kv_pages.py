"""Paged KV-cache page scatter/gather.

The paged cache is the TPU-native analogue of vLLM's block tables: one
physical pool of pages per layer, shape ``[num_pages, kv_heads, page_size,
head_dim]``, addressed through per-sequence page tables. Everything here is
shape-static and jit-safe: padded positions are routed to a reserved
garbage page (page 0) instead of branching.

Layout note (TPU-deliberate): ``page_size`` and ``head_dim`` are the two
minor dimensions, so a page of one kv head is exactly one Mosaic-tileable
``[page_size, head_dim]`` block — the Pallas kernels DMA ``cache[page, h]``
HBM→VMEM without slicing inside a tiled dimension (slicing one head out of
a ``[.., page_size, kv_heads, ..]`` layout violates the (8/16,128) tiling
and fails to lower). Verified on v5e.

These ops are also the heart of the offload data plane: ``gather_pages_flat``
assembles the contiguous slab that gets DMA'd to pinned host memory (the
role ``tensor_copier.cu`` plays in the reference — see SURVEY.md §2.2).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Physical page 0 is reserved as the write target for padded/invalid
# positions so scatters need no data-dependent control flow.
GARBAGE_PAGE = 0


class PageWrites(NamedTuple):
    """Where a step's new tokens land, a page at a time (``page_writes``)."""

    pages: jax.Array  # [runs] the physical page of each run of tokens
    src: jax.Array  # [runs, page_size] the token landing in each slot, or -1


def page_writes(
    page_size: int,
    page_table: jax.Array,  # [rows, pages_per_seq] int32 (physical page ids)
    positions: jax.Array,  # [batch, seq] int32, or ragged: [total_q]
    valid: jax.Array,  # bool, as ``positions``
    row_of: jax.Array | None = None,  # ragged: [total_q] owning row
) -> PageWrites:
    """The pages a step's new tokens touch, and which token lands in each
    slot of each: the same for every layer and for K and V, so a step
    program works it out once.

    Token ``[b, s]`` belongs to row ``b`` of ``page_table``; a ragged flat
    token to row ``row_of``. A row's valid tokens come first and sit at
    consecutive positions (a chunk after its context), which bounds the
    pages it touches. Positions past the page table are clamped (their
    writes are invalid anyway) and invalid tokens route to slot 0 of the
    garbage page; of tokens with one target the last wins.
    """
    logical_page = jnp.minimum(positions // page_size, page_table.shape[1] - 1)
    if row_of is None:
        seq = positions.shape[1]
        page = jnp.take_along_axis(page_table, logical_page, axis=1)
        # seq consecutive positions span at most this many pages; one more
        # run for the row's invalid tail.
        runs = positions.shape[0] * min(
            seq, (seq + page_size - 2) // page_size + 2)
    else:
        total_q = positions.shape[0]
        page = page_table[
            jnp.clip(row_of, 0, page_table.shape[0] - 1), logical_page]
        # A row of n tokens spans under n / page_size + 2 pages; one more
        # run for the padding behind the last row.
        runs = min(total_q,
                   total_q // page_size + 2 * page_table.shape[0] + 1)
    page = jnp.where(valid, page, GARBAGE_PAGE).reshape(-1)
    slot = jnp.where(valid, positions % page_size, 0).reshape(-1)

    # One page per run of equal pages along the flat token axis; entries
    # past the last run name no page and are dropped on the write. Two runs
    # of one page (the garbage page after every row) get the same ``src``.
    opens = jnp.concatenate([jnp.ones((1,), bool), page[1:] != page[:-1]])
    pages = jnp.full((runs,), jnp.iinfo(page.dtype).max, page.dtype).at[
        jnp.cumsum(opens) - 1].set(page, mode="drop")
    hits = ((pages[:, None, None] == page)
            & (jnp.arange(page_size)[None, :, None] == slot))
    src = jnp.max(jnp.where(hits, jnp.arange(page.shape[0]), -1), axis=-1)
    return PageWrites(pages, src)


def write_kv_pages(
    cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    writes: PageWrites,
    new_kv: jax.Array,  # [batch, seq, kv_heads, head_dim] or [total_q, ..]
    layer_idx: int | None = None,
) -> jax.Array:
    """``cache`` with the tokens of ``new_kv`` (cast to the pool's dtype)
    where ``writes`` puts them. With ``layer_idx``, ``cache`` is the whole
    ``[layers, num_pages, ...]`` stack and they land in that layer of it.
    Donate ``cache`` under jit for an in-place update.

    Written a page at a time: the touched pages are read, the tokens' rows
    set in them, and whole pages written back, so the cost is the pages
    touched. A scatter of the rows themselves has the window ``[kv_heads,
    head_dim]``, which is not minor in ``[.., kv_heads, page_size,
    head_dim]``, and the TPU compiler re-lays the whole operand around it
    (a v5e, 28 layers of 2560 pages: 30 ms a step for 8 rows); indexed by
    kv head too it stays in place but pays per 256-byte update (33 ms for
    1024 rows). A page is the pool's own unit and its window is minor:
    1.2-1.6 ms for 8-32 rows, 2.6 ms for 1024.
    """
    index = (writes.pages,) if layer_idx is None else (layer_idx, writes.pages)
    rows = new_kv.astype(cache.dtype).reshape((-1,) + new_kv.shape[-2:])
    rows = rows[jnp.maximum(writes.src, 0)]  # [runs, page_size, kvh, hd]
    pages = jnp.where((writes.src >= 0)[:, None, :, None],
                      rows.transpose(0, 2, 1, 3), cache[index])
    return cache.at[index].set(pages, mode="drop", unique_indices=False)


def scatter_kv_pages(
    cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    new_kv: jax.Array,  # [batch, seq, kv_heads, head_dim]
    page_table: jax.Array,  # [batch, pages_per_seq] int32 (physical page ids)
    positions: jax.Array,  # [batch, seq] int32 logical positions
    valid: jax.Array,  # [batch, seq] bool
    layer_idx: int | None = None,
) -> jax.Array:
    """Write new K or V vectors into their pages; returns the updated cache
    (``page_writes`` then ``write_kv_pages``, which a caller with several
    caches to write at the same places calls itself)."""
    writes = page_writes(cache.shape[-2], page_table, positions, valid)
    return write_kv_pages(cache, writes, new_kv, layer_idx)


def scatter_kv_pages_ragged(
    cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    new_kv: jax.Array,  # [total_q, kv_heads, head_dim] flat mixed batch
    page_table: jax.Array,  # [rows, pages_per_seq] int32
    row_of: jax.Array,  # [total_q] int32 owning row per flat token
    positions: jax.Array,  # [total_q] int32 logical positions
    valid: jax.Array,  # [total_q] bool
    layer_idx: int | None = None,
) -> jax.Array:
    """`scatter_kv_pages` over a ragged flat token axis: the mixed
    prefill+decode batch is one flat axis where each token knows its owning
    row (``row_of``) and logical position, and a row's tokens are adjacent."""
    writes = page_writes(cache.shape[-2], page_table, positions, valid, row_of)
    return write_kv_pages(cache, writes, new_kv, layer_idx)


def gather_kv_pages(
    cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    page_table: jax.Array,  # [batch, pages_per_seq] int32
    layer_idx: int | None = None,
) -> jax.Array:
    """Gather each sequence's pages into logical order.

    Returns ``[batch, pages_per_seq * page_size, kv_heads, head_dim]``.
    With ``layer_idx``, ``cache`` is the ``[layers, num_pages, ...]`` stack
    and the layer is one more index of the same gather.
    """
    batch, pages_per_seq = page_table.shape
    kv_heads, page_size, head_dim = cache.shape[-3:]
    # [batch, pages_per_seq, kv, page_size, hd]
    gathered = (cache[page_table] if layer_idx is None
                else cache[layer_idx, page_table])
    return gathered.transpose(0, 1, 3, 2, 4).reshape(
        batch, pages_per_seq * page_size, kv_heads, head_dim
    )


def gather_pages_flat(
    cache: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    page_ids: jax.Array,  # [n] int32 physical page ids
) -> jax.Array:
    """Gather arbitrary physical pages into one contiguous block.

    The offload store path: selected pages → a contiguous
    ``[n, kv_heads, page_size, head_dim]`` slab ready for a device→host
    transfer.
    """
    return cache[page_ids]
