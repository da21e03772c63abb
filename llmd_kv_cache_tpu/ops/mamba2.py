"""Mamba-2: the recurrence of a state-space layer whose cache is one state a
sequence, not a page a block of tokens.

Per head ``h`` (of ``H``, each ``P`` channels wide), with the step ``d_t =
softplus(dt_t + dt_bias) > 0``, ``A_h < 0`` and ``B_t, C_t [N]`` shared by
the heads of a group (``Gr`` groups of ``H / Gr`` heads in order: head ``h``
reads group ``h // (H / Gr)``; ``B, C [T, N]`` is one group)::

    S_t = exp(d_t A) S_{t-1} + d_t x_t (x) B_t          S [P, N] a head
    y_t = S_t C_t + D x_t

No delta term and no inverse (``ops.gated_deltanet`` has those): a token's
write is an outer product and nothing of the state is read back into it.

**The state's layout.** ``P`` is 64 where a register's lanes are 128, so a
state is kept as tiles ``[N, W]`` of ``pack = W / P`` heads side by side
(``heads_per_tile``): tile ``g``, lane ``j * P + p`` is channel ``p`` of
head ``g * pack + j``, which is where ``x`` flattened to ``[H * P]`` has it.
A state of ``[H, P, N]`` is ``[G, N, W]`` in the pool (``state_shape``,
``pack_state`` / ``unpack_state``), every lane of every tile used; as ``[H,
N, P]`` the pool's last axis would be padded to the lanes and weigh twice
as much. A tile's heads are of one group (``_groups`` refuses a shape
where they would not be), so a tile reads one ``B`` and one ``C``.

Two forms, each in XLA (what the CPU serves and the kernels are tested
against) and as a Pallas kernel (what a TPU serves):

- ``mamba2_scan``: a chunk of tokens of one sequence, in blocks of
  ``block`` tokens (the page): the blocked (SSD) form of the recurrence.
  Inside a block, for a tile's heads: ``C B^T`` (computed once a group)
  under each head's decay mask times ``d x``; between blocks the
  carried state, read by ``C`` and written by ``B^T (d x)``. It returns the
  state at the chunk's end and at the end of one requested block: a
  snapshot at a block boundary costs no second pass and splits no chunk.
  A decay over a block reaches ``e^-50`` and beyond, so every decay is the
  ``exp`` of a difference of the block's cumulative log-decays that is at
  most 0 (``gc_i - gc_j`` for ``j <= i``, ``gc_last - gc_j``, ``gc_i``),
  never a quotient of two ``exp`` that each may overflow.
- ``mamba2_step``: one token of every row of a decode batch, the rows'
  states updated in place in the (donated) pool under their slot ids. Rows
  that decode nothing name the spare slot 0 and hand in ``dt = 0``, which
  leaves a state as it was: the XLA form reads and writes the spare slot
  so, once a padded row; the kernel neither reads nor writes it, and walks
  the live rows' states alone (``gated_deltanet._live_walk``, which the
  delta rule's two step kernels share).

Every product is float32 at ``Precision.HIGHEST`` and the state stays
float32. The jitted wrappers' names are what a device trace calls the
kernels (``mamba2_scan.<n>``, ``mamba2_step.<n>``); readers of traces match
them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_deltanet import (_HIGHEST, _column, _dot, _dot_nt, _dot_tn,
                              _live_walk, _step_params, _step_row,
                              _tiles_a_step)

KERNEL_SCAN = "mamba2_scan"
KERNEL_STEP = "mamba2_step"

_LANES = 128


def heads_per_tile(heads: int, head_dim: int) -> int:
    """Heads that share a state tile's lanes."""
    return math.gcd(heads, max(1, _LANES // head_dim))


def state_shape(heads: int, head_dim: int, state_dim: int) -> tuple:
    """``(G, N, W)``: a sequence's state in one layer as the pool holds
    it."""
    pack = heads_per_tile(heads, head_dim)
    return (heads // pack, state_dim, pack * head_dim)


def pack_state(s: jax.Array) -> jax.Array:
    """``S [H, P, N]`` as the pool's tiles ``[G, N, W]``."""
    h, p, n = s.shape
    pack = heads_per_tile(h, p)
    return s.reshape(h // pack, pack, p, n).transpose(0, 3, 1, 2).reshape(
        h // pack, n, pack * p)


def unpack_state(tiles: jax.Array, head_dim: int) -> jax.Array:
    """``pack_state`` undone: ``[G, N, W] -> [H, P, N]``."""
    g, n, w = tiles.shape
    pack = w // head_dim
    return tiles.reshape(g, n, pack, head_dim).transpose(0, 2, 3, 1).reshape(
        g * pack, head_dim, n)


def _block_update(x, b, c, cb, gd, skip, st, *, pack):
    """One block of one tile's heads. ``x [c, W]`` the tokens' inputs, ``b,
    c [c, N]``, ``cb = c b^T [c, c]``, ``skip [1, W]`` (``D`` over the
    lanes), ``st [N, W]`` the state before the block, all float32; ``gd [2
    pack, c]``: for head ``j`` of the tile, row ``2 j`` the log-decay
    summed from the block's first token to each token (inclusive), row ``2
    j + 1`` the step ``d``. Returns ``(y [c, W], st')``. A padded token has
    ``d = 0``: it leaves the state as it was."""
    n_tok, w = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (n_tok, n_tok), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_tok, n_tok), 1)
    eye = (row == col).astype(jnp.float32)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // (w // pack)
    # The heads' log-decays and steps over their own lanes, and each
    # head's pairs: exp(gc_i - gc_j) for j <= i, else 0 (never exp of a
    # positive number).
    gl = jnp.zeros((n_tok, w), jnp.float32)
    dl = jnp.zeros((n_tok, w), jnp.float32)
    pairs = []
    for j in range(pack):
        gc = gd[2 * j:2 * j + 1]
        gcol = _column(eye, gc)
        gl = jnp.where(lane_head == j, gcol, gl)
        dl = jnp.where(lane_head == j, _column(eye, gd[2 * j + 1:2 * j + 2]),
                       dl)
        pairs.append(cb * jnp.exp(jnp.where(row >= col, gcol - gc,
                                            -jnp.inf)))
    xd = x * dl
    y = _dot(c, st) * jnp.exp(gl) + x * skip
    for j in range(pack):
        y = y + _dot(pairs[j], jnp.where(lane_head == j, xd, 0.0))
    g_last = gl[n_tok - 1:n_tok]
    st = st * jnp.exp(g_last) + _dot_tn(b, xd * jnp.exp(g_last - gl))
    return y, st


def _scan_xla(xw, b, c, cb, gd, skip, st0, snap_block, pack):
    t, hp = xw.shape
    g, nb = gd.shape[:2]
    gr, block = b.shape[0], t // nb
    # A group's tiles share its b, c and c b^T; the groups each their own.
    per_tile = jax.vmap(jax.vmap(
        functools.partial(_block_update, pack=pack),
        in_axes=(0, None, None, None, 0, 0, 0)))

    def grouped(a):  # the tiles' axis (the first) as [groups, tiles of one]
        return a.reshape(gr, g // gr, *a.shape[1:])

    def body(carry, xs):
        st, snap = carry
        i, x_i, b_i, c_i, cb_i, gd_i = xs
        y, st = per_tile(grouped(x_i.astype(jnp.float32)), b_i, c_i, cb_i,
                         grouped(gd_i), grouped(skip), grouped(st))
        st = st.reshape(st0.shape)
        return (st, jnp.where(i == snap_block, st, snap)), y.reshape(
            g, block, hp // g)

    (st, snap), y = jax.lax.scan(
        body, (st0, st0),
        (jnp.arange(nb),
         xw.reshape(nb, block, g, hp // g).transpose(0, 2, 1, 3),
         b.reshape(gr, nb, block, -1).transpose(1, 0, 2, 3),
         c.reshape(gr, nb, block, -1).transpose(1, 0, 2, 3),
         cb.transpose(1, 0, 2, 3), gd.transpose(1, 0, 2, 3)))
    return y.transpose(0, 2, 1, 3).reshape(t, hp), st, snap


def _scan_kernel(snap_ref, x_ref, b_ref, c_ref, cb_ref, gd_ref, skip_ref,
                 st0_ref, y_ref, end_ref, snap_out_ref, st_scr, *, nb, pack):
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _():
        st_scr[...] = st0_ref[0]
        snap_out_ref[0] = st0_ref[0]

    y, st = _block_update(x_ref[...].astype(jnp.float32), b_ref[0],
                          c_ref[0], cb_ref[0, 0], gd_ref[0, 0], skip_ref[0],
                          st_scr[...], pack=pack)
    y_ref[...] = y
    st_scr[...] = st

    @pl.when(blk == snap_ref[0])
    def _():
        snap_out_ref[0] = st

    @pl.when(blk == nb - 1)
    def _():
        end_ref[0] = st


def _scan_pallas(xw, b, c, cb, gd, skip, st0, snap_block, pack, interpret):
    t, hp = xw.shape
    g, nb = gd.shape[:2]
    block = t // nb
    n, w = st0.shape[1:]
    per_group = g // b.shape[0]    # tile i reads group i // per_group

    def group_block(i, k, *_):
        return (i // per_group, k, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, nb),
        in_specs=[
            pl.BlockSpec((block, w), lambda i, k, *_: (k, i)),
            pl.BlockSpec((1, block, n), group_block),
            pl.BlockSpec((1, block, n), group_block),
            pl.BlockSpec((1, 1, block, block),
                         lambda i, k, *_: (i // per_group, k, 0, 0)),
            pl.BlockSpec((1, 1, 2 * pack, block),
                         lambda i, k, *_: (i, k, 0, 0)),
            pl.BlockSpec((1, 1, w), lambda i, k, *_: (i, 0, 0)),
            pl.BlockSpec((1, n, w), lambda i, k, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, w), lambda i, k, *_: (k, i)),
            pl.BlockSpec((1, n, w), lambda i, k, *_: (i, 0, 0)),
            pl.BlockSpec((1, n, w), lambda i, k, *_: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((n, w), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, nb=nb, pack=pack),
        out_shape=[jax.ShapeDtypeStruct((t, hp), jnp.float32),
                   jax.ShapeDtypeStruct(st0.shape, jnp.float32),
                   jax.ShapeDtypeStruct(st0.shape, jnp.float32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(snap_block, (1,)).astype(jnp.int32), xw, b, c, cb, gd,
      skip, st0)


def _groups(a: jax.Array, tiles: int) -> jax.Array:
    """``B`` or ``C`` as float32 ``[rows, Gr, N]``, from that or, one
    group, ``[rows, N]``. The state's ``tiles`` must split evenly over the
    groups: a tile never holds heads of two."""
    a = a.astype(jnp.float32)
    a = a[:, None] if a.ndim == 2 else a
    if tiles % a.shape[1]:
        raise ValueError(
            f"{a.shape[1]} groups of B and C over {tiles} tiles of heads: a "
            f"tile would hold heads of two groups")
    return a


@functools.partial(jax.jit, static_argnames=("block", "kernel", "interpret"))
def mamba2_scan(x, B, C, dt, A, D, state, snap_block, block: int,
                kernel: bool = False, interpret: bool = False):
    """A chunk of one sequence. ``x [T, H, P]`` (after the conv and its
    SiLU), ``B, C [T, Gr, N]`` (or ``[T, N]``: one group), ``dt [T, H]`` the
    steps (after the softplus; 0 at a padded token), ``A, D [H]`` (``A``
    negative), ``state [G, N, W]``
    float32 before the chunk (``state_shape``); ``T`` a whole number of
    blocks. Returns ``(y [T, H, P] float32, the state after the chunk, the
    state after block snap_block)``; the last is the state before the chunk
    where ``snap_block`` names no block."""
    t, h, p = x.shape
    if t % block:
        raise ValueError(f"a chunk of {t} tokens is not a whole number of "
                         f"blocks of {block}")
    f32 = jnp.float32
    nb = t // block
    pack = heads_per_tile(h, p)
    g = h // pack
    dt = dt.astype(f32)
    gc = jnp.cumsum((dt * A.astype(f32)).reshape(nb, block, h), axis=1)
    # [G, nb, 2 pack, block]: a tile's heads' rows, each its running
    # log-decay and then its step.
    gd = jnp.stack([gc, dt.reshape(nb, block, h)], axis=-1).reshape(
        nb, block, g, 2 * pack).transpose(2, 0, 3, 1)
    b, c = (_groups(v, g).transpose(1, 0, 2) for v in (B, C))  # [Gr, T, N]
    cb = jax.vmap(jax.vmap(_dot_nt))(c.reshape(-1, nb, block, c.shape[-1]),
                                     b.reshape(-1, nb, block, b.shape[-1]))
    skip = jnp.repeat(D.astype(f32), p).reshape(g, 1, pack * p)
    xw = x.reshape(t, h * p)
    if kernel:
        y, st, snap = _scan_pallas(xw, b, c, cb, gd, skip, state, snap_block,
                                   pack, interpret)
    else:
        y, st, snap = _scan_xla(xw, b, c, cb, gd, skip, state, snap_block,
                                pack)
    return y.reshape(t, h, p), st, snap


def _step_kernel(slot_ref, stand_ref, layer_ref, a_ref, dx_ref, b_ref, c_ref,
                 pool_ref, y_ref, out_ref, *, tiles):
    del layer_ref  # the index maps read it
    n = b_ref.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (row == col).astype(jnp.float32)

    def update():
        bcol, ccol = _column(eye, b_ref[0, 0]), _column(eye, c_ref[0, 0])

        def tile(j, _):
            at = pl.ds(j, 1)
            st = (pool_ref[0, 0, j] * a_ref[0, 0, at, :]
                  + bcol * dx_ref[0, 0, at, :])
            out_ref[0, 0, j] = st
            y_ref[0, 0, at, :] = jnp.sum(ccol * st, axis=0, keepdims=True)

        # A loop and not its unrolling: a program lowers the kernel once a
        # layer and decode shape, and the block's tiles are many.
        jax.lax.fori_loop(0, tiles, tile, None)

    _step_row(slot_ref, stand_ref, pool_ref, out_ref, y_ref, update)


def _step_pallas(pool, layer, slots, a, dx, b, c, interpret):
    rows, g, w = a.shape
    _, gr, n = b.shape
    # A grid step's tiles are of one group (so many of a group's tiles),
    # and a row's vectors are blocked as [rows, Gr, a group's tiles, W]: a
    # block of fewer than a sublane tile of 8 is then a group's whole.
    tiles = _tiles_a_step(g // gr, n * w * 4)
    steps = g // gr // tiles                      # grid steps a group
    scalars, vec, out, state = _live_walk(slots, layer, g // tiles)

    def in_group(index_map):
        def at(r, j, *refs):
            r, j, _ = index_map(r, j, *refs)
            return (r, j // steps, j % steps, 0)
        return at

    def once(r, j, *_):
        return (r, j // steps, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(rows, g // tiles),
        in_specs=[pl.BlockSpec((1, 1, tiles, w), in_group(vec)),
                  pl.BlockSpec((1, 1, tiles, w), in_group(vec)),
                  pl.BlockSpec((1, 1, 1, n), once),
                  pl.BlockSpec((1, 1, 1, n), once),
                  pl.BlockSpec((1, 1, tiles, n, w), state)],
        out_specs=[pl.BlockSpec((1, 1, tiles, w), in_group(out)),
                   pl.BlockSpec((1, 1, tiles, n, w), state)],
    )
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, tiles=tiles),
        out_shape=[jax.ShapeDtypeStruct((rows, gr, g // gr, w), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # Operand 7 (behind the three scalars) is the pool: updated in
        # place.
        input_output_aliases={7: 1},
        compiler_params=_step_params(tiles * n * w * 4),
        interpret=interpret,
    )(*scalars, a.reshape(rows, gr, -1, w), dx.reshape(rows, gr, -1, w),
      b[:, :, None], c[:, :, None], pool)
    return y.reshape(rows, g, w), pool


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"),
                   donate_argnames=("pool",))
def mamba2_step(pool, layer, slots, x, B, C, dt, A, D, kernel: bool = False,
                interpret: bool = False):
    """One token of every row. ``pool [layers, slots, G, N, W]`` float32
    (donated; row ``r``'s state is ``pool[layer, slots[r]]``, and rows that
    decode nothing share the spare slot 0 and hand in ``dt = 0``), ``x
    [rows, H, P]``, ``B, C [rows, Gr, N]`` (or ``[rows, N]``: one group),
    ``dt [rows, H]`` (after the softplus), ``A, D [H]``. Returns ``(y [rows,
    H, P] float32, pool)``.
    The XLA form updates the spare slot as any other (``dt = 0`` decays it
    by 1 and adds 0: it stays what it was); the kernel reads ``slots`` and
    neither reads nor writes it, nor any slot no row names, and a row of
    the spare slot gets ``D x`` alone for its output."""
    f32 = jnp.float32
    rows, h, p = x.shape
    g, _, w = pool.shape[2:]
    x, dt = x.astype(f32), dt.astype(f32)
    a = jnp.repeat(jnp.exp(dt * A.astype(f32)), p, axis=1).reshape(rows, g, w)
    dx = (dt[..., None] * x).reshape(rows, g, w)
    b, c = _groups(B, g), _groups(C, g)                   # [rows, Gr, N]
    if kernel:
        y, pool = _step_pallas(pool, layer, slots, a, dx, b, c, interpret)
    else:
        def grouped(t):  # [rows, G, ...] as [rows, Gr, a group's tiles, ...]
            return t.reshape(rows, b.shape[1], -1, *t.shape[2:])

        st = (grouped(pool[layer, slots]) * grouped(a)[..., None, :]
              + b[:, :, None, :, None] * grouped(dx)[..., None, :])
        y = jnp.einsum("rkn,rktnw->rktw", c, st, precision=_HIGHEST)
        pool = pool.at[layer, slots].set(st.reshape(rows, g, *st.shape[3:]))
    return y.reshape(rows, h, p) + D.astype(f32)[:, None] * x, pool
