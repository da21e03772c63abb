"""Gated DeltaNet: the recurrence of a linear-attention layer whose cache is
one state a sequence, not a page a block of tokens.

Per value head, with ``k`` of unit length, ``beta`` in (0, 1) and ``alpha =
exp(g)`` in (0, 1]::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

The state is kept transposed, ``St = S^T [dk, dv]`` in float32, so that a
token's read is a row times a matrix and its write an outer product of a
column and a row. Two forms, each in XLA (what the CPU serves and the
kernels are tested against) and as a Pallas kernel (what a TPU serves):

- ``gdn_scan``: a chunk of tokens of one sequence, in blocks of ``block``
  tokens (the page). Inside a block the ``block`` rank-one updates are
  folded into matrix products (the WY form of the delta rule: ``U = (I +
  A)^-1 beta V`` with ``A`` strictly lower-triangular, inverted by products
  of powers, 16 tokens at a time: ``_unit_lower_inverse``); the state
  passes from block to block. It returns the state at the chunk's end and
  at the end of one requested block: a snapshot at a block boundary costs
  no second pass and splits no chunk.
- ``gdn_step``: one token of every row of a decode batch, the rows' states
  updated in place in the (donated) pool under their slot ids. Rows that
  decode nothing name the spare slot 0 and hand in ``g = beta = 0``, which
  leaves a state as it was: the XLA form reads and writes the spare slot
  so, once a padded row; the kernel neither reads nor writes it, and walks
  the live rows' states alone (``_live_walk``, shared with ``kda_step`` and
  ``ops.mamba2``'s ``mamba2_step``).

The jitted wrappers' names are what a device trace calls the kernels
(``gdn_scan.<n>``, ``gdn_step.<n>``); readers of traces match them.

**The channel-wise form** (Kimi-style delta attention, ``kda_scan`` /
``kda_step``): the decay is a vector, one value a key channel, ``alpha_t``
in (0, 1)^dk::

    St_t = (I - beta_t k_t k_t^T) Diag(alpha_t) St_{t-1} + beta_t k_t v_t^T
    o_t = St_t^T q_t

(the scalar form above with ``alpha_t`` the same in every channel). The
state, its pool and the snapshot at a requested block are the scalar
form's. ``beta`` reaches 2 here, so the block's triangular system is
inverted over nested groups (``_nested_unit_lower_inverse``). And a
block's pairwise matrices differ:
``sum_c x_ic k_jc exp(G_ic - G_jc)`` (``G`` the log-decay summed from the
block's start) is no product of two factors that both stay finite over a
page (a channel may lose e^-50 and more), so they are built over three
levels of blocking (``_kda_pairs``): a pair's decay is split at a token
between the two, whose row of ``G`` is taken by static slices and sublane
rolls (``_group_rows``; no matrix product copies rows), and a level is one
product of scaled rows and scaled keys, at the outermost level on the rows
that are kept alone. Every product of the block, the pairwise ones, the
inverse's and the state's, is float32 at ``Precision.HIGHEST`` (six
bfloat16 passes), and the state stays float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_SCAN = "gdn_scan"
KERNEL_STEP = "gdn_step"
KERNEL_KDA_SCAN = "kda_scan"
KERNEL_KDA_STEP = "kda_step"

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):  # a @ b.T
    return _dot(a, b, (((1,), (1,)), ((), ())))


def _dot_tn(a, b):  # a.T @ b
    return _dot(a, b, (((0,), (0,)), ((), ())))


def _column(eye, row):
    """``row [1, n]`` as a column ``[n, 1]``: a lane-to-sublane move made
    of a multiply and a lane reduction, which every backend lowers."""
    return jnp.sum(eye * row, axis=1, keepdims=True)


# Tokens whose triangular system is inverted by a product of powers.
_SUB = 16


def _neumann(n, eye, steps: int):
    """``(I - n)^-1 = (I + n)(I + n^2)(I + n^4)...`` for ``n`` with ``n^(2
    ** steps) = 0``."""
    t, p = eye + n, n
    for _ in range(steps - 1):
        p = _dot(p, p)
        t = t + _dot(t, p)
    return t


def _unit_lower_inverse(n, eye, row, col):
    """``(I - n)^-1`` for a strictly lower-triangular ``n [c, c]``, by
    matrix products alone. The product of powers is exact (``n`` is
    nilpotent) and cheap, and over a whole block of 64 it is useless: keys
    that lie close to each other (a conv and a SiLU leave them all in one
    orthant) make ``n``'s entries a half or more, its 32nd power 1e8, and
    the inverse, whose entries are of order one, the difference of such
    terms. So the diagonal sub-blocks of ``_SUB`` tokens are inverted that
    way (powers up to the 8th: some thousands at worst), ``D = (I -
    n_d)^-1``, and the rest, ``n_o``, which lies below them, is folded in
    as ``(I - n)^-1 = (I - D n_o)^-1 D``: ``D n_o`` is nilpotent in as many
    steps as there are sub-blocks (4 of 16 in a block of 64). Whole-matrix
    operations under masks: no slice that a kernel would have to cut."""
    c = n.shape[0]
    sub = min(_SUB, c)
    if c % sub:
        raise ValueError(f"a block of {c} tokens is not sub-blocks of {sub}")
    within = (row // sub) == (col // sub)
    d = _neumann(jnp.where(within, n, 0.0), eye, (sub - 1).bit_length())
    if c == sub:
        return d
    p = _dot(d, jnp.where(within, 0.0, n))
    return _dot(_neumann(p, eye, (c // sub - 1).bit_length()), d)


def _block_update(q, k, v, gc, beta, st):
    """One block of one head. ``q, k [c, dk]``, ``v [c, dv]`` float32;
    ``gc [1, c]`` the log-decay summed from the block's first token to each
    token (inclusive), ``beta [1, c]``; ``st [dk, dv]`` the state before the
    block. Returns ``(o [c, dv], st')``. A padded token has ``g = 0`` and
    ``beta = 0``: it leaves the state as it was."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(jnp.float32)
    gcol, bcol = _column(eye, gc), _column(eye, beta)
    g_last = jnp.sum(jnp.where(col[:1] == c - 1, gc, 0.0), axis=1,
                     keepdims=True)                               # [1, 1]
    # exp(g_i - g_j) for j <= i, else 0 (never exp of a positive number).
    decay = jnp.exp(jnp.where(row >= col, gcol - gc, -jnp.inf))
    n = jnp.where(row > col, -(bcol * _dot_nt(k, k)) * decay, 0.0)
    t = _unit_lower_inverse(n, eye, row, col)
    u = _dot(t, v * bcol)                                         # [c, dv]
    w = _dot(t, k * (bcol * jnp.exp(gcol)))                       # [c, dk]
    v_new = u - _dot(w, st)
    inside = jnp.where(row >= col, _dot_nt(q, k) * decay, 0.0)
    o = _dot(q * jnp.exp(gcol), st) + _dot(inside, v_new)
    st = st * jnp.exp(g_last) + _dot_tn(k * jnp.exp(g_last - gcol), v_new)
    return o, st


def _blocks(q, k, v, g, beta, block):
    """Head-major blocks and the in-block running log-decay:
    ``q, k [Hk, T, dk] -> same``, ``gb [Hv, T / block, 2, block]``."""
    t, hv = g.shape
    nb = t // block
    gc = jnp.cumsum(g.astype(jnp.float32).reshape(nb, block, hv), axis=1)
    gb = jnp.stack([gc, beta.astype(jnp.float32).reshape(nb, block, hv)],
                   axis=2)                                # [nb, block, 2, hv]
    return (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            gb.transpose(3, 0, 2, 1))


def _scan_xla(qh, kh, vh, gb, st0, snap_block):
    hv, nb = gb.shape[:2]
    rep = hv // qh.shape[0]
    block = gb.shape[-1]

    def split(x):  # [H, T, d] -> [nb, H, block, d]
        return x.reshape(x.shape[0], nb, block, -1).transpose(1, 0, 2, 3)

    per_head = jax.vmap(_block_update)

    def body(carry, xs):
        st, snap = carry
        i, qb, kb, vb, gbb = xs
        f32 = jnp.float32
        o, st = per_head(jnp.repeat(qb, rep, 0).astype(f32),
                         jnp.repeat(kb, rep, 0).astype(f32), vb.astype(f32),
                         gbb[:, 0:1], gbb[:, 1:2], st)
        return (st, jnp.where(i == snap_block, st, snap)), o

    (st, snap), o = jax.lax.scan(
        body, (st0, st0),
        (jnp.arange(nb), split(qh), split(kh), split(vh),
         gb.transpose(1, 0, 2, 3)))
    return o.transpose(1, 0, 2, 3).reshape(hv, nb * block, -1), st, snap


def _scan_kernel(snap_ref, q_ref, k_ref, v_ref, gb_ref, st0_ref,
                 o_ref, end_ref, snap_out_ref, st_scr, *, nb):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        st_scr[...] = st0_ref[0]
        snap_out_ref[0] = st0_ref[0]

    f32 = jnp.float32
    o, st = _block_update(q_ref[0].astype(f32), k_ref[0].astype(f32),
                          v_ref[0].astype(f32), gb_ref[0, 0, 0:1, :],
                          gb_ref[0, 0, 1:2, :], st_scr[...])
    o_ref[0] = o.astype(o_ref.dtype)
    st_scr[...] = st

    @pl.when(b == snap_ref[0])
    def _():
        snap_out_ref[0] = st

    @pl.when(b == nb - 1)
    def _():
        end_ref[0] = st


def _scan_pallas(qh, kh, vh, gb, st0, snap_block, interpret):
    hk, t, dk = qh.shape
    hv, nb, _, block = gb.shape
    dv = vh.shape[-1]
    rep = hv // hk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hv, nb),
        in_specs=[
            pl.BlockSpec((1, block, dk), lambda h, b, *_: (h // rep, b, 0)),
            pl.BlockSpec((1, block, dk), lambda h, b, *_: (h // rep, b, 0)),
            pl.BlockSpec((1, block, dv), lambda h, b, *_: (h, b, 0)),
            pl.BlockSpec((1, 1, 2, block), lambda h, b, *_: (h, b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, b, *_: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, dv), lambda h, b, *_: (h, b, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, b, *_: (h, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, b, *_: (h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, nb=nb),
        out_shape=[jax.ShapeDtypeStruct((hv, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((hv, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((hv, dk, dv), jnp.float32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(snap_block, (1,)).astype(jnp.int32), qh, kh, vh, gb, st0)


@functools.partial(jax.jit, static_argnames=("block", "kernel", "interpret"))
def gdn_scan(q, k, v, g, beta, state, snap_block, block: int,
             kernel: bool = False, interpret: bool = False):
    """A chunk of one sequence. ``q, k [T, Hk, dk]`` (unit-length keys,
    scaled queries), ``v [T, Hv, dv]``, ``g, beta [T, Hv]`` (both 0 at a
    padded token), ``state [Hv, dk, dv]`` float32 before the chunk; ``T`` a
    whole number of blocks. Returns ``(o [T, Hv, dv] float32, the state
    after the chunk, the state after block snap_block)``; the last is the
    state before the chunk where ``snap_block`` names no block."""
    if q.shape[0] % block:
        raise ValueError(f"a chunk of {q.shape[0]} tokens is not a whole "
                         f"number of blocks of {block}")
    qh, kh, vh, gb = _blocks(q, k, v, g, beta, block)
    if kernel:
        o, st, snap = _scan_pallas(qh, kh, vh, gb, state, snap_block,
                                   interpret)
    else:
        o, st, snap = _scan_xla(qh, kh, vh, gb, state, snap_block)
    return o.transpose(1, 0, 2), st, snap


# A grid step of a decode step moves at most this much of a row's state in,
# and as much out: half of a state of the three served models (64 tiles of
# [128, 128] float32). A full batch moves 600 GB/s at any block from 512 KiB
# up; a row that decodes nothing costs its grid steps, about 0.35 us each,
# so few large blocks serve a batch of few live rows best, and a block of
# the whole state gains no more (``hack/bench_mamba2.py``, ``bench_kda.py``;
# ``PERF.md`` section 6, PR 60).
_STEP_BLOCK_BYTES = 2 * 2 ** 20


def _tiles_a_step(tiles: int, tile_bytes: int) -> int:
    """How many of a state's ``tiles`` (heads, or lane tiles of heads) one
    grid step of a decode step takes: the most that ``_STEP_BLOCK_BYTES``
    holds, in whole sublane tiles of 8 (the rows' vectors are blocked with
    them), else all of them."""
    if tiles % 8:
        return tiles
    most = max(8, _STEP_BLOCK_BYTES // tile_bytes)
    return max(t for t in range(8, tiles + 1, 8)
               if tiles % t == 0 and t <= most)


def _step_params(block_bytes: int):
    """Room for a state block in and out, each twice buffered, and the
    body's own values beside them."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=4 * block_bytes + 12 * 2 ** 20)


def _live_walk(slots, layer, groups: int):
    """What a decode step's grid ``(rows, groups)`` walks, from the rows'
    slots alone: a row is live where its slot is not the spare slot 0, and
    only a live row's state is read, updated and written.

    Returns ``(scalars, vec, out, state)``: the kernel's prefetched scalars
    ``(slot_at [rows], stand [rows], layer [1])`` and the index maps of a
    row's vectors (blocks ``[1, tiles, width]``: ``vec`` what the kernel
    reads, ``out`` what it writes, every block of every row) and of the
    pool (blocks ``[1, 1, tiles, ., .]``), read and written under the same
    map. ``stand[r]`` is -1 for a live row, which walks its own slot's
    groups ``j`` in order. A row that decodes nothing stands still instead:
    at the last group of the live row before it, the block that row has
    just left behind, or, with no live row before it, at group 0 of the
    live row after it, the block that row starts with. Pallas copies a
    block in when its index changes and out before it changes, so a step
    whose block stands still moves nothing, and what a live row wrote goes
    out once, when the walk moves on (``_step_row`` guards the body). With
    no live row at all the walk stands at the spare slot's group 0, which
    ``_step_row`` hands through as it was."""
    slots = slots.astype(jnp.int32)
    rows = slots.shape[0]
    at = jnp.arange(rows, dtype=jnp.int32)
    live = slots != 0
    before = jax.lax.cummax(jnp.where(live, at, -1))
    after = jax.lax.cummin(jnp.where(live, at, rows), reverse=True)
    slot_at = slots[jnp.where(before >= 0, before,
                              jnp.minimum(after, rows - 1))]
    stand = jnp.where(live, -1, jnp.where(before >= 0, groups - 1, 0))
    scalars = (slot_at, stand.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32))

    def group(r, j, stand_ref):
        return jnp.where(stand_ref[r] < 0, j, stand_ref[r])

    def vec(r, j, slot_ref, stand_ref, layer_ref):
        return (r, group(r, j, stand_ref), 0)

    def out(r, j, *_):
        return (r, j, 0)

    def state(r, j, slot_ref, stand_ref, layer_ref):
        return (layer_ref[0], slot_ref[r], group(r, j, stand_ref), 0, 0)

    return scalars, vec, out, state


def _step_row(slot_ref, stand_ref, pool_ref, out_ref, o_ref, update):
    """One grid step of ``_live_walk``: ``update()`` for a live row; for a
    row that decodes nothing, zeros for its output and no touch of the
    state block, which keeps what the live row it stands at left there."""
    r, j = pl.program_id(0), pl.program_id(1)
    pl.when(stand_ref[r] < 0)(update)

    @pl.when(stand_ref[r] >= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((r == 0) & (j == 0) & (slot_ref[0] == 0))
    def _():  # nothing is live: the block that goes out is the spare slot's
        out_ref[...] = pool_ref[...]


def _step_kernel(slot_ref, stand_ref, layer_ref, q_ref, k_ref, v_ref, a_ref,
                 b_ref, pool_ref, o_ref, out_ref, *, heads, channelwise):
    del layer_ref  # the index maps read it
    dk = q_ref.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    eye = (row == col).astype(jnp.float32)

    sub = math.gcd(heads, 8)

    def group(i, _):
        # A sublane tile of heads: their vectors loaded once, aligned, and
        # taken apart by static slices.
        base = pl.multiple_of(i * sub, sub)
        q, k, v, a, b = (ref[0, pl.ds(base, sub), :]
                         for ref in (q_ref, k_ref, v_ref, a_ref, b_ref))
        for u in range(sub):
            at = slice(u, u + 1)
            st = pool_ref[0, 0, base + u] * (
                _column(eye, a[at]) if channelwise else a[at])   # decay
            kcol = _column(eye, k[at])
            sk = jnp.sum(kcol * st, axis=0, keepdims=True)       # [1, dv]
            st = st + kcol * (b[at] * (v[at] - sk))
            out_ref[0, 0, base + u] = st
            o_ref[0, pl.ds(base + u, 1), :] = jnp.sum(
                _column(eye, q[at]) * st, axis=0, keepdims=True)

    def update():
        # A loop over the block's tiles of heads and not its unrolling: a
        # program lowers the kernel once a layer and decode shape.
        jax.lax.fori_loop(0, heads // sub, group, None)

    _step_row(slot_ref, stand_ref, pool_ref, out_ref, o_ref, update)


def _step_pallas(pool, layer, slots, q, k, v, alpha, beta, interpret,
                 channelwise=False):
    """``alpha [rows, Hv, dv]`` (one decay a head, over the value lanes) or,
    ``channelwise``, ``[rows, Hv, dk]`` (one a key channel)."""
    rows, hv, dk = q.shape
    dv = v.shape[-1]
    heads = _tiles_a_step(hv, dk * dv * 4)
    scalars, vec, out, state = _live_walk(slots, layer, hv // heads)

    def vecs(width):
        return pl.BlockSpec((1, heads, width), vec)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(rows, hv // heads),
        in_specs=[vecs(dk), vecs(dk), vecs(dv), vecs(alpha.shape[-1]),
                  vecs(dv), pl.BlockSpec((1, 1, heads, dk, dv), state)],
        out_specs=[pl.BlockSpec((1, heads, dv), out),
                   pl.BlockSpec((1, 1, heads, dk, dv), state)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads,
                          channelwise=channelwise),
        out_shape=[jax.ShapeDtypeStruct((rows, hv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # Operand 8 (behind the three scalars) is the pool: updated in
        # place.
        input_output_aliases={8: 1},
        compiler_params=_step_params(heads * dk * dv * 4),
        interpret=interpret,
    )(*scalars, q, k, v, alpha,
      jnp.broadcast_to(beta[..., None], v.shape), pool)
    return o, pool


def _step_xla(pool, layer, slots, q, k, v, alpha, beta):
    # alpha [rows, Hv, 1 | dk]: one decay a head, or one a key channel.
    st = pool[layer, slots] * alpha[..., None]          # [rows, Hv, dk, dv]
    sk = jnp.einsum("rhk,rhkv->rhv", k, st, precision=_HIGHEST)
    st = st + k[..., :, None] * (beta[..., None] * (v - sk))[..., None, :]
    o = jnp.einsum("rhk,rhkv->rhv", q, st, precision=_HIGHEST)
    return o, pool.at[layer, slots].set(st)


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"),
                   donate_argnames=("pool",))
def gdn_step(pool, layer, slots, q, k, v, g, beta, kernel: bool = False,
             interpret: bool = False):
    """One token of every row. ``pool [layers, slots, Hv, dk, dv]``
    float32 (donated; row ``r``'s state is ``pool[layer, slots[r]]``, and
    rows that decode nothing share the spare slot 0 and hand in ``g = beta
    = 0``), ``q, k [rows, Hk, dk]``, ``v [rows, Hv, dv]``, ``g, beta [rows,
    Hv]``. Returns ``(o [rows, Hv, dv] float32, pool)``. The XLA form
    updates the spare slot as any other (by 1 and 0: it stays what it
    was); the kernel reads ``slots`` and neither reads nor writes it, nor
    any slot no row names, and a row of the spare slot gets zeros for its
    output."""
    f32 = jnp.float32
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x.astype(f32), rep, axis=1) for x in (q, k))
    v, alpha, beta = v.astype(f32), jnp.exp(g.astype(f32)), beta.astype(f32)
    if kernel:
        return _step_pallas(pool, layer, slots, q, k, v,
                            jnp.broadcast_to(alpha[..., None], v.shape),
                            beta, interpret)
    return _step_xla(pool, layer, slots, q, k, v, alpha[..., None], beta)


# -- the channel-wise form --

# Group sizes of the pairwise matrices' levels, outermost first (the block
# itself stands before them).
_KDA_GROUPS = (16, 4, 1)


def _group_rows(a, size, offset=0):
    """``a[(i // size) * size + offset]`` in row ``i``: every group of
    ``size`` rows gets its own row ``offset``, bit for bit and by no
    product. Static row slices, each broadcast over its group, where the
    groups are whole tiles of 8 rows; in smaller groups, ``a`` rolled by
    up to ``size - 1`` rows under a select on ``i % size``."""
    c, d = a.shape
    if size >= c:
        return a[offset:offset + 1]
    if size % 8 == 0:
        return jnp.concatenate(
            [jnp.broadcast_to(a[s + offset:s + offset + 1], (size, d))
             for s in range(0, c, size)], axis=0)
    at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) % size
    out = a
    for r in range(size):
        if r != offset:
            out = jnp.where(at == r, jnp.roll(a, r - offset, axis=0), out)
    return out


def _kda_pairs(x, k, gc):
    """``P[i, j] = sum_c x[i, c] k[j, c] exp(gc[i, c] - gc[j, c])`` for ``j
    < i`` (0 elsewhere): ``x [n, dk]`` (a block's queries and keys stacked:
    ``n`` a multiple of the block's ``c`` tokens, row ``i`` is token ``i %
    c``), ``k, gc [c, dk]``.

    ``exp(gc_i - gc_j)`` is at most 1 and ``exp(-gc_j)`` alone may be e^50:
    a pair's decay is split at a token between the two, ``exp(gc_i - gc_m)
    exp(gc_m - gc_j)`` with ``j < m <= i``, both factors at most 1 whatever
    the decays (a product that underflows was under e^-87 itself). Which
    ``m`` serves a pair: tokens are grouped by 16, by 4 inside a 16 and
    singly inside a 4; a pair whose tokens part at a level takes the first
    token of the row's group at that level. A level's pairs are then, for
    each of the up to three earlier groups ``p`` a row's group may follow
    inside the enclosing one, rows scaled by ``exp(gc_i - gc_m(i))`` times
    keys scaled by ``exp(gc_m - gc_j)``, kept where row and key stand so.

    The reference rows ``gc_m`` are rows of ``gc`` at static places, the
    same float32 row on both sides of a pair, taken by slices and rolls
    (``_group_rows``) and never by a product. At the outermost level group
    ``p`` keeps whole rows ``p * inner ..``, so only those are multiplied
    (aligned slices, one product a group); at the levels inside it the
    kept rows are interleaved: one product of all rows with the level's
    key operands stacked, under masks. Five products for a block of 64,
    all at the highest precision: at three bfloat16 passes for six the
    close keys' state read 1.4e-4 of the recurrence for 2e-5 (2e-4 is
    allowed) and the scan took 1.97 ms a layer's chunk for 2.08 on the
    chip (``PERF.md``, PR 49). The XLA form and the kernel share all of
    it."""
    c = k.shape[0]
    parts = x.shape[0] // c

    def tall(a):                        # over the stacked queries and keys
        return a if parts == 1 else jnp.concatenate([a] * parts, axis=0)

    token = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    row = tall(jax.lax.broadcasted_iota(jnp.int32, (c, c), 0))
    col = tall(jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    sizes = [c] + [s for s in _KDA_GROUPS if s < c and c % s == 0]
    out = jnp.zeros((parts * c, c), jnp.float32)
    for outer, inner in zip(sizes, sizes[1:]):
        rows = x if inner == 1 else x * tall(
            jnp.exp(gc - _group_rows(gc, inner)))
        groups = range(1, outer // inner)
        # For key j: the start of group p of its enclosing group, the
        # reference of the rows that stand in that group; it serves the
        # keys before it.
        keys = [k * jnp.exp(jnp.where(
            token % outer < p * inner,
            _group_rows(gc, outer, p * inner) - gc, -jnp.inf))
            for p in groups]
        if outer == c:
            got = [jnp.zeros((parts * inner, c), jnp.float32)] + [
                _dot_nt(jnp.concatenate(
                    [rows[m * c + p * inner:m * c + (p + 1) * inner]
                     for m in range(parts)], axis=0), keys_p)
                for p, keys_p in zip(groups, keys)]
            out = jnp.concatenate([g[m * inner:(m + 1) * inner]
                                   for m in range(parts) for g in got],
                                  axis=0)
            continue
        got = _dot_nt(rows, jnp.concatenate(keys, axis=0))
        same = (row // outer) == (col // outer)
        group = (row % outer) // inner           # the row's, inside outer
        for p in groups:
            out = out + jnp.where(same & (group == p),
                                  got[:, (p - 1) * c:p * c], 0.0)
    return out


def _nested_unit_lower_inverse(n, eye, row, col):
    """``(I - n)^-1`` for a strictly lower-triangular ``n [c, c]`` whose
    entries may reach 2 (``beta`` in (0, 2) on keys that lie close
    together). ``_unit_lower_inverse`` takes powers of a 16-token block up
    to the 8th: with such entries they reach 1e5 and the inverse, of
    order one, is what is left of their differences (an error of 0.1 in
    float32, measured). Here no power beyond the third is ever formed:
    groups of 4 tokens are inverted by ``(I + n)(I + n^2)``, and each
    further level (16, then the block) folds what lies between its four
    groups in as ``(I - D n_o)^-1 D``, ``D n_o`` nilpotent in four steps
    again. As many matrix products as the other."""
    c = n.shape[0]
    sizes = [s for s in (4, 16) if s < c and c % s == 0] + [c]
    inside = (row // sizes[0]) == (col // sizes[0])
    d = _neumann(jnp.where(inside, n, 0.0), eye,
                 (sizes[0] - 1).bit_length())
    for below, size in zip(sizes, sizes[1:]):
        level = (row // size) == (col // size)
        p = _dot(d, jnp.where(level & ~inside, n, 0.0))
        d = _dot(_neumann(p, eye, (size // below - 1).bit_length()), d)
        inside = level
    return d


def _kda_block_update(q, k, v, gc, beta, st):
    """One block of one head, channel-wise decay. ``q, k [c, dk]``, ``v [c,
    dv]`` float32; ``gc [c, dk]`` each channel's log-decay summed from the
    block's first token to each token (inclusive), ``beta [1, c]``; ``st
    [dk, dv]`` the state before the block. Returns ``(o [c, dv], st')``.
    ``_block_update`` with the decay inside the sums over channels."""
    c, dk = q.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(jnp.float32)
    bcol = _column(eye, beta)
    decay = jnp.exp(gc)                           # from the block's start
    pairs = _kda_pairs(jnp.concatenate([q, k], axis=0), k, gc)
    n = -(bcol * pairs[c:])                       # strictly lower
    t = _nested_unit_lower_inverse(n, eye, row, col)
    u = _dot(t, v * bcol)                                         # [c, dv]
    w = _dot(t, k * decay * bcol)                                 # [c, dk]
    v_new = u - _dot(w, st)
    inside = pairs[:c] + eye * jnp.sum(q * k, axis=1, keepdims=True)
    o = _dot(q * decay, st) + _dot(inside, v_new)
    g_last = gc[c - 1:c]                                          # [1, dk]
    krow = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    whole = _column((krow == kcol).astype(jnp.float32), jnp.exp(g_last))
    st = st * whole + _dot_tn(k * jnp.exp(g_last - gc), v_new)
    return o, st


def _kda_blocks(q, k, v, g, beta, block):
    """Head-major operands: ``q, k, v [H, T, d]``, the in-block running
    log-decay ``gc [H, T, dk]`` and ``beta [H, T / block, 1, block]``."""
    t, h, dk = g.shape
    nb = t // block
    gc = jnp.cumsum(g.astype(jnp.float32).reshape(nb, block, h, dk), axis=1)
    return (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            gc.reshape(t, h, dk).transpose(1, 0, 2),
            beta.astype(jnp.float32).reshape(nb, 1, block, h).transpose(
                3, 0, 1, 2))


def _kda_scan_xla(qh, kh, vh, gc, bb, st0, snap_block):
    h, nb = bb.shape[:2]
    block = bb.shape[-1]

    def split(x):  # [H, T, d] -> [nb, H, block, d]
        return x.reshape(h, nb, block, -1).transpose(1, 0, 2, 3)

    per_head = jax.vmap(_kda_block_update)

    def body(carry, xs):
        st, snap = carry
        i, qb, kb, vb, gb, betab = xs
        f32 = jnp.float32
        o, st = per_head(qb.astype(f32), kb.astype(f32), vb.astype(f32),
                         gb, betab, st)
        return (st, jnp.where(i == snap_block, st, snap)), o

    (st, snap), o = jax.lax.scan(
        body, (st0, st0),
        (jnp.arange(nb), split(qh), split(kh), split(vh), split(gc),
         bb.transpose(1, 0, 2, 3)))
    return o.transpose(1, 0, 2, 3).reshape(h, nb * block, -1), st, snap


def _kda_scan_kernel(snap_ref, q_ref, k_ref, v_ref, gc_ref, b_ref, st0_ref,
                     o_ref, end_ref, snap_out_ref, st_scr, *, nb):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        st_scr[...] = st0_ref[0]
        snap_out_ref[0] = st0_ref[0]

    f32 = jnp.float32
    o, st = _kda_block_update(q_ref[0].astype(f32), k_ref[0].astype(f32),
                              v_ref[0].astype(f32), gc_ref[0],
                              b_ref[0, 0], st_scr[...])
    o_ref[0] = o.astype(o_ref.dtype)
    st_scr[...] = st

    @pl.when(b == snap_ref[0])
    def _():
        snap_out_ref[0] = st

    @pl.when(b == nb - 1)
    def _():
        end_ref[0] = st


def _kda_scan_pallas(qh, kh, vh, gc, bb, st0, snap_block, interpret):
    h, t, dk = qh.shape
    nb, block = bb.shape[1], bb.shape[-1]
    dv = vh.shape[-1]

    def tokens(width):
        return pl.BlockSpec((1, block, width), lambda i, b, *_: (i, b, 0))

    def state():
        return pl.BlockSpec((1, dk, dv), lambda i, b, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, nb),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                  pl.BlockSpec((1, 1, 1, block),
                               lambda i, b, *_: (i, b, 0, 0)),
                  state()],
        out_specs=[tokens(dv), state(), state()],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kda_scan_kernel, nb=nb),
        out_shape=[jax.ShapeDtypeStruct((h, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, dk, dv), jnp.float32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(snap_block, (1,)).astype(jnp.int32), qh, kh, vh, gc, bb,
      st0)


@functools.partial(jax.jit, static_argnames=("block", "kernel", "interpret"))
def kda_scan(q, k, v, g, beta, state, snap_block, block: int,
             kernel: bool = False, interpret: bool = False):
    """``gdn_scan`` with a decay for every key channel: ``q, k [T, H, dk]``,
    ``v [T, H, dv]``, ``g [T, H, dk]`` (a token's log-decay a channel, at
    most 0), ``beta [T, H]`` (``g`` and ``beta`` 0 at a padded token),
    ``state [H, dk, dv]`` float32. Returns what ``gdn_scan`` returns."""
    if q.shape[0] % block:
        raise ValueError(f"a chunk of {q.shape[0]} tokens is not a whole "
                         f"number of blocks of {block}")
    operands = _kda_blocks(q, k, v, g, beta, block)
    if kernel:
        o, st, snap = _kda_scan_pallas(*operands, state, snap_block,
                                       interpret)
    else:
        o, st, snap = _kda_scan_xla(*operands, state, snap_block)
    return o.transpose(1, 0, 2), st, snap


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"),
                   donate_argnames=("pool",))
def kda_step(pool, layer, slots, q, k, v, g, beta, kernel: bool = False,
             interpret: bool = False):
    """``gdn_step`` with a decay for every key channel: ``q, k, g [rows, H,
    dk]``, ``v [rows, H, dv]``, ``beta [rows, H]``; as there, the kernel
    moves the states of the rows whose slot is not the spare slot 0 and no
    other."""
    f32 = jnp.float32
    args = (pool, layer, slots, q.astype(f32), k.astype(f32), v.astype(f32),
            jnp.exp(g.astype(f32)), beta.astype(f32))
    if kernel:
        return _step_pallas(*args, interpret, channelwise=True)
    return _step_xla(*args)
