"""Mamba-2's recurrence (``ops.mamba2``) on the CPU: the blocked scan and
the decode step, each in XLA and as a Pallas kernel (interpreted), against
the definition a token at a time in float64; the state's tiles; decays that
a quotient of two ``exp`` would not survive; and a state kept in bfloat16,
which has to fail where the float32 one passes."""

import re

import jax.numpy as jnp
import live_rows
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops import mamba2 as m2
from llmd_kv_cache_tpu.ops.mamba2 import mamba2_scan, mamba2_step

FORMS = pytest.mark.parametrize("kernel", [False, True],
                                ids=["xla", "pallas"])


def recurrence(x, b, c, dt, a, skip, state):
    """``S_t = exp(d_t A) S_{t-1} + d_t x_t (x) B_t``, ``y_t = S_t C_t + D
    x_t`` a token at a time, in float64: ``x [T, H, P]``, ``b, c [T, N]``,
    ``dt [T, H]``, ``state [H, P, N]``. Returns the outputs and every
    token's state."""
    x, b, c, dt, a, skip = (np.asarray(v, np.float64)
                            for v in (x, b, c, dt, a, skip))
    S = np.asarray(state, np.float64).copy()
    outs, states = [], []
    for t in range(x.shape[0]):
        S = (np.exp(dt[t] * a)[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        outs.append(np.einsum("hpn,n->hp", S, c[t]) + skip[:, None] * x[t])
        states.append(S.copy())
    return np.stack(outs), states


def inputs(tokens, valid, seed=0, heads=4, p=16, n=16, fastest=16.0):
    """x, B and C in one orthant with neighbours alike (what a conv and a
    SiLU leave); a step a head log-uniform in [1e-3, 1e-1] times a token's
    own factor and ``A`` up to ``-fastest``: over a block of 16 a head's
    log-decay runs from about -0.05 to -50 and beyond. The last ``tokens -
    valid`` tokens are padding (``dt`` 0)."""
    rng = np.random.default_rng(seed)

    def behind_silu(width):
        raw = rng.normal(size=(tokens + 3, width))
        mixed = sum(0.5 * raw[j:j + tokens] for j in range(4))
        return mixed / (1 + np.exp(-mixed))

    x = behind_silu(heads * p).reshape(tokens, heads, p)
    b, c = behind_silu(n), behind_silu(n)
    dt = (np.exp(np.linspace(np.log(1e-3), np.log(1e-1), heads))[None, :]
          * rng.uniform(1.0, 4.0, size=(tokens, heads)))
    dt = np.where((np.arange(tokens) < valid)[:, None], dt, 0.0)
    a = -np.linspace(1.0, fastest, heads)
    skip = rng.uniform(0.5, 1.5, size=(heads,))
    state = rng.normal(size=(heads, p, n))
    return tuple(np.asarray(v, np.float32)
                 for v in (x, b, c, dt, a, skip, state))


def test_a_states_tiles_hold_heads_side_by_side_and_no_lane_is_padding():
    assert m2.heads_per_tile(128, 64) == 2 and m2.heads_per_tile(4, 16) == 4
    assert m2.heads_per_tile(3, 64) == 1 and m2.heads_per_tile(8, 256) == 1
    assert m2.state_shape(128, 64, 128) == (64, 128, 128)
    s = np.random.default_rng(0).normal(size=(6, 32, 8)).astype(np.float32)
    tiles = m2.pack_state(jnp.asarray(s))
    assert tiles.shape == m2.state_shape(6, 32, 8) == (3, 8, 64)
    # Tile g, lane j * P + p is channel p of head g * pack + j: where x
    # flattened to [H * P] has it.
    assert float(tiles[1, 5, 32 + 7]) == s[3, 7, 5]
    np.testing.assert_array_equal(m2.unpack_state(tiles, 32), s)


@FORMS
@pytest.mark.parametrize("block", [16, 32, 64])
def test_the_scan_is_the_recurrence(kernel, block):
    """A padded chunk (the last 23 tokens are not real) whose heads lose
    from e^-0.05 to e^-50 and beyond over a block: outputs of the real
    tokens, the state at the chunk's end and at a block boundary inside
    it, to 1e-4 in float32."""
    tokens, valid = 192, 169
    x, b, c, dt, a, skip, state = inputs(tokens, valid)
    over = dt[:block].sum(0) * a
    assert over.min() < -50 < -1 < over.max()
    want, states = recurrence(x, b, c, dt, a, skip, state)
    snap_block = 128 // block - 1                # the boundary at token 128
    y, end, inner = mamba2_scan(x, b, c, dt, a, skip, m2.pack_state(state),
                                snap_block, block=block, kernel=kernel,
                                interpret=True)
    assert y.dtype == end.dtype == inner.dtype == jnp.float32
    assert np.isfinite(y).all() and np.isfinite(end).all()
    scale = np.abs(want).max()
    np.testing.assert_allclose(y[:valid], want[:valid], atol=1e-4 * scale)
    np.testing.assert_allclose(m2.unpack_state(end, 16), states[valid - 1],
                               atol=1e-4)
    np.testing.assert_allclose(m2.unpack_state(inner, 16), states[127],
                               atol=1e-4)


@FORMS
def test_decays_are_differences_of_log_decays_never_quotients(kernel):
    """A head that loses e^-8 a token loses e^-512 over a page of 64:
    ``exp`` of the negated running log-decay is infinite in float32 and a
    quotient of two such is NaN. The scan forms ``exp`` of differences that
    are at most 0 and stays finite and right."""
    x, b, c, dt, a, skip, state = inputs(128, 128, seed=1, fastest=80.0)
    dt = np.full_like(dt, 0.1)
    assert (dt[:64].sum(0) * a).min() < -500
    want, states = recurrence(x, b, c, dt, a, skip, state)
    y, end, inner = mamba2_scan(x, b, c, dt, a, skip, m2.pack_state(state),
                                0, block=64, kernel=kernel, interpret=True)
    assert np.isfinite(y).all() and np.isfinite(end).all()
    np.testing.assert_allclose(y, want, atol=1e-4 * np.abs(want).max())
    np.testing.assert_allclose(m2.unpack_state(end, 16), states[-1],
                               atol=1e-4)
    np.testing.assert_allclose(m2.unpack_state(inner, 16), states[63],
                               atol=1e-4)


def test_a_snapshot_nobody_asked_for_is_the_state_before_the_chunk():
    x, b, c, dt, a, skip, state = inputs(32, 32, seed=2)
    tiles = m2.pack_state(state)
    _, _, inner = mamba2_scan(x, b, c, dt, a, skip, tiles, -1, block=16)
    np.testing.assert_array_equal(inner, tiles)


def test_chunks_of_unequal_size_chain_to_the_whole():
    x, b, c, dt, a, skip, state = inputs(96, 96, seed=3)
    want, states = recurrence(x, b, c, dt, a, skip, state)
    tiles, at, outs = m2.pack_state(state), 0, []
    for size in (32, 48, 16):
        y, tiles, _ = mamba2_scan(
            *(v[at:at + size] for v in (x, b, c, dt)), a, skip, tiles, -1,
            block=16)
        outs.append(y)
        at += size
    np.testing.assert_allclose(np.concatenate(outs), want,
                               atol=1e-4 * np.abs(want).max())
    np.testing.assert_allclose(m2.unpack_state(tiles, 16), states[-1],
                               atol=1e-4)


def test_a_state_kept_in_bfloat16_fails_where_float32_passes():
    """The same chunks chained with the state rounded to bfloat16 between
    them: the end state is off by over ten times the limit the float32
    state meets."""
    x, b, c, dt, a, skip, state = inputs(96, 96, seed=4, fastest=2.0)
    _, states = recurrence(x, b, c, dt, a, skip, state)

    def chained(keep):
        tiles = m2.pack_state(state)
        for at in range(0, 96, 16):
            _, tiles, _ = mamba2_scan(
                *(v[at:at + 16] for v in (x, b, c, dt)), a, skip, tiles, -1,
                block=16)
            tiles = keep(tiles)
        return float(np.abs(m2.unpack_state(tiles, 16) - states[-1]).max())

    assert chained(lambda t: t) < 1e-4
    assert chained(lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)) > (
        1e-3)


@FORMS
def test_the_step_is_one_token_of_the_scan_and_of_the_recurrence(kernel):
    """Three rows' states under their slots, a fourth row that decodes
    nothing (slot 0, ``dt`` 0): each live row's output and state are one
    token of the recurrence and of the scan (a block whose other tokens
    are padding); every other slot, the spare one too, stays as it was."""
    rows, heads, p, n = 4, 8, 16, 16
    x, b, c, dt, a, skip, _ = inputs(rows, 3, seed=5, heads=heads)
    pool = np.random.default_rng(5).normal(
        size=(2, 6, *m2.state_shape(heads, p, n))).astype(np.float32)
    slots = np.array([4, 2, 5, 0], np.int32)
    y, new = mamba2_step(jnp.asarray(pool), 1, slots, x, b, c, dt, a, skip,
                         kernel=kernel, interpret=True)
    want = pool.copy()
    for r in range(3):
        before = m2.unpack_state(jnp.asarray(pool[1, slots[r]]), p)
        out, states = recurrence(x[r:r + 1], b[r:r + 1], c[r:r + 1],
                                 dt[r:r + 1], a, skip, before)
        np.testing.assert_allclose(y[r], out[0], atol=2e-5)
        want[1, slots[r]] = m2.pack_state(jnp.asarray(states[0], np.float32))

        def padded(v):
            return np.concatenate([v[r:r + 1], np.zeros_like(v[:1])
                                   .repeat(15, 0)])
        y_scan, end, _ = mamba2_scan(
            padded(x), padded(b), padded(c), padded(dt), a, skip,
            pool[1, slots[r]], -1, block=16)
        np.testing.assert_allclose(y[r], y_scan[0], atol=2e-5)
        np.testing.assert_allclose(new[1, slots[r]], end, atol=2e-5)
    np.testing.assert_allclose(new, want, atol=2e-5)   # nothing else moved
    np.testing.assert_array_equal(new[1, 0], pool[1, 0])
    np.testing.assert_array_equal(new[0], pool[0])


@live_rows.CASES
def test_the_step_moves_the_live_rows_states_and_no_other(slots, monkeypatch):
    """A row of the spare slot 0 costs the kernel no state: 16 tiles a
    state, 8 a grid step, rows that hand in ``dt = 0`` as the engine's do."""
    heads, p, n = 32, 64, 16
    shape = m2.state_shape(heads, p, n)
    assert shape == (16, 16, 128)
    live_rows.two_groups_a_row(monkeypatch, int(np.prod(shape)) * 4)
    x, b, c, dt, a, skip, _ = inputs(live_rows.ROWS, live_rows.ROWS, seed=6,
                                     heads=heads, p=p, n=n)
    dt = dt * (np.asarray(slots) != 0)[:, None]
    pool = np.random.default_rng(6).normal(
        size=(2, live_rows.SLOTS, *shape)).astype(np.float32)

    def step(pool, slots, kernel):
        return mamba2_step.__wrapped__(pool, 1, slots, x, b, c, dt, a, skip,
                                       kernel=kernel, interpret=kernel)

    live_rows.check(step, pool, slots)


def test_the_kernels_names_are_what_a_trace_calls_them():
    """``kvbench/metrics/mamba2_*`` find the kernels by the names their
    jitted wrappers give the ops; the scopes inside ``attention`` carry the
    same names, and neither pattern takes a delta rule's kernel."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from kvbench.harness import names as N

    assert (mamba2_scan.__name__, mamba2_step.__name__) == (
        m2.KERNEL_SCAN, m2.KERNEL_STEP) == ("mamba2_scan", "mamba2_step")
    for reader, own, other in (
            ("mamba2_scan_roofline", "mamba2_scan", "gdn_scan"),
            ("mamba2_step_roofline", "mamba2_step", "kda_step"),
            ("mamba2_step_share", "mamba2_step", "mamba2_scan")):
        pattern = N.metric(reader).KERNEL
        assert re.search(pattern, f"{own}.12")
        assert not re.search(pattern, f"{other}.12")


# -- more than one group of B and C ------------------------------------------
# Heads of 128 channels (one head a tile) in two groups, each with its own
# B and C: head ``h`` reads group ``h // (heads / groups)``.


def grouped(tokens, valid, seed, heads=4, p=128, n=32, groups=2, **kw):
    """``inputs`` with ``B, C [T, groups, N]``, each group its own draw."""
    x, b, c, dt, a, skip, state = inputs(tokens, valid, seed=seed,
                                         heads=heads, p=p, n=n, **kw)
    more = [inputs(tokens, valid, seed=seed + 100 * g, heads=heads, p=p,
                   n=n)[1:3] for g in range(1, groups)]
    b = np.stack([b] + [m[0] for m in more], axis=1)
    c = np.stack([c] + [m[1] for m in more], axis=1)
    return x, b, c, dt, a, skip, state


def grouped_recurrence(x, b, c, dt, a, skip, state):
    """``recurrence`` a group of heads at a time: ``b, c [T, Gr, N]``."""
    heads, groups = x.shape[1], b.shape[1]
    per = heads // groups
    outs, states = [], []
    for g in range(groups):
        of = slice(g * per, (g + 1) * per)
        o, s = recurrence(x[:, of], b[:, g], c[:, g], dt[:, of], a[of],
                          skip[of], state[of])
        outs.append(o)
        states.append(s)
    return (np.concatenate(outs, axis=1),
            [np.concatenate([s[t] for s in states])
             for t in range(x.shape[0])])


@FORMS
def test_the_grouped_scan_is_the_recurrence(kernel):
    """Two groups, one head a tile: the outputs, the end state and the
    requested block's state are the recurrence's, to 1e-4 in float32."""
    x, b, c, dt, a, skip, state = grouped(48, 40, seed=11)
    assert m2.heads_per_tile(4, 128) == 1
    want, states = grouped_recurrence(x, b, c, dt, a, skip, state)
    y, end, snap = mamba2_scan(x, b, c, dt, a, skip, m2.pack_state(state), 1,
                               block=16, kernel=kernel, interpret=True)
    np.testing.assert_allclose(y[:40], want[:40],
                               atol=1e-4 * np.abs(want).max())
    np.testing.assert_allclose(m2.unpack_state(end, 128), states[39],
                               atol=1e-4)
    np.testing.assert_allclose(m2.unpack_state(snap, 128), states[31],
                               atol=1e-4)


def test_the_grouped_kernels_are_their_xla_forms():
    """Pallas (interpreted) against XLA at one head a tile and two groups:
    the scan over three blocks and the step over four rows."""
    x, b, c, dt, a, skip, state = grouped(48, 48, seed=12)
    tiles = m2.pack_state(state)
    for got, want in zip(
            mamba2_scan(x, b, c, dt, a, skip, tiles, 0, block=16,
                        kernel=True, interpret=True),
            mamba2_scan(x, b, c, dt, a, skip, tiles, 0, block=16)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    pool = np.random.default_rng(12).normal(
        size=(2, 6, *tiles.shape)).astype(np.float32)
    slots = np.array([3, 0, 5, 1], np.int32)
    step_dt = dt[:4] * (slots != 0)[:, None]
    for got, want in zip(
            mamba2_step(jnp.asarray(pool), 1, slots, x[:4], b[:4], c[:4],
                        step_dt, a, skip, kernel=True, interpret=True),
            mamba2_step(jnp.asarray(pool), 1, slots, x[:4], b[:4], c[:4],
                        step_dt, a, skip)):
        got, want = np.asarray(got), np.asarray(want)
        if got.ndim == 3:           # a padded row's output is the kernel's 0
            got, want = got[slots != 0], want[slots != 0]
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_grouped_state_kept_in_bfloat16_fails_where_float32_passes():
    x, b, c, dt, a, skip, state = grouped(96, 96, seed=13, fastest=2.0)
    _, states = grouped_recurrence(x, b, c, dt, a, skip, state)

    def chained(keep):
        tiles = m2.pack_state(state)
        for at in range(0, 96, 16):
            _, tiles, _ = mamba2_scan(
                *(v[at:at + 16] for v in (x, b, c, dt)), a, skip, tiles, -1,
                block=16)
            tiles = keep(tiles)
        return float(np.abs(m2.unpack_state(tiles, 128) - states[-1]).max())

    assert chained(lambda t: t) < 1e-4
    assert chained(lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)) > (
        1e-3)


@FORMS
def test_the_grouped_step_is_one_token_of_the_scan(kernel):
    """Each live row's output and state are one token of the recurrence
    and of the grouped scan; the spare slot and the other layer stay."""
    rows, heads, p, n = 4, 4, 128, 32
    x, b, c, dt, a, skip, _ = grouped(rows, 3, seed=14)
    pool = np.random.default_rng(14).normal(
        size=(2, 6, *m2.state_shape(heads, p, n))).astype(np.float32)
    slots = np.array([4, 2, 5, 0], np.int32)
    y, new = mamba2_step(jnp.asarray(pool), 1, slots, x, b, c, dt, a, skip,
                         kernel=kernel, interpret=True)
    for r in range(3):
        before = m2.unpack_state(jnp.asarray(pool[1, slots[r]]), p)
        out, states = grouped_recurrence(x[r:r + 1], b[r:r + 1], c[r:r + 1],
                                         dt[r:r + 1], a, skip, before)
        np.testing.assert_allclose(y[r], out[0], atol=2e-5)
        np.testing.assert_allclose(
            new[1, slots[r]],
            m2.pack_state(jnp.asarray(states[0], np.float32)), atol=2e-5)

        def padded(v):
            return np.concatenate([v[r:r + 1], np.zeros_like(v[:1])
                                   .repeat(15, 0)])
        y_scan, end, _ = mamba2_scan(
            padded(x), padded(b), padded(c), padded(dt), a, skip,
            pool[1, slots[r]], -1, block=16)
        np.testing.assert_allclose(y[r], y_scan[0], atol=2e-5)
        np.testing.assert_allclose(new[1, slots[r]], end, atol=2e-5)
    np.testing.assert_array_equal(new[1, 0], pool[1, 0])
    np.testing.assert_array_equal(new[0], pool[0])


@FORMS
def test_one_group_is_the_call_without_a_group_axis(kernel):
    """``B, C [T, N]``, ``[T, 1, N]`` and two groups that carry the same B
    and C give the same numbers exactly, scan and step."""
    x, b, c, dt, a, skip, state = inputs(32, 32, seed=15, heads=4, p=128,
                                         n=32)
    tiles = m2.pack_state(state)
    pool = jnp.asarray(np.random.default_rng(15).normal(
        size=(1, 5, *tiles.shape)).astype(np.float32))
    slots = np.array([1, 4, 0, 2], np.int32)
    use = dict(kernel=kernel, interpret=True)

    def both(b, c):
        return (*mamba2_scan(x, b, c, dt, a, skip, tiles, 0, block=16,
                             **use),
                *mamba2_step(pool + 0, 0, slots, x[:4], b[:4], c[:4],
                             dt[:4] * (slots != 0)[:, None], a, skip, **use))

    flat = both(b, c)
    for b_g, c_g in ((b[:, None], c[:, None]),
                     (np.stack([b, b], 1), np.stack([c, c], 1))):
        for got, want in zip(both(b_g, c_g), flat):
            np.testing.assert_array_equal(got, want)


def test_a_tile_of_two_groups_is_refused():
    """Two heads of 64 share a tile: they cannot read two groups."""
    x, b, c, dt, a, skip, state = grouped(16, 16, seed=16, heads=2, p=64)
    with pytest.raises(ValueError, match="two groups"):
        mamba2_scan(x, b, c, dt, a, skip, m2.pack_state(state), -1, block=16)
    pool = jnp.zeros((1, 2, *m2.state_shape(2, 64, 32)), jnp.float32)
    with pytest.raises(ValueError, match="two groups"):
        mamba2_step(pool, 0, np.array([1], np.int32), x[:1], b[:1], c[:1],
                    dt[:1], a, skip)
