#!/usr/bin/env python3
"""The Mamba-2 recurrence alone, at the published widths of
``granite-4.0-h-small-ep2-l10`` (128 heads of 64 channels over a state of
128, one group): ``mamba2_scan`` over a chunk of 512 tokens and
``mamba2_step`` over 16 rows, each Pallas kernel against the recurrence run
a token at a time in float32, on inputs as the model's own look (x, B and C
behind a conv and a SiLU, so in one orthant with neighbours alike; steps
and decays at the family's initialisation, so that a head loses from e^-0.06
to e^-100 and more over a page); then the scan's time, its calls chained
inside one program (a program of single calls measures their launches).

The step's time of record is the cell's trace (``mamba2_step_roofline``,
``mamba2_step_share``): what this script prints for it is the kernel over
9 layers chained in one program with the (donated) pool carried in place,
at 1, 4 and 16 live rows of the decode shape's 16 (the others name the
spare slot 0, as the engine pads a batch), in GB/s of the live rows' states;
before that, the kernel against the XLA form on the device itself with
spare-slot rows behind, between and before the live ones, and every slot
that no live row names compared bit for bit with what it held.

  chiprun -- python3 hack/bench_mamba2.py       # one v5e, ~1 min
  python3 hack/bench_mamba2.py --rehearse       # the CPU, toy sizes, no times

Another shape by ``--heads``, ``--head-dim``, ``--state``, ``--groups`` (of B
and C; head ``h`` reads group ``h // (heads / groups)``) and ``--rows``:
``falcon-h1-34b-l9``'s is ``--heads 32 --head-dim 128 --state 256 --groups 2
--rows 12`` (one head a tile of ``[256, 128]``).
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def inputs(rng, tokens, heads, p, n, groups=1):
    """x, B, C (``[tokens, groups, n]``) as a conv over 4 tokens and a SiLU
    leave them; the step a head log-uniform in [1e-3, 1e-1] times a token's
    own factor; A in [1, 16)."""
    def behind_silu(width):
        raw = rng.normal(size=(tokens + 3, width))
        mixed = sum(0.5 * raw[j:j + tokens] for j in range(4))
        return mixed / (1 + np.exp(-mixed))

    x = behind_silu(heads * p).reshape(tokens, heads, p)
    dt = (np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(1, heads)))
          * rng.uniform(0.5, 4.0, size=(tokens, heads)))
    a = -rng.uniform(1.0, 16.0, size=(heads,))
    return (x, behind_silu(groups * n).reshape(tokens, groups, n),
            behind_silu(groups * n).reshape(tokens, groups, n), dt, a,
            np.ones((heads,)))


def token_at_a_time(x, b, c, dt, a, skip, state):
    """``state [H, P, N]``, ``b, c [T, groups, N]``: returns (the end state,
    y [T, H, P])."""
    import jax
    import jax.numpy as jnp

    per = x.shape[1] // b.shape[1]                     # heads a group

    def token(s, at):
        x_t, b_t, c_t, d_t = at
        b_t, c_t = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)  # [H, N]
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, c_t, precision="highest")
        return s, y + skip[:, None] * x_t

    return jax.lax.scan(token, state, (x, b, c, dt))


def padded(at, live):
    """Rows' slots as the engine pads a batch: the first ``live`` rows keep
    theirs, the others name the spare slot 0."""
    import jax.numpy as jnp

    return jnp.where(jnp.arange(at.shape[0]) < live, at, 0)


def against_xla_form(name, step, pool, at):
    """``step(pool, layer, slots, salt, kernel=...) -> (out, pool)`` as a
    kernel against its XLA form, on whatever device this is, with rows of
    the spare slot behind, between and before the live ones and with none
    live: the live rows' outputs and states, every other slot bit for bit
    against what it held, and the padded rows' outputs finite."""
    import jax
    import jax.numpy as jnp

    rows = at.shape[0]
    where = jnp.arange(rows)
    for what, slots in (
            ("1 live", padded(at, 1)),
            (f"{rows // 2} live", padded(at, rows // 2)),
            ("every other", jnp.where(where % 2 == 1, at, 0)),
            ("last alone", jnp.where(where == rows - 1, at, 0)),
            ("none", padded(at, 0))):
        live = np.asarray(slots) != 0
        names = np.asarray(slots)[live]
        out, new = jax.block_until_ready(
            step(jnp.copy(pool), 1, slots, 0.0))
        ref, want = step(jnp.copy(pool), 1, slots, 0.0, kernel=False)
        scale = float(jnp.abs(ref).max()) or 1.0
        gap = (float(jnp.abs(out - ref)[live].max()) / scale
               if live.any() else 0.0)
        gap_s = float(jnp.abs(new - want).max()
                      / jnp.abs(want).max())
        rest = np.setdiff1d(np.arange(pool.shape[1]), names)
        same = bool((np.asarray(new[1, rest]).view(np.uint32)
                     == np.asarray(pool[1, rest]).view(np.uint32)).all()
                    and (np.asarray(new[0]).view(np.uint32)
                         == np.asarray(pool[0]).view(np.uint32)).all())
        print(f"{name}, {what} of {rows}: out {gap:.2e} state {gap_s:.2e} "
              f"of the XLA form; every slot no live row names bit-equal "
              f"{same}; padded rows' outputs finite "
              f"{bool(jnp.isfinite(out).all())}", flush=True)
        if not same or gap > 1e-4 or gap_s > 1e-4:
            raise SystemExit(f"{name}: the kernel left the XLA form")


def time_steps(name, step, pool, at, layers, lives, reps=10):
    """The step over ``layers`` layers chained in one program, the donated
    pool carried in place, ``reps`` times a call, at each count of live
    rows: ms a decode step's layers and GB/s of the live rows' states (read
    and written)."""
    import jax

    rows = at.shape[0]
    state_bytes = pool[0, 0].size * pool.dtype.itemsize
    for live in lives:
        slots = padded(at, live)

        def steps(pool):
            def layer(i, carry):
                pool, out = carry
                out, pool = step(pool, i % layers, slots, out[:1, :1, :1] * 0)
                return pool, out
            out0, pool = step(pool, 0, slots, 0.0)
            return jax.lax.fori_loop(0, reps * layers, layer, (pool, out0))

        run = jax.jit(steps, donate_argnums=0)
        pool, _ = jax.block_until_ready(run(pool))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            pool, _ = jax.block_until_ready(run(pool))
            best = min(best, time.perf_counter() - t0)
        per = best / (reps * layers + 1) * layers
        moved = 2 * layers * live * state_bytes
        print(f"{name}: {per * 1e3:.3f} ms a decode step's {layers} layers, "
              f"{live} live rows of {rows} ({moved / 1e9:.3f} GB of live "
              f"state: {moved / per / 1e9:.0f} GB/s)", flush=True)
    return pool


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--head-dim", type=int, default=0)
    ap.add_argument("--state", type=int, default=0)
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.ops import mamba2 as m2

    toy = args.rehearse
    if not toy and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the times are a chip's (--rehearse walks "
                         "the path on the CPU)")
    tokens, heads, p, n, page, rows, layers, slots = (
        (64, 4, 16, 16, 32, 3, 2, 5) if toy
        else (512, 128, 64, 128, 64, 16, 9, 41))
    heads, p, n, rows = (args.heads or heads, args.head_dim or p,
                         args.state or n, args.rows or rows)
    rng = np.random.default_rng(57)
    f32 = jnp.float32
    x, b, c, dt, a, skip = (jnp.asarray(v, f32)
                            for v in inputs(rng, tokens, heads, p, n,
                                            args.groups))
    state = jnp.asarray(rng.normal(size=(heads, p, n)), f32)
    over_page = (dt[:page].sum(0) * a)
    print(f"device {jax.devices()[0].device_kind}; a head's log-decay over "
          f"a page: {float(over_page.max()):.3g} .. "
          f"{float(over_page.min()):.3g}", flush=True)

    def rel(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    want_end, want_y = jax.jit(token_at_a_time)(x, b, c, dt, a, skip, state)
    want_snap, _ = jax.jit(token_at_a_time)(
        x[:2 * page], b[:2 * page], c[:2 * page], dt[:2 * page], a, skip,
        state)
    tiles = m2.pack_state(state)
    for kernel in (True, False):
        y, end, snap = jax.block_until_ready(m2.mamba2_scan(
            x, b, c, dt, a, skip, tiles, jnp.int32(1), block=page,
            kernel=kernel, interpret=toy))
        print(f"mamba2_scan {tokens} x {heads} heads of {p} over a state "
              f"of {n}, {args.groups} group(s), "
              f"{'kernel' if kernel else 'XLA form'}: finite "
              f"{bool(jnp.isfinite(y).all() and jnp.isfinite(end).all())} "
              f"y {rel(y, want_y):.2e} end "
              f"{rel(m2.unpack_state(end, p), want_end):.2e} snap "
              f"{rel(m2.unpack_state(snap, p), want_snap):.2e}", flush=True)

    pool = jnp.asarray(rng.normal(
        size=(layers, slots, *m2.state_shape(heads, p, n))), f32)
    at = jnp.asarray(rng.permutation(np.arange(1, slots))[:rows], jnp.int32)
    before = jax.vmap(lambda t: m2.unpack_state(t, p))(pool[1, at])
    y, new = m2.mamba2_step(jnp.copy(pool), 1, at, x[:rows], b[:rows],
                            c[:rows], dt[:rows], a, skip, kernel=True,
                            interpret=toy)
    worst_y = worst_s = 0.0
    for r in range(rows):
        s_r, y_r = token_at_a_time(x[r:r + 1], b[r:r + 1], c[r:r + 1],
                                   dt[r:r + 1], a, skip, before[r])
        worst_y = max(worst_y, rel(y[r], y_r[0]))
        worst_s = max(worst_s, rel(m2.unpack_state(new[1, at[r]], p), s_r))
    print(f"mamba2_step {rows} rows: y {worst_y:.2e} state {worst_s:.2e}",
          flush=True)

    def step(pool, layer, at, salt, kernel=True):
        live = (at != 0)[:, None]
        return m2.mamba2_step(pool, layer, at, x[:rows] + salt, b[:rows],
                              c[:rows], dt[:rows] * live, a, skip,
                              kernel=kernel, interpret=toy and kernel)

    against_xla_form("mamba2_step", step, pool, at)
    if toy:
        return

    reps = 10

    @jax.jit
    def scans(x, tiles):
        def one(_, carry):
            y, end, _ = m2.mamba2_scan(
                x + carry[0][:1, :1, :1] * 0, b, c, dt, a, skip, carry[1],
                jnp.int32(1), block=page, kernel=True)
            return y, end
        return jax.lax.fori_loop(0, reps, one, (x, tiles))

    jax.block_until_ready(scans(x, tiles))
    t0 = time.perf_counter()
    jax.block_until_ready(scans(x, tiles))
    per = (time.perf_counter() - t0) / reps
    print(f"mamba2_scan: {per * 1e3:.3f} ms a layer's chunk of {tokens} "
          f"({reps} chained in one program)", flush=True)
    time_steps("mamba2_step", step, pool, at, layers, (1, 4, rows))


if __name__ == "__main__":
    main()
