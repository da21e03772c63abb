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
9 layers chained in one program with the pool carried in place, all 16 rows
live, for a first look.

  chiprun -- python3 hack/bench_mamba2.py       # one v5e, ~1 min
  python3 hack/bench_mamba2.py --rehearse       # the CPU, toy sizes, no times
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def inputs(rng, tokens, heads, p, n):
    """x, B, C as a conv over 4 tokens and a SiLU leave them; the step a
    head log-uniform in [1e-3, 1e-1] times a token's own factor; A in
    [1, 16)."""
    def behind_silu(width):
        raw = rng.normal(size=(tokens + 3, width))
        mixed = sum(0.5 * raw[j:j + tokens] for j in range(4))
        return mixed / (1 + np.exp(-mixed))

    x = behind_silu(heads * p).reshape(tokens, heads, p)
    dt = (np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(1, heads)))
          * rng.uniform(0.5, 4.0, size=(tokens, heads)))
    a = -rng.uniform(1.0, 16.0, size=(heads,))
    return x, behind_silu(n), behind_silu(n), dt, a, np.ones((heads,))


def token_at_a_time(x, b, c, dt, a, skip, state):
    """``state [H, P, N]``: returns (the end state, y [T, H, P])."""
    import jax
    import jax.numpy as jnp

    def token(s, at):
        x_t, b_t, c_t, d_t = at
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", s, c_t, precision="highest")
        return s, y + skip[:, None] * x_t

    return jax.lax.scan(token, state, (x, b, c, dt))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
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
    rng = np.random.default_rng(57)
    f32 = jnp.float32
    x, b, c, dt, a, skip = (jnp.asarray(v, f32)
                            for v in inputs(rng, tokens, heads, p, n))
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
        print(f"mamba2_scan {tokens} x {heads} heads, "
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
    untouched = float(jnp.abs(new.at[1, at].set(0)
                              - pool.at[1, at].set(0)).max())
    print(f"mamba2_step {rows} rows: y {worst_y:.2e} state {worst_s:.2e}; "
          f"every other slot moved by {untouched:.1g}", flush=True)
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

    @jax.jit
    def steps(pool):
        def layer(i, carry):
            pool, y = carry
            y, pool = m2.mamba2_step(pool, i % layers, at,
                                     x[:rows] + y[:1, :1, :1] * 0, b[:rows],
                                     c[:rows], dt[:rows], a, skip,
                                     kernel=True)
            return pool, y
        return jax.lax.fori_loop(0, reps * layers, layer, (pool, x[:rows]))

    pool, _ = jax.block_until_ready(steps(pool))
    t0 = time.perf_counter()
    jax.block_until_ready(steps(pool))
    per = (time.perf_counter() - t0) / reps
    moved = 2 * layers * rows * heads * p * n * 4
    print(f"mamba2_step: {per * 1e3:.3f} ms a decode step's {layers} layers "
          f"of {rows} live rows ({moved / 1e9:.2f} GB of state: "
          f"{moved / per / 1e9:.0f} GB/s; the cell's trace is the record)",
          flush=True)


if __name__ == "__main__":
    main()
