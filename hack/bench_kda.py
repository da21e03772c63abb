#!/usr/bin/env python3
"""The channel-wise recurrence alone, at the published widths of
``solar-open2-ep16-l8`` (64 heads of 128 x 128): ``kda_scan`` over a chunk
of 512 tokens and ``kda_step`` over 8 rows, each Pallas kernel against the
recurrence run a token at a time in float32, on keys that lie in one
orthant with neighbours nearly parallel (what a conv and a SiLU leave),
``beta`` in (1, 2) and decays that take a channel from e^0 to e^-50 and
below over a page; both steps against their XLA forms on the device itself
with rows of the spare slot behind, between and before the live ones
(``bench_mamba2.against_xla_form``); then each kernel's time and the scalar
form's on the same shapes: a scan's single calls, and the steps over the
cell's 6 linear layers chained in one program with the donated pool carried
in place, at 1, 2 and 8 live rows of the decode shape's 8, in GB/s of the
live rows' states (``bench_mamba2.time_steps``).

  chiprun -- python3 hack/bench_kda.py       # one v5e, ~1 min
  python3 hack/bench_kda.py --rehearse       # the CPU, toy sizes, no times
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def inputs(rng, tokens, heads, dk, dv, page):
    # Neighbouring keys nearly parallel (a conv over 4 tokens and a SiLU
    # leave them so) and beta up to 2: the block's triangular system at
    # its worst.
    k = (np.abs(rng.normal(size=(1, heads, dk)))
         + 0.05 * rng.normal(size=(tokens, heads, dk)))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(tokens, heads, dk))
    q *= dk ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    v = rng.normal(size=(tokens, heads, dv))
    # A channel's rate: its log-decay over a page runs from -0.006 to -100.
    rate = np.exp(rng.uniform(np.log(1e-4), np.log(100 / page),
                              size=(1, heads, dk)))
    g = -rate * rng.uniform(0.5, 1.5, size=(tokens, heads, dk))
    beta = rng.uniform(1.0, 2.0, size=(tokens, heads))
    return q, k, v, g, beta


def token_at_a_time(q, k, v, g, beta, state):
    import jax
    import jax.numpy as jnp

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        read = functools.partial(jnp.einsum, "hkv,hk->hv",
                                 precision="highest")
        s = s + k_t[:, :, None] * (
            b_t[:, None] * (v_t - read(s, k_t)))[:, None, :]
        return s, read(s, q_t)

    return jax.lax.scan(token, state, (q, k, v, g, beta))


def products(fn, *shapes):
    """``(count, MFLOP at one pass)`` of the ``dot_general``s in ``fn``'s
    jaxpr at float32 operands of ``shapes``."""
    import jax
    import jax.numpy as jnp

    def walk(jaxpr):
        for e in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)
            if e.primitive.name == "dot_general":
                (contract, _), _ = e.params["dimension_numbers"]
                lhs = e.invars[0].aval.shape
                yield 2e-6 * np.prod(e.outvars[0].aval.shape) * np.prod(
                    [lhs[i] for i in contract])

    flops = list(walk(jax.make_jaxpr(fn)(*(
        jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)).jaxpr))
    return len(flops), sum(flops)


def print_products(c, d) -> None:
    """A block's matrix products, counted from the jaxprs: what the
    kernel's time follows (every one of them runs in so many bfloat16
    passes: 6 at the highest precision)."""
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.ops import gated_deltanet as gd

    tok, ch, sq = (c, d), (1, c), (c, c)
    kda = products(gd._kda_block_update, tok, tok, tok, tok, ch, (d, d))
    pairs = products(gd._kda_pairs, (2 * c, d), tok, tok)

    def invert(n):
        row = jax.lax.broadcasted_iota(jnp.int32, sq, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, sq, 1)
        return gd._nested_unit_lower_inverse(
            n, (row == col).astype(jnp.float32), row, col)

    inverse = products(invert, sq)
    scalar = products(gd._block_update, tok, tok, tok, ch, ch, (d, d))
    print(f"products of one block of one head ({c} tokens, dk = dv = {d}; "
          "count, MFLOP at one pass):")
    for name, (count, mflop) in (
            ("_kda_pairs", pairs), ("_nested_unit_lower_inverse", inverse),
            ("u, w and the state's", tuple(
                a - b - i for a, b, i in zip(kda, pairs, inverse))),
            ("the channel-wise block", kda), ("the scalar form's", scalar)):
        print(f"  {name}: {count}, {mflop:.1f}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.ops.gated_deltanet import kda_scan, kda_step

    toy = args.rehearse
    if not toy and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the times are a chip's (--rehearse walks "
                         "the path on the CPU)")
    tokens, heads, d, page, rows, layers, slots = (
        (64, 2, 32, 32, 3, 2, 5) if toy else (512, 64, 128, 64, 8, 6, 41))
    rng = np.random.default_rng(48)
    f32 = jnp.float32
    q, k, v, g, beta = (jnp.asarray(a, f32)
                        for a in inputs(rng, tokens, heads, d, d, page))
    state = jnp.asarray(rng.normal(size=(heads, d, d)), f32)
    print(f"device {jax.devices()[0].device_kind}; a channel's log-decay "
          f"over a page: {float(g[:page].sum(0).max()):.3g} .. "
          f"{float(g[:page].sum(0).min()):.3g}", flush=True)

    print_products(page, d)
    want_end, want_o = jax.jit(token_at_a_time)(q, k, v, g, beta, state)
    want_snap, _ = jax.jit(token_at_a_time)(
        q[:2 * page], k[:2 * page], v[:2 * page], g[:2 * page],
        beta[:2 * page], state) if tokens >= 2 * page else (None, None)
    scan = lambda: kda_scan(q, k, v, g, beta, state, jnp.int32(1),
                            block=page, kernel=True, interpret=toy)
    o, end, snap = jax.block_until_ready(scan())

    def rel(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    print(f"kda_scan {tokens} x {heads} heads: finite "
          f"{bool(jnp.isfinite(o).all() and jnp.isfinite(end).all())} "
          f"o {rel(o, want_o):.2e} end {rel(end, want_end):.2e}"
          + (f" snap {rel(snap, want_snap):.2e}" if want_snap is not None
             else ""), flush=True)

    if not toy:
        # The same blocked algorithm through the compiler's own float32
        # matmuls: what the kernel's arithmetic adds is the difference.
        o, end, snap = jax.block_until_ready(kda_scan(
            q, k, v, g, beta, state, jnp.int32(1), block=page))
        print(f"kda_scan, XLA form: o {rel(o, want_o):.2e} end "
              f"{rel(end, want_end):.2e}", flush=True)
    pool = jnp.asarray(rng.normal(size=(layers, slots, heads, d, d)), f32)
    at = jnp.asarray(rng.permutation(np.arange(1, slots))[:rows], jnp.int32)
    before = pool[1, at]
    step = lambda p: kda_step(p, 1, at, q[:rows], k[:rows], v[:rows],
                              g[:rows], beta[:rows], kernel=True,
                              interpret=toy)
    o1, pool = jax.block_until_ready(step(pool))
    want = [token_at_a_time(q[r:r + 1], k[r:r + 1], v[r:r + 1], g[r:r + 1],
                            beta[r:r + 1], before[r]) for r in range(rows)]
    print(f"kda_step {rows} rows: o "
          f"{max(rel(o1[r], want[r][1][0]) for r in range(rows)):.2e} state "
          f"{max(rel(pool[1, at[r]], want[r][0]) for r in range(rows)):.2e}",
          flush=True)
    from bench_mamba2 import against_xla_form, time_steps
    from llmd_kv_cache_tpu.ops.gated_deltanet import gdn_scan, gdn_step

    g1 = g[..., 0]

    def channelwise(pool, layer, at, salt, kernel=True):
        live = (at != 0)[:, None]
        return kda_step(pool, layer, at, q[:rows] + salt, k[:rows], v[:rows],
                        g[:rows] * live[..., None], beta[:rows] * live,
                        kernel=kernel, interpret=toy and kernel)

    def scalar(pool, layer, at, salt, kernel=True):
        live = (at != 0)[:, None]
        return gdn_step(pool, layer, at, q[:rows] + salt, k[:rows], v[:rows],
                        g1[:rows] * live, beta[:rows] / 2 * live,
                        kernel=kernel, interpret=toy and kernel)

    against_xla_form("kda_step", channelwise, pool, at)
    against_xla_form("gdn_step", scalar, pool, at)
    if toy:
        return
    t0 = time.perf_counter()
    for _ in range(20):
        out = scan()
    jax.block_until_ready(out)
    print(f"kda_scan: {(time.perf_counter() - t0) / 20 * 1e3:.3f} ms a "
          f"layer's chunk of {tokens}", flush=True)
    # The scalar form on the same shapes (one decay a head), for scale.
    scalar_scan = lambda: gdn_scan(q, k, v, g1, beta / 2, state, jnp.int32(1),
                                   block=page, kernel=True)
    jax.block_until_ready(scalar_scan())
    t0 = time.perf_counter()
    for _ in range(20):
        out = scalar_scan()
    jax.block_until_ready(out)
    print(f"gdn_scan: {(time.perf_counter() - t0) / 20 * 1e3:.3f} ms",
          flush=True)
    # The steps over the linear layers of a decode program, the pool
    # carried in place: 1, 2 and 8 live rows of the decode shape's 8.
    pool = time_steps("kda_step", channelwise, pool, at, layers,
                      (1, 2, rows))
    time_steps("gdn_step", scalar, pool, at, layers, (1, 2, rows))


if __name__ == "__main__":
    main()
