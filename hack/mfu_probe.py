#!/usr/bin/env python
"""MFU ground-truth probe for the bench's production-shaped prefill.

Times each suspect component of the 0.9B/4k cold prefill on the real
device, excluding dispatch latency (async dispatch of K calls, one final
sync; the per-call wall clock is the steady-state device time once the
queue is primed). Prints a breakdown so optimization targets are
profile-backed, not guessed (VERDICT r2, weak #1).

Usage (on a TPU v5e; the percentages are shares of that chip's peaks):
  python hack/mfu_probe.py [--decode|--moe|--mla|--burst|--fp8|--big]
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from llmd_kv_cache_tpu.models.llama import (
    LlamaConfig, forward, forward_prefill_pallas, fuse_params, init_kv_cache,
    init_params,
)
from llmd_kv_cache_tpu.ops.paged_attention import paged_attention
from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
    pallas_paged_decode_attention, pallas_paged_prefill_attention,
)
from llmd_kv_cache_tpu.ops.kv_pages import scatter_kv_pages

# The bench's TPU sizing (bench.py main()).
CFG = LlamaConfig(
    vocab_size=32000, hidden_size=2048, num_layers=16,
    num_heads=16, num_kv_heads=8, head_dim=128,
    intermediate_size=5632, page_size=16,
)
CHUNK = 2048
PAGES_PER_SEQ = 272
NUM_PAGES = 1024


def _sync(out):
    """Wait for completion, not for the enqueue: fetch a scalar derived
    from every output leaf."""
    leaves = jax.tree_util.tree_leaves(out)
    s = sum(jnp.sum(jnp.ravel(l)[:1].astype(jnp.float32)) for l in leaves)
    return float(s)


def timed(label, fn, *args, iters=8, flops=None, **kw):
    """Compile, then time `iters` back-to-back dispatches + one value sync."""
    out = fn(*args, **kw)
    _sync(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    _sync(out)
    dt = (time.perf_counter() - start) / iters
    note = ""
    if flops:
        note = f"  {flops / dt / 1e12:.1f} TFLOP/s ({flops / dt / 197e12 * 100:.1f}% of v5e peak)"
    print(f"{label:<44s} {dt * 1e3:8.2f} ms{note}", flush=True)
    return dt


def timed_threaded(label, fn, state, iters=8, flops=None):
    """Like timed, for fns that thread donated state: fn(state) -> state."""
    state = fn(state)
    _sync(state)
    start = time.perf_counter()
    for _ in range(iters):
        state = fn(state)
    _sync(state)
    dt = (time.perf_counter() - start) / iters
    note = ""
    if flops:
        note = f"  {flops / dt / 1e12:.1f} TFLOP/s ({flops / dt / 197e12 * 100:.1f}% of v5e peak)"
    print(f"{label:<44s} {dt * 1e3:8.2f} ms{note}", flush=True)
    return dt


def timed_scanned(op, operand, *big_operands, reps=16, iters=4):
    """Steady-state seconds per op via a jit'd ``lax.scan`` of ``reps``
    applications with a carry-dependent operand (defeats CSE/hoisting;
    the multiplier casts back to the operand dtype so the timed op runs
    the production bf16 path). One definition for every in-jit probe so
    the methodology cannot drift between stages (review r5).

    Any large array (KV caches, expert weights) MUST ride in
    ``big_operands`` — ``op`` receives them as extra positional args.
    Closure-captured concrete arrays become jaxpr constants baked into
    the program: slow to compile, and a second copy in HBM."""
    @jax.jit
    def scanned(x, *rest):
        def body(c, _):
            o = op(x * (1 + c * 0).astype(x.dtype), *rest)
            return o.ravel()[0].astype(jnp.float32), None
        out, _ = jax.lax.scan(body, jnp.float32(0), None, length=reps)
        return out

    out = scanned(operand, *big_operands)
    _sync(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = scanned(operand, *big_operands)
    _sync(out)
    return (time.perf_counter() - start) / iters / reps


def timed_chunked_prefill(label, fwd, cfg, params, table, full_tokens,
                          num_pages, flops, iters, chunk=CHUNK):
    """Time the engine-style chunked 4k prefill (2 chunks scanned inside
    one jit, caches threaded through donated state) for any forward fn
    and config — shared by the bench-sized and --big stages so the
    chunking/sync methodology cannot drift between them."""
    n_chunks = full_tokens.shape[1] // chunk

    @jax.jit
    def prefill_chunked(params, k, v, tokens):
        def body(carry, i):
            k, v = carry
            chunk_toks = jax.lax.dynamic_slice(
                tokens, (0, i * chunk), (1, chunk))
            logits, k, v = fwd(
                params, cfg, chunk_toks, k, v, table,
                (i * chunk)[None].astype(jnp.int32),
                jnp.asarray([chunk], jnp.int32), last_only=True)
            return (k, v), logits[0, 0, 0]
        (k, v), ls = jax.lax.scan(body, (k, v),
                                  jnp.arange(n_chunks, dtype=jnp.int32))
        return k, v, ls

    k_cache, v_cache = init_kv_cache(cfg, num_pages)

    def step(state):
        k, v = state
        k, v, _ = prefill_chunked(params, k, v, full_tokens)
        return (k, v)

    timed_threaded(label, step, (k_cache, v_cache), iters=iters,
                   flops=flops)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev} ({dev.platform})", flush=True)
    rng = np.random.default_rng(0)

    # --- host<->device roundtrip: fetch a ready scalar ---
    z = jnp.float32(1.0) + 1.0
    _sync(z)
    start = time.perf_counter()
    for _ in range(8):
        _sync(z)
    print(f"{'value-fetch roundtrip':<44s} "
          f"{(time.perf_counter() - start) / 8 * 1e3:8.2f} ms", flush=True)

    # --- roofline probe: plain big bf16 matmul ---
    a = jnp.asarray(rng.normal(size=(4096, 2048)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(2048, 5632)), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    timed("roofline bf16 matmul 4096x2048x5632", mm, a, b,
          flops=2 * 4096 * 2048 * 5632)

    f32a = a.astype(jnp.float32)
    f32b = b.astype(jnp.float32)
    timed("same matmul fp32", mm, f32a, f32b, flops=2 * 4096 * 2048 * 5632)

    # --- full forward step, one 2048-token chunk (both backends) ---
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.asarray(rng.integers(1, 30000, (1, CHUNK)), jnp.int32)
    table = jnp.asarray(np.arange(1, 1 + PAGES_PER_SEQ, dtype=np.int32))[None, :]
    ctx = jnp.asarray([2048], jnp.int32)   # second chunk of the 4k prefill
    new = jnp.asarray([CHUNK], jnp.int32)

    # FLOPs for one chunk: 2*P_nonembed*T matmuls + attention (causal,
    # ctx 2048 before it).
    p_nonembed = (CFG.num_layers * (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                                    + 3 * 2048 * 5632) + 2048 * 32000)
    attn_flops = CFG.num_layers * 4 * CHUNK * (2048 + CHUNK / 2) * 2048
    chunk_flops = 2 * p_nonembed * CHUNK + attn_flops
    print(f"chunk FLOPs: {chunk_flops / 1e12:.2f} TFLOP "
          f"(matmul {2 * p_nonembed * CHUNK / 1e12:.2f}, attn {attn_flops / 1e12:.2f})",
          flush=True)

    k_cache, v_cache = init_kv_cache(CFG, NUM_PAGES)

    def xla_step(state):
        k, v = state
        logits, k, v = forward(params, CFG, tokens, k, v, table, ctx, new)
        return (k, v)

    timed_threaded("forward XLA-attn chunk 2048 (ctx 2048)",
                   xla_step, (k_cache, v_cache), flops=chunk_flops)

    k_cache, v_cache = init_kv_cache(CFG, NUM_PAGES)

    def pallas_step(state):
        k, v = state
        logits, k, v = forward_prefill_pallas(
            params, CFG, tokens, k, v, table, ctx, new)
        return (k, v)

    timed_threaded("forward Pallas-prefill chunk 2048",
                   pallas_step, (k_cache, v_cache), flops=chunk_flops)

    # --- attention op alone ---
    q = jnp.asarray(rng.normal(size=(1, CHUNK, 16, 128)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(NUM_PAGES, 8, 16, 128)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NUM_PAGES, 8, 16, 128)), jnp.bfloat16)
    qpos = ctx[:, None] + jnp.arange(CHUNK)[None, :]
    tot = ctx + CHUNK
    per_layer_attn = 4 * CHUNK * (2048 + CHUNK / 2) * 2048
    xattn = jax.jit(lambda *a: paged_attention(*a))
    timed("paged_attention (XLA) one layer", xattn, q, kc, vc, table, qpos, tot,
          flops=per_layer_attn)
    timed("pallas prefill attention one layer",
          lambda *a: pallas_paged_prefill_attention(*a, q_tile=16),
          q, kc, vc, table, ctx, tot, flops=per_layer_attn)

    # --- scatter alone ---
    newkv = jnp.asarray(rng.normal(size=(1, CHUNK, 8, 128)), jnp.bfloat16)
    valid = jnp.ones((1, CHUNK), bool)
    sc = jax.jit(lambda c, n: scatter_kv_pages(c, n, table, qpos, valid))
    timed("scatter_kv_pages one layer (2048 tok)", sc, kc, newkv)

    # --- lm_head over the full chunk vs one row ---
    x = jnp.asarray(rng.normal(size=(1, CHUNK, 2048)), jnp.bfloat16)
    lm = params["lm_head"]
    timed("lm_head full chunk (2048x32000)",
          jax.jit(lambda x, w: (x @ w).astype(jnp.float32)), x, lm,
          flops=2 * CHUNK * 2048 * 32000)
    timed("lm_head last row only",
          jax.jit(lambda x, w: (x[:, -1] @ w).astype(jnp.float32)), x, lm)

    # --- in-jit measurements (dispatch excluded): scan N reps inside one
    # program so the per-call dispatch floor amortizes away ---
    reps = 16

    @jax.jit
    def mm_scan(a, b):
        def body(c, _):
            # defeat CSE/hoisting: operand depends on the carry
            return (a * (1 + c[0, 0] * 0)) @ b, None
        out, _ = jax.lax.scan(body, a @ b, None, length=reps)
        return out[0, 0]

    timed("roofline bf16 matmul, in-jit x16", mm_scan, a, b, iters=4,
          flops=reps * 2 * 4096 * 2048 * 5632)

    # Full 4096-token prefill: scan over 2 chunks of 2048 inside ONE jit —
    # the engine's chunked prefill with the dispatch boundary removed.
    full_tokens = jnp.asarray(rng.integers(1, 30000, (1, 4096)), jnp.int32)
    prefill_flops = (2 * p_nonembed * 4096
                     + CFG.num_layers * 4 * (4096 ** 2 / 2) * 2048)

    for fwd, label in ((forward, "4096-tok prefill, 2x2048 chunks in-jit"),
                       (forward_prefill_pallas,
                        "same, flash prefill (engine default, unfused)")):
        timed_chunked_prefill(label, fwd, CFG, params, table, full_tokens,
                              NUM_PAGES, prefill_flops, iters=4)
    # Fused QKV/gate+up variant: at this hidden-2048 shape it measured
    # ~8% SLOWER on the v5e, which is why llama.fuse_profitable gates
    # the engine's auto default OFF here (fused is the default only at
    # hidden >= 4096 — see --big). Kept in the probe to re-check the
    # crossover whenever kernels or XLA change.
    timed_chunked_prefill(
        "same, flash + fused QKV/gateup (off by default)",
        forward_prefill_pallas, CFG, fuse_params(params, CFG), table,
        full_tokens, NUM_PAGES, prefill_flops, iters=4)

    # Same, single 4096-token chunk (no scan): the chunking overhead bound.
    table_full = table

    @jax.jit
    def prefill_one(params, k, v, tokens):
        logits, k, v = forward(
            params, CFG, tokens, k, v, table_full,
            jnp.asarray([0], jnp.int32), jnp.asarray([4096], jnp.int32),
            last_only=True)
        return k, v, logits[0, 0, 0]

    k_cache, v_cache = init_kv_cache(CFG, NUM_PAGES)

    def prefill_one_step(state):
        k, v = state
        k, v, _ = prefill_one(params, k, v, full_tokens)
        return (k, v)

    timed_threaded("4096-tok prefill, single chunk in-jit",
                   prefill_one_step, (k_cache, v_cache), iters=4,
                   flops=prefill_flops)

    # --- per-layer attention, in-jit (the single-dispatch measurements
    # above include the dispatch floor, so the op is scanned REPS× inside
    # one program with a carry dependence defeating CSE). ---
    attn_reps = 16

    def op_injit(label, fn, q_op, flops, unit, iters=4):
        """Time fn(q_like, kc, vc) scanned attn_reps× inside one jit.

        The carry dependence defeats CSE/hoisting; the multiplier is cast
        back to the query dtype so the timed op runs the production bf16
        path (an f32 carry would silently promote q to fp32 — off the
        bf16 MXU fast path)."""
        @jax.jit
        def scanned(q_op, kc, vc):
            def body(c, _):
                o = fn(q_op * (1 + c * 0).astype(q_op.dtype), kc, vc)
                return o.ravel()[0].astype(jnp.float32), None
            out, _ = jax.lax.scan(body, jnp.float32(0), None,
                                  length=attn_reps)
            return out
        out = scanned(q_op, kc, vc)
        _sync(out)
        start = time.perf_counter()
        for _ in range(iters):
            out = scanned(q_op, kc, vc)
        _sync(out)
        dt = (time.perf_counter() - start) / iters / attn_reps
        print(f"{label:<44s} {dt * 1e3:8.2f} {unit}  "
              f"{flops / dt / 1e12:.1f} TFLOP/s "
              f"({flops / dt / 197e12 * 100:.1f}% of v5e peak)",
              flush=True)

    def attn_injit(label, fn):
        op_injit(label, fn, q, per_layer_attn, "ms/layer")

    attn_injit("XLA paged_attention in-jit x16",
               lambda q, kc, vc: paged_attention(q, kc, vc, table, qpos, tot))
    # q_tile × keys-per-round sweep around the engine default
    # (group·q_tile ≈ 1024 rows, ~1024 keys per online-softmax round —
    # the measured optimum; see forward_prefill_pallas).
    for q_tile in (128, 256, 512, 1024):
        for kpb in (8, 32, 64):
            try:
                attn_injit(
                    f"flash prefill q_tile={q_tile:<4d} kpb={kpb:<2d} in-jit",
                    lambda q, kc, vc, qt=q_tile, kb=kpb:
                    pallas_paged_prefill_attention(
                        q, kc, vc, table, ctx, tot, q_tile=qt,
                        pages_per_block=kb))
            except Exception as e:  # Mosaic rejection at an extreme point
                print(f"flash prefill q_tile={q_tile} kpb={kpb}: "
                      f"{type(e).__name__}: {str(e)[:120]}", flush=True)

    # Flash-decode superblock sweep at long context (batch 8, ctx 4096),
    # in-jit for the same reason (decode steps are ~100 µs — far below
    # the dispatch floor).
    qd = jnp.asarray(rng.normal(size=(8, 16, 128)), jnp.bfloat16)
    table8 = jnp.asarray(
        1 + np.arange(8 * PAGES_PER_SEQ).reshape(8, PAGES_PER_SEQ) %
        (NUM_PAGES - 1), jnp.int32)
    lens8 = jnp.full((8,), 4096, jnp.int32)
    dec_flops = 8 * 4 * 4096 * 16 * 128

    for kpb in (4, 8, 16, 32):
        try:
            op_injit(f"flash decode kpb={kpb:<2d} (b8, ctx 4k) in-jit",
                     lambda qd, kc, vc, kb=kpb: pallas_paged_decode_attention(
                         qd, kc, vc, table8, lens8, pages_per_block=kb),
                     qd, dec_flops, "ms/step ")
        except Exception as e:  # Mosaic rejection at an extreme point
            print(f"flash decode kpb={kpb}: "
                  f"{type(e).__name__}: {str(e)[:120]}", flush=True)


def main_decode():
    """Decode-bandwidth sweep (`--decode`, VERDICT r5 #1): the merged
    flash-decode kernel across batch_rows (rows co-scheduled per
    program) × keys-per-round, at b8/b32 × ctx 2k/4k — ms/step,
    effective KV GB/s, and % of the ~819 GB/s v5e HBM roofline. KV bytes
    per step = b · ctx · kvh · hd · 2 streams · itemsize; the weights
    are not in this op, so the number isolates the attention stream."""
    import sys

    rng = np.random.default_rng(0)
    kvh, hd, ps = 8, 128, 16  # kv_heads, head_dim, page size
    num_pages = 16 * 1024 + 1
    kc = jnp.asarray(rng.normal(size=(num_pages, kvh, ps, hd)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(num_pages, kvh, ps, hd)), jnp.bfloat16)

    def run(batch, ctx, rows, kpb):
        q = jnp.asarray(rng.normal(size=(batch, 16, hd)), jnp.bfloat16)
        pages_per_seq = ctx // ps
        table = jnp.asarray(
            1 + (np.arange(batch * pages_per_seq, dtype=np.int64)
                 * 2654435761 % (num_pages - 1)).reshape(
                     batch, pages_per_seq).astype(np.int32))
        lens = jnp.full((batch,), ctx, jnp.int32)
        kv_bytes = batch * ctx * kvh * hd * 2 * 2
        dt = timed_scanned(
            lambda q_op, kc_op, vc_op: pallas_paged_decode_attention(
                q_op, kc_op, vc_op, table, lens, pages_per_block=kpb,
                batch_rows=rows),
            q, kc, vc)
        gbs = kv_bytes / dt / 1e9
        print(f"decode b{batch:<3d} ctx{ctx:<5d} rows={rows:<2d} "
              f"kpb={'auto' if kpb is None else kpb:<4} "
              f"{dt * 1e3:8.3f} ms/step  {gbs:7.1f} GB/s eff "
              f"({gbs / 819 * 100:5.1f}% of v5e HBM)", flush=True)

    # Optional shape filter ("b8x4096"): ~20 fresh kernel compiles per
    # shape, so one call to the chip can take one shape.
    only = next((a for a in sys.argv[1:] if a.startswith("b")), None)
    for batch, ctx in ((8, 4096), (8, 2048), (32, 2048), (32, 4096)):
        if only and only != f"b{batch}x{ctx}":
            continue
        for rows in (1, 2, 4, 8):
            if rows > batch:
                continue
            for kpb in (None, 8, 16, 32, 64):
                try:
                    run(batch, ctx, rows, kpb)
                except Exception as e:
                    print(f"decode b{batch} ctx{ctx} rows={rows} kpb={kpb}: "
                          f"{type(e).__name__}: {str(e)[:110]}", flush=True)


def main_moe():
    """MoE expert-dispatch probe (`--moe`, VERDICT r5 #5a): time the
    capacity-dispatch einsum path at Qwen3-MoE-A3B-like and
    Mixtral-like shapes against (a) a dense MLP doing the same ACTIVE
    FLOPs (dispatch overhead bound) and (b) the all-expert weight-read
    byte roofline (at low tokens/expert the expert matmuls are
    bandwidth-bound on reading every expert's weights, not FLOPs)."""
    import contextlib
    import signal

    from llmd_kv_cache_tpu.models.llama import _mlp

    @contextlib.contextmanager
    def deadline(seconds, label):
        """Per-point watchdog: one pathological remote compile must not
        consume the whole ladder stage (the first qwen3-moe attempt ate
        its full 1200 s box compiling and nothing else ran)."""
        def _raise(signum, frame):
            raise TimeoutError(f"{label}: exceeded {seconds}s")
        old = signal.signal(signal.SIGALRM, _raise)
        signal.alarm(seconds)
        try:
            yield
        except Exception as exc:  # noqa: BLE001 — probe must keep going
            print(f"{label}: {type(exc).__name__}: {str(exc)[:140]}",
                  flush=True)
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)

    rng = np.random.default_rng(0)
    shapes = {
        # (hidden, inter_per_expert, experts, top_k) — few-expert shape
        # first: it compiles in seconds, so a blowup in the many-expert
        # compile still leaves committed numbers.
        "mixtral-8x7b-ish": (4096, 14336, 8, 2),
        "qwen3-moe-a3b": (2048, 768, 128, 8),
    }
    tokens = 2048
    for name, (h, inter, e, k) in shapes.items():
        # capacity_factor pinned to 1.0: at the default 2.0 the expert
        # einsums do 2x the active FLOPs, and the dense-baseline ratio
        # would conflate that extra compute with dispatch cost
        # (review r5). The default-capacity point is printed separately.
        cfgs = {
            1.0: LlamaConfig(
                vocab_size=32000, hidden_size=h, num_layers=1,
                num_heads=16, num_kv_heads=8, head_dim=128,
                intermediate_size=inter, num_experts=e,
                num_experts_per_token=k, moe_intermediate_size=inter,
                moe_capacity_factor=1.0, page_size=16),
            2.0: LlamaConfig(
                vocab_size=32000, hidden_size=h, num_layers=1,
                num_heads=16, num_kv_heads=8, head_dim=128,
                intermediate_size=inter, num_experts=e,
                num_experts_per_token=k, moe_intermediate_size=inter,
                moe_capacity_factor=2.0, page_size=16),
        }
        params = init_params(jax.random.PRNGKey(0), cfgs[1.0])
        layer = params["layers"][0]
        x = jnp.asarray(rng.normal(size=(1, tokens, h)), jnp.bfloat16)
        active_flops = 2 * tokens * k * 3 * h * inter
        w_bytes = e * 3 * h * inter * 2  # every expert's weights, bf16

        dts = {}
        for cf, cfg in cfgs.items():
            with deadline(420, f"moe {name} cf={cf}"):
                dts[cf] = timed_scanned(
                    lambda x_op, layer_op, cfg=cfg: _mlp(x_op, layer_op, cfg),
                    x, layer, reps=8)
        if 1.0 in dts:
            dt = dts[1.0]
            print(f"moe {name:<18s} {tokens} tok cf=1: {dt * 1e3:8.2f} ms  "
                  f"{active_flops / dt / 1e12:6.1f} TFLOP/s active "
                  f"({active_flops / dt / 197e12 * 100:4.1f}% peak)  "
                  f"weight-read roofline {w_bytes / 819e9 * 1e3:.2f} ms "
                  f"({w_bytes / dt / 1e9:.0f} GB/s eff)", flush=True)
        if 2.0 in dts:
            print(f"    cf=2 (engine default):         "
                  f"{dts[2.0] * 1e3:8.2f} ms", flush=True)

        # Dense MLP at the same ACTIVE shape: k experts' worth of inter.
        dcfg = LlamaConfig(
            vocab_size=32000, hidden_size=h, num_layers=1, num_heads=16,
            num_kv_heads=8, head_dim=128, intermediate_size=inter * k,
            page_size=16)
        dparams = init_params(jax.random.PRNGKey(0), dcfg)
        dlayer = dparams["layers"][0]
        with deadline(420, f"moe {name} dense-baseline"):
            ddt = timed_scanned(
                lambda x_op, dlayer_op: _mlp(x_op, dlayer_op, dcfg),
                x, dlayer, reps=8)
            if 1.0 in dts:
                print(f"    dense same-active-FLOPs MLP:   {ddt * 1e3:8.2f} ms"
                      f"  (dispatch overhead {dts[1.0] / ddt:.2f}x at cf=1)",
                      flush=True)
            else:
                print(f"    dense same-active-FLOPs MLP:   {ddt * 1e3:8.2f} ms",
                      flush=True)


def main_mla():
    """MLA flash-decode probe (`--mla`, VERDICT r5 #5b): DeepSeek
    latent-576 shapes (512 rank + 64 rope, latent_pad 64 → 640 kernel
    width), single-stream (shared_kv: V DMA skipped) vs two-stream —
    the measured check on the 'half the latent HBM traffic' claim."""
    rng = np.random.default_rng(0)
    width, ps = 640, 16  # padded latent width, page size
    num_pages = 8 * 1024 + 1
    latent = jnp.asarray(rng.normal(size=(num_pages, 1, ps, width)),
                         jnp.bfloat16)
    for batch, ctx in ((8, 4096), (32, 2048)):
        q = jnp.asarray(rng.normal(size=(batch, 16, width)), jnp.bfloat16)
        pps = ctx // ps
        table = jnp.asarray(
            1 + (np.arange(batch * pps, dtype=np.int64) * 2654435761
                 % (num_pages - 1)).reshape(batch, pps).astype(np.int32))
        lens = jnp.full((batch,), ctx, jnp.int32)
        # Three latent feeds: reuse = one HBM read, one buffer aliased
        # into both matmuls (r5 probe measured it 2x slower at b8/4k —
        # the one buffer serves a head_dim-contraction AND a
        # key-contraction, forcing per-round relayouts); copy = one HBM
        # read + local VMEM mirror (the fix: engine default); dual = two
        # HBM reads of the same pages (what a non-shared cache would do).
        variants = (("single/reuse", dict(shared_kv=True,
                                          shared_stream="reuse"), 1),
                    ("single/copy ", dict(shared_kv=True,
                                          shared_stream="copy"), 1),
                    ("dual-stream ", dict(shared_kv=False), 2))
        for name, kw, streams in variants:
            kv_bytes = batch * ctx * width * streams * 2
            dt = timed_scanned(
                lambda q_op, lat_op, kw=kw: pallas_paged_decode_attention(
                    q_op, lat_op, lat_op, table, lens, **kw),
                q, latent)
            print(f"mla decode b{batch:<3d} ctx{ctx:<5d} "
                  f"{name} "
                  f"{dt * 1e3:8.3f} ms/step  "
                  f"{kv_bytes / dt / 1e9:7.1f} GB/s eff", flush=True)


def main_burst():
    """Fused-burst decomposition (`--burst`): the engine's b32/ctx2048
    decode measured 17 ms/step end-to-end (hack/decode_batch_sweep) while
    the kernel-level sweeps predict ~5 ms (1.6 ms attention + ~3 ms
    weight reads at measured GB/s). Time `forward_decode_steps` — the
    exact burst program the engine dispatches — in isolation at the
    sweep's shapes to split program cost from engine/dispatch overhead,
    across backends and batch, plus a no-tail single-step scan as the
    floor."""
    from llmd_kv_cache_tpu.models.llama import (forward_decode_pallas,
                                                forward_decode_steps)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=16,
                      num_heads=16, num_kv_heads=8, head_dim=128,
                      intermediate_size=5632, page_size=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    steps = 32
    for batch, ctx in ((32, 2048), (8, 2048), (32, 64)):
        pps = (ctx + 128) // 16 + 2
        num_pages = batch * pps + 64
        table = jnp.asarray(
            1 + np.arange(batch * pps).reshape(batch, pps), jnp.int32)
        ctx_lens = jnp.full((batch,), ctx, jnp.int32)
        active = jnp.full((batch,), 10 ** 9, jnp.int32)
        last = jnp.asarray(rng.integers(1, 30000, (batch,)), jnp.int32)

        for use_pallas, tag in ((True, "pallas"), (False, "xla   ")):
            k, v = init_kv_cache(cfg, num_pages)

            def burst(state, up=use_pallas):
                k, v = state
                toks, k, v = forward_decode_steps(
                    params, cfg, last, k, v, table, ctx_lens, active,
                    steps=steps, use_pallas=up)
                return (k, v)

            dt = timed_threaded(
                f"burst32 b{batch:<3d} ctx{ctx:<5d} {tag} (per burst)",
                burst, (k, v), iters=4)
            print(f"    -> {dt / steps * 1e3:8.3f} ms/step", flush=True)

        # Comparison point: the single-token decode program dispatched
        # per step (timed_threaded — donation needs the jit boundary, so
        # this one is NOT in-jit and includes ~one dispatch per step;
        # subtract the burst's per-step cost to see what bursting saves,
        # don't read it as an overhead-free floor).
        k, v = init_kv_cache(cfg, num_pages)

        def single(state):
            k, v = state
            logits, k, v = forward_decode_pallas(
                params, cfg, last[:, None], k, v, table,
                ctx_lens, jnp.ones((batch,), jnp.int32))
            return (k, v)

        dt = timed_threaded(
            f"single-step b{batch:<3d} ctx{ctx:<5d} pallas (per step)",
            single, (k, v), iters=8)


def main_fp8():
    """fp8 KV probe (`--fp8`): the quantized merged-decode kernel vs the
    bf16 kernel at the bandwidth-bound serving shapes, plus the engine's
    decode-only tok/s on an fp8 pool — the measured check on "half the
    KV bytes ≈ double the attention-stream bandwidth"."""
    rng = np.random.default_rng(0)
    kvh, hd, ps = 8, 128, 16
    num_pages = 16 * 1024 + 1
    kb = jnp.asarray(rng.normal(size=(num_pages, kvh, ps, hd)), jnp.bfloat16)
    vb = jnp.asarray(rng.normal(size=(num_pages, kvh, ps, hd)), jnp.bfloat16)
    k8 = kb.astype(jnp.float8_e4m3fn)
    v8 = vb.astype(jnp.float8_e4m3fn)

    for batch, ctx in ((32, 2048), (32, 4096), (8, 4096)):
        pps = ctx // ps
        q = jnp.asarray(rng.normal(size=(batch, 16, hd)), jnp.bfloat16)
        table = jnp.asarray(
            1 + (np.arange(batch * pps, dtype=np.int64) * 2654435761
                 % (num_pages - 1)).reshape(batch, pps).astype(np.int32))
        lens = jnp.full((batch,), ctx, jnp.int32)
        for name, kc, vc, streams_bytes in (
                ("bf16", kb, vb, 2), ("fp8 ", k8, v8, 1)):
            kv_bytes = batch * ctx * kvh * hd * 2 * streams_bytes
            try:
                dt = timed_scanned(
                    lambda q_op, kc_op, vc_op: pallas_paged_decode_attention(
                        q_op, kc_op, vc_op, table, lens), q, kc, vc)
                print(f"decode b{batch:<3d} ctx{ctx:<5d} {name} "
                      f"{dt * 1e3:8.3f} ms/step  "
                      f"{kv_bytes / dt / 1e9:7.1f} GB/s eff (tok-bytes "
                      f"{batch * ctx * kvh * hd * 2 * 2 / dt / 1e9:7.1f})",
                      flush=True)
            except Exception as e:
                print(f"decode b{batch} ctx{ctx} {name}: "
                      f"{type(e).__name__}: {str(e)[:110]}", flush=True)

    # Engine-level: the decode-sweep b32/ctx2048 point on an fp8 pool.
    import time as _time

    from llmd_kv_cache_tpu.models import engine as engine_mod

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=16,
                      num_heads=16, num_kv_heads=8, head_dim=128,
                      intermediate_size=5632, page_size=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch, ctx, max_new = 32, 2048, 128
    prompts = [rng.integers(1, 30000, ctx).tolist() for _ in range(batch)]
    for dtype_name in ("bf16", "f8_e4m3"):
        pages = batch * ((ctx + max_new) // 16 + 2)
        eng = engine_mod.MiniEngine(
            engine_mod.EngineConfig(
                model=cfg, num_pages=pages + 64,
                max_pages_per_seq=(ctx + max_new) // 16 + 2,
                max_batch=batch, model_name="fp8-probe",
                pod_identifier="p", decode_burst=32,
                max_prefill_tokens=2048, kv_cache_dtype=dtype_name),
            params=params, seed=0)
        reqs = [eng.add_request(f"r{i}", p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        eng.step()
        start = _time.perf_counter()
        before = sum(len(r.output) for r in reqs)
        while not all(r.done for r in reqs):
            eng.step()
        dt = _time.perf_counter() - start
        toks = sum(len(r.output) for r in reqs) - before
        print(f"0.46B engine decode b32 ctx2048 {dtype_name}: "
              f"{toks / dt:7.0f} tok/s ({toks} toks in {dt:.2f}s, "
              f"{dt / (toks / batch) * 1e3:.2f} ms/step)", flush=True)
        del eng


def main_big():
    """3.1B-param scaling datapoint (`--big`): the bench model's MFU is
    bounded by its small matmul shapes (hidden 2048); at Llama-7B-like
    widths the same code lands much closer to the chip's measured matmul
    ceiling. Measured 2026-07-30 on the v5e: flash default 220.8 ms for
    the 4k prefill = 120.0 TFLOP/s (60.9% of nominal peak, ~80% of the
    151 TFLOP/s big-matmul ceiling); XLA attention 319.3 ms (42.1%)."""
    cfg = LlamaConfig(vocab_size=32000, hidden_size=4096, num_layers=16,
                      num_heads=32, num_kv_heads=8, head_dim=128,
                      intermediate_size=11008, page_size=16)
    chunk, pages_per_seq, num_pages = 2048, 272, 512
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"params: {n_params / 1e9:.2f} B", flush=True)
    table = jnp.asarray(
        np.arange(1, 1 + pages_per_seq, dtype=np.int32))[None, :]
    full_tokens = jnp.asarray(rng.integers(1, 30000, (1, 4096)), jnp.int32)
    h, kvd, inter = (cfg.hidden_size, cfg.num_kv_heads * cfg.head_dim,
                     cfg.intermediate_size)
    p_nonembed = (cfg.num_layers * (h * h + 2 * h * kvd + h * h
                                    + 3 * h * inter) + h * cfg.vocab_size)
    prefill_flops = (2 * p_nonembed * 4096
                     + cfg.num_layers * 4 * (4096 ** 2 / 2) * h)
    print(f"prefill FLOPs: {prefill_flops / 1e12:.1f} T", flush=True)

    for fwd, prm, label in (
            (forward_prefill_pallas, params,
             "3.1B 4k prefill in-jit, flash (unfused)"),
            (forward_prefill_pallas, fuse_params(params, cfg),
             "3.1B 4k prefill, flash + fused (TPU default)"),
            (forward, params, "3.1B 4k prefill in-jit, XLA attention")):
        timed_chunked_prefill(label, fwd, cfg, prm, table, full_tokens,
                              num_pages, prefill_flops, iters=3,
                              chunk=chunk)


def _require_v5e() -> None:
    """The shares printed here divide by the v5e's published peaks
    (197 TFLOP/s bf16, 819 GB/s HBM); on any other device they would be
    wrong, and a CPU has no such number at all."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" or "v5 lite" not in dev.device_kind:
        raise SystemExit(
            f"mfu_probe.py knows the peaks of a TPU v5e only; JAX found "
            f"{dev.platform!r} / {dev.device_kind!r}")


if __name__ == "__main__":
    import sys

    _require_v5e()
    if "--big" in sys.argv:
        main_big()
    elif "--decode" in sys.argv:
        main_decode()
    elif "--moe" in sys.argv:
        main_moe()
    elif "--mla" in sys.argv:
        main_mla()
    elif "--burst" in sys.argv:
        main_burst()
    elif "--fp8" in sys.argv:
        main_fp8()
    else:
        main()
