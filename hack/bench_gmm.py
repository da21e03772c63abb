#!/usr/bin/env python3
"""The routed experts' grouped matmuls alone (megablox ``gmm`` behind
``llama._grouped_matmul``), at the published widths of the five routed
configurations: gate + up + down of one layer over the experts a chip holds,
for a decode step (the cell's live tokens x top-k) and for a chunk of 512,
under the rule that stood until PR 58 (``gcd(k, 512) x gcd(n, 1024)``) and
under the tree's own (``llama.gmm_tiling``). A token's experts are drawn as
the router's: top-k distinct of all the experts, those the chip holds kept;
every layer of a run has a draw of its own, and the layers are chained in
one program (a program of single calls measures their launches). A layer's
time is the device time of the ops a trace calls ``gmm`` (what
``moe_dispatch_roofline`` reads) beside the host's clock around the program;
the bounds are the touched experts' bytes over 819 GB/s and the held
assignments' FLOPs over 197 TFLOP/s.

  chiprun -- python3 hack/bench_gmm.py            # one v5e, ~4 min
  chiprun -- python3 hack/bench_gmm.py --sweep    # every tiling of 1-4 MB a matrix alone, ~20 min
  python3 hack/bench_gmm.py --rehearse            # the CPU, toy widths, no times
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# name, experts held, of, hidden, expert width, top-k, live tokens of a
# decode step (the cell's decode_rows_mean, ledger, PR 57; two positions a
# row where a draft is verified), the decode shape's tokens.
CONFIGS = (
    ("deepseek-v3.2-exp-ep16-l5", 16, 256, 7168, 2048, 8, 1, 8),
    ("gigachat3.5-ep16-l5", 16, 256, 7168, 2048, 8, 2, 8),
    ("solar-open2-ep16-l8", 20, 320, 4096, 1280, 8, 1, 8),
    ("openpangu-ultra-ep32-l5", 8, 256, 7680, 2048, 8, 4, 16),
    ("granite-4.0-h-small-ep2-l10", 36, 72, 4096, 768, 10, 5, 16),
)
TOY = (("toy", 3, 6, 256, 384, 2, 2, 4),)
CHUNK = 512
PEAKS = json.loads((ROOT / "kvbench" / "trace" / "peaks.json").read_text())[
    "TPU v5 lite"]
LAYERS = 16  # draws chained in one program


def old_rule(m, k, n, itemsize):
    return 128, math.gcd(k, 512), math.gcd(n, 1024)


def draws(rng, layers, tokens, top_k, held, of):
    """``[layers, held]``: how many of ``tokens``' assignments fall to each
    expert held, a layer a draw."""
    sizes = np.zeros((layers, held), np.int32)
    for layer in range(layers):
        for _ in range(tokens):
            chosen = rng.choice(of, top_k, replace=False)
            np.add.at(sizes[layer], chosen[chosen < held], 1)
    return sizes


def gmm_seconds(trace_dir):
    """Device seconds of the ops a trace calls ``gmm``."""
    from kvbench.trace import reduce

    planes = reduce.load(reduce.find_xplane(trace_dir), [])
    return reduce.NS * sum(
        e.dur for plane in planes
        for e in plane.lines.get(reduce.OPS_LINE, [])
        if e.name.startswith("gmm"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="every tiling whose piece is 1-4 MB, a matrix alone")
    ap.add_argument("--tm", default="128",
                    help="row tiles the sweep tries (the padding follows)")
    ap.add_argument("--only", default="", help="configurations named so")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from llmd_kv_cache_tpu.models import llama

    toy = args.rehearse
    if not toy and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the times are a chip's (--rehearse walks "
                         "the path on the CPU)")
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    layers = 2 if toy else LAYERS
    rng = np.random.default_rng(58)
    bf16 = jnp.bfloat16

    def program(rule):
        """``layers`` layers' matmuls under ``rule(m, k, n, itemsize)``,
        chained: a layer's group sizes are its own."""
        def mm(lhs, rhs, sizes):
            return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                       tiling=rule(lhs.shape[0], *rhs.shape[1:], 2),
                       interpret=toy)

        @jax.jit
        def run(lhs, weights, sizes):
            def layer(i, seen):
                return seen + sum(mm(x, w, sizes[i])[:8, :128]
                                  for x, w in zip(lhs, weights))
            return jax.lax.fori_loop(0, sizes.shape[0], layer,
                                     jnp.zeros((8, 128), jnp.float32))
        return run

    def measure(run, *operands):
        """(device seconds of ``gmm``, host seconds) a layer."""
        jax.block_until_ready(run(*operands))
        if toy:
            return 0.0, 0.0
        t0 = time.perf_counter()
        jax.block_until_ready(run(*operands))
        wall = time.perf_counter() - t0
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            jax.block_until_ready(run(*operands))
            jax.profiler.stop_trace()
            device = gmm_seconds(trace_dir)
        return device / layers, wall / layers

    swept = set()
    for name, held, of, hidden, width, top_k, live, slots in (
            TOY if toy else CONFIGS):
        if args.only not in name or (held, hidden, width) in swept:
            continue
        if args.sweep:
            swept.add((held, hidden, width))
        keys = jax.random.split(jax.random.key(58), 3)
        w_gate, w_up = (0.02 * jax.random.normal(
            key, (held, hidden, width), bf16) for key in keys[:2])
        w_down = 0.02 * jax.random.normal(
            keys[2], (held, width, hidden), bf16)
        for what, tokens, shape in (("decode", live, slots),
                                    ("chunk", CHUNK, CHUNK)):
            sizes = draws(rng, layers, tokens, top_k, held, of)
            touched = float((sizes > 0).sum(1).mean())
            assigned = float(sizes.sum(1).mean())
            least_bytes = (touched * 6 * hidden * width
                           / PEAKS["hbm_bytes_per_s"])
            least_flops = (assigned * 6 * hidden * width
                           / PEAKS["bf16_flops_per_s"])
            print(f"{name} {what}: {tokens} tokens x {top_k}, {assigned:.1f} "
                  f"held assignments over {touched:.1f} of {held} experts a "
                  f"layer; bytes {least_bytes * 1e6:.1f} us, FLOPs "
                  f"{least_flops * 1e6:.1f} us", flush=True)

            def operands(tm):
                m = -(-shape * top_k // tm) * tm
                return (jnp.asarray(rng.normal(size=(m, hidden)), bf16),
                        jnp.asarray(rng.normal(size=(m, width)), bf16))

            if not args.sweep:
                rows, act = operands(128)
                took = {}
                for label, rule in (("old", old_rule),
                                    ("new", llama.gmm_tiling)):
                    device, wall = measure(
                        program(rule), (rows, rows, act),
                        (w_gate, w_up, w_down), jnp.asarray(sizes))
                    took[label] = device
                    share = (100 * max(least_bytes, least_flops) / device
                             if device else float("nan"))
                    print(f"  {label} gate/up "
                          f"{rule(rows.shape[0], hidden, width, 2)} down "
                          f"{rule(rows.shape[0], width, hidden, 2)}: gmm "
                          f"{device * 1e6:.1f} us a layer ({share:.1f}% of "
                          f"the larger bound), host clock {wall * 1e6:.1f} us",
                          flush=True)
                if took["old"]:
                    print(f"  new / old {took['new'] / took['old']:.3f}",
                          flush=True)
                continue
            for matrix, k, n, w in (("gate", hidden, width, w_gate),
                                    ("down", width, hidden, w_down)):
                for tm in (int(t) for t in args.tm.split(",")):
                    rows, act = operands(tm)
                    lhs = rows if matrix == "gate" else act
                    if tm != 128:
                        cands = [llama.gmm_tiling(lhs.shape[0], k, n, 2)[1:]]
                    else:
                        cands = [(tk, tn)
                                 for tk in range(128, k + 1, 128) if k % tk == 0
                                 for tn in range(128, n + 1, 128) if n % tn == 0
                                 if 1 << 20 <= tk * tn * 2 <= 4 << 20
                                 and llama.gmm_vmem_bytes(tm, tk, tn, 2)
                                 <= 14 << 20]
                        cands.insert(0, old_rule(0, k, n, 2)[1:])
                    for tk, tn in cands:
                        try:
                            device, wall = measure(
                                program(lambda *_: (tm, tk, tn)), (lhs,),
                                (w,), jnp.asarray(sizes))
                        except Exception as e:  # a tiling Mosaic refuses
                            print(f"  {matrix} [{k}, {n}] ({tm}, {tk}, {tn}) "
                                  f"refused: {str(e)[:120]!r}", flush=True)
                            continue
                        print(f"  {matrix} [{k}, {n}] ({tm}, {tk}, {tn}) "
                              f"{tk * tn * 2 / 2**20:.2f} MiB a piece: gmm "
                              f"{device * 1e6:.1f} us, host clock "
                              f"{wall * 1e6:.1f} us", flush=True)


if __name__ == "__main__":
    main()
