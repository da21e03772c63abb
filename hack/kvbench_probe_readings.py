#!/usr/bin/env python3
"""The readings a configuration's ``TOLERANCE`` and ``MARGIN`` are set from,
taken with the benchmark's own probe (``kvbench/harness/correct.py``) over
several seeds: one replica of the configuration as the cell builds it, a
line a seed. Not part of a run of the benchmark; a builder's tool, for the
chip (``chiprun -- python3 hack/kvbench_probe_readings.py ...``) or, with
``--rehearse``, the CPU at toy widths.

  --seeds 1,2,3        the probe against the served program
  --margins            also the reference's ``margin_readings`` (how far
                       bfloat16 moves a router's deciding gaps)
  --branches           also the reference's ``branch_sizes`` where it has
                       them (a layer's residual and what each branch adds
                       to it, over 512 tokens): what a configuration's
                       initialisation scales are set from
  --control TYPE       the probe against the reference's own ``Control``
                       (its forward rounded to TYPE, e.g. float8_e4m3fn;
                       ``state:bfloat16`` where the reference keeps a
                       sequence state) in the engine's place: it has to
                       come out not ok
  --set KEY=VALUE      a published key of the configuration replaced
  --serve KEY=VALUE    a key replaced in what the engine SERVES only (the
                       reference keeps the configuration's): a planted
                       fault, e.g. ``swiglu_limit=0``; a model of window
                       and full layers' three controls: ``rope_parameters=``
                       with the yarn rule under both kinds, or the plain
                       one under both, and ``sliding_window=131072`` (the
                       window ignored: both pools, a window that never
                       binds)
  --fault NAME         a fault planted in the serving program
                       (``FAULTS``); it has to come out not ok
                       (``verify-mask``: a drafting model's first verified
                       position sees the draft's key too;
                       ``no-residual-scale``: a model with a
                       ``residual_multiplier`` served without it;
                       ``xla-recurrence`` is no fault: the recurrence's
                       XLA form in the kernels' place, which has to read
                       as the kernels do; a model of two mixers a layer:
                       ``norm-all-channels``, ``wrong-group``,
                       ``no-key-multiplier``, ``out-multipliers-swapped``,
                       ``no-rope``). Several, comma-separated, are planted
                       one after another in one process
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kvbench.harness import correct, fleet as F, names  # noqa: E402


def _stale_state() -> None:
    """A prefix hit takes the pages and not the snapshot: the row's working
    slot keeps what its last owner left in it."""
    from llmd_kv_cache_tpu.models import engine

    engine.copy_state_slot = lambda state, _src_dst: state


def _conv_tail_dropped() -> None:
    """Every prefill chunk starts its conv from zeros, as if the row had
    no earlier tokens."""
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.models import llama

    served = llama._gated_deltanet

    def dropped(x, layer, cfg, lj, state, *rest):
        recurrent, conv, slots, snap = state
        if x.shape[1] == 1:
            return served(x, layer, cfg, lj, state, *rest)
        out, (recurrent, new, slots, snap) = served(
            x, layer, cfg, lj, (recurrent, jnp.zeros_like(conv), slots,
                                snap), *rest)
        return out, (recurrent, conv.at[lj].set(new[lj]), slots, snap)

    llama._gated_deltanet = dropped


def _gate_left_out() -> None:
    """Attention's heads reach ``wo`` ungated (the forward reads the gate
    off the weight tree, so the tree is shown without it)."""
    from llmd_kv_cache_tpu.models import llama

    served = llama._sublayer_out

    def ungated(out, gate_in, layer, *rest):
        return served(out, gate_in,
                      {k: v for k, v in layer.items() if k != "w_og"}, *rest)

    llama._sublayer_out = ungated


def _residual_unscaled() -> None:
    """What a sub-layer adds joins the residual whole, as in a model
    without ``residual_multiplier``."""
    import dataclasses

    from llmd_kv_cache_tpu.models import llama

    served = llama._sublayer_out

    def unscaled(out, gate_in, layer, cfg, which):
        return served(out, gate_in, layer,
                      dataclasses.replace(cfg, residual_multiplier=1.0),
                      which)

    llama._sublayer_out = unscaled


def _xla_recurrence() -> None:
    """Not a fault: the recurrence's XLA form in the kernels' place (the
    same blocked algorithm, the compiler's float32 matmuls), to tell a
    kernel's arithmetic from the algorithm's."""
    import functools

    from llmd_kv_cache_tpu.ops import gated_deltanet as gd, mamba2 as m2

    for module, name in ((gd, "gdn_scan"), (gd, "gdn_step"),
                         (gd, "kda_scan"), (gd, "kda_step"),
                         (m2, "mamba2_scan"), (m2, "mamba2_step")):
        served = getattr(module, name)

        def xla(*args, _served=served, **kw):
            return _served(*args, **{**kw, "kernel": False,
                                     "interpret": False})

        setattr(module, name, functools.wraps(served)(xla))


def _verify_mask_off_by_one() -> None:
    """A speculative step's first position sees the draft's key too (every
    query row of the decode kernel takes the last position's bound)."""
    from llmd_kv_cache_tpu.ops import pallas_paged_attention as ppa

    served = ppa._decode_mask

    def off_by_one(positions, ctx_len, sliding_window, sinks, back=None,
                   first_key=0):
        return served(positions, ctx_len, sliding_window, sinks, None,
                      first_key)

    ppa._decode_mask = off_by_one


def _norm_over_all_channels() -> None:
    """A Mamba-2 mixer's gated norm takes one mean square over all inner
    channels where the model norms each group of B and C apart."""
    from llmd_kv_cache_tpu.models import llama

    served = llama._group_rms
    llama._group_rms = lambda y, groups, eps: served(y, 1, eps)


def _wrong_group() -> None:
    """A head reads the B and C of another group (the groups in reverse)."""
    from llmd_kv_cache_tpu.ops import mamba2 as m2

    served = m2._groups
    m2._groups = lambda a, tiles: served(a, tiles)[:, ::-1]


def _served_as(**changes):
    """A fault in a model's scalars: the step programs read the parameters
    (``llama.multiplied``) under a configuration with ``changes(cfg)``."""
    def plant() -> None:
        import dataclasses

        from llmd_kv_cache_tpu.models import llama

        served = llama.multiplied
        llama.multiplied = lambda params, cfg: served(
            params, dataclasses.replace(
                cfg, **{k: v(cfg) for k, v in changes.items()}))
    return plant


def _rope_left_out() -> None:
    """Queries and keys reach attention unrotated."""
    from llmd_kv_cache_tpu.models import llama

    llama._rope = lambda x, *_args, **_kw: x


# Faults planted in the program, by name. Each replaces something the step
# programs look up when they are first traced.
FAULTS = {"stale-state": _stale_state, "conv-tail": _conv_tail_dropped,
          "no-gate": _gate_left_out,
          "no-residual-scale": _residual_unscaled,
          "xla-recurrence": _xla_recurrence,
          "verify-mask": _verify_mask_off_by_one,
          "norm-all-channels": _norm_over_all_channels,
          "wrong-group": _wrong_group,
          "no-key-multiplier": _served_as(
              attention_multiplier=lambda cfg: cfg.head_dim ** -0.5),
          "out-multipliers-swapped": _served_as(
              ssm_out_multiplier=lambda cfg: cfg.attention_out_multiplier,
              attention_out_multiplier=lambda cfg: cfg.ssm_out_multiplier),
          "no-rope": _rope_left_out}


@contextlib.contextmanager
def planted(fault: str):
    """``FAULTS[fault]`` planted for the block's length (nothing for ""):
    every module a fault touches is put back as it was, and nothing traced
    before, inside or after the block is shared across its edges."""
    import jax

    from llmd_kv_cache_tpu.models import engine, llama
    from llmd_kv_cache_tpu.ops import (gated_deltanet, mamba2,
                                       pallas_paged_attention)

    modules = (engine, llama, gated_deltanet, mamba2, pallas_paged_attention)
    saved = [dict(vars(m)) for m in modules]
    jax.clear_caches()
    if fault:
        FAULTS[fault]()
    try:
        yield
    finally:
        for module, was in zip(modules, saved):
            vars(module).update(was)
        jax.clear_caches()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="deepseek-v3.2-exp-ep16-l5")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--margins", action="store_true")
    ap.add_argument("--branches", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--serve", action="append", default=[])
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    faults = args.fault.split(",")
    if set(faults) - {"", *FAULTS}:
        ap.error(f"--fault: one or more of {sorted(FAULTS)}")
    for fault in faults:
        with planted(fault):
            readings(args, fault)


def readings(args, fault: str) -> None:
    import jax

    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine

    conf = names.config_for_run(names.benchmark(), args.config, args.rehearse)
    for pair in args.set:
        key, value = pair.split("=", 1)
        conf[key] = json.loads(value)
    served_conf = dict(conf)
    for pair in args.serve:
        key, value = pair.split("=", 1)
        served_conf[key] = json.loads(value)
    ref = names.reference(conf)
    eng = conf["kvbench"]["engine"]
    sizes = conf["kvbench"]["probe"]
    n, new = int(sizes["prompt_tokens"]), int(sizes["decode_tokens"])
    gaps = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        cfg, params = F.build_model(conf, seed)
        fl = F.Fleet()
        fl.cfg = cfg
        if args.control:
            fl.engines["pod-0"] = ref.Control(params, cfg, args.control)
        else:
            fl.engines["pod-0"] = MiniEngine(EngineConfig(
                model=F.model_config(served_conf), model_name="m",
                pod_identifier="pod-0",
                num_pages=int(eng["num_pages"]),
                max_pages_per_seq=int(eng["max_pages_per_seq"]),
                max_batch=int(eng["max_batch"]),
                max_prefill_tokens=int(eng["max_prefill_tokens"])),
                params=params)
        rep = correct.probe(fl, params, ref, seed, n, new)
        stats = jax.devices()[0].memory_stats() or {}
        what = " ".join(filter(None, [args.control or "served", fault,
                                      *args.serve]))
        print(f"READING seed {seed} {what}: ok "
              f"{rep['ok']} prefill_err {rep['prefill_rel_err']:.4f} "
              f"shortfall {rep['decode_worst_shortfall']:.4f} hit "
              f"{rep['hit_rel_err']:.4f} alternatives "
              f"{rep['alternatives']} faults {rep['faults']} | "
              f"{time.time() - t0:.0f}s peak "
              f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f}GB", flush=True)
        if args.branches:
            rng = np.random.default_rng(seed + 1)
            for at, sizes in enumerate(ref.branch_sizes(
                    params, cfg, rng.integers(1, cfg.vocab_size, 512))):
                print(f"BRANCHES seed {seed} layer {at}: residual "
                      f"{sizes[0]:.4f} adds", " ".join(
                          f"{v:.4f}" for v in sizes[1:]), flush=True)
        if args.margins:
            rng = np.random.default_rng(seed + 1)
            tokens = rng.integers(1, cfg.vocab_size, n + new).tolist()
            gaps += ref.margin_readings(params, cfg, tokens,
                                        list(range(n - 1, n + new)))
        del fl, params, rep
    if gaps:
        for what, values in (("expert", [a for a, _ in gaps]),
                             ("group", [b for _, b in gaps])):
            print(f"MARGIN {what} gap moved by", json.dumps(
                {p: float(np.percentile(values, p))
                 for p in (50, 90, 99, 100)}), f"({len(values)} readings)",
                flush=True)


if __name__ == "__main__":
    main()
