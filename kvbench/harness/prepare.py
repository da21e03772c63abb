"""Set-up: everything between process start and the window.

Device, native build, compile cache, weights, fleet, the correctness probe,
warm-up of every shape the window will use, and the set-up traffic the mix
asks for. Each phase's seconds are recorded; together they are the run's
set-up time.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import correct, fleet as fleet_mod, names
from .fleet import log
from .loop import now, serve_to_completion


@contextlib.contextmanager
def phase(ctx, name: str):
    t0 = now()
    c0 = ctx.stats.compiles if ctx.stats else 0
    yield
    ctx.phases[name] = round(now() - t0, 2)
    log(f"set-up: {name} {ctx.phases[name]:.1f}s"
        + (f" ({ctx.stats.compiles - c0} programs compiled)"
           if ctx.stats else ""))


def programs_first_used(stats) -> int:
    """Programs compiled or fetched from the persistent cache so far: either
    way a shape's first use. This is what must not move inside a window."""
    return stats.compiles + stats.hits


def warm_up(ctx) -> None:
    """Every prefill bucket up to the chunk cap (a prefix hit can end at any
    page, so the uncached remainder falls into any power-of-two bucket) and
    the one decode shape, on every replica; with a storage tier, every
    gather and scatter size of the copier (it moves up to 128 pages at a
    time and the rest in one odd-sized piece, each size its own program)."""
    cfg = ctx.fleet.cfg
    page = cfg.page_size
    rng = np.random.default_rng(7)
    for pod, eng in ctx.fleet.engines.items():
        cap_pages = max(1, eng.cfg.max_prefill_tokens // page)
        pages = 1
        reqs = []
        while pages <= cap_pages:
            prompt = rng.integers(1, cfg.vocab_size, pages * page).tolist()
            reqs.append(eng.enqueue(f"warm-{pod}-{pages}", prompt,
                                    max_new_tokens=2))
            pages *= 2
        deadline = now() + 1100.0
        while not all(r.done for r in reqs):
            eng.step()
            if now() > deadline:
                raise TimeoutError(f"warm-up of {pod} is stuck")
        if eng.offload_handlers is None:
            continue
        eng.flush_offload(timeout_s=120.0)
        copier = eng.offload_handlers.copier
        copier.k_cache, copier.v_cache = eng.k_cache, eng.v_cache
        for n in range(1, copier.MAX_BATCH_PAGES + 1):
            ids = list(range(1, n + 1))
            (slab,) = copier.gather_many_to_host([ids])
            # The same bytes back to the same pages: nothing changes.
            copier.scatter_many_from_host([(slab, ids)])
        eng.k_cache, eng.v_cache = copier.k_cache, copier.v_cache


def prepare(cell: dict, conf: dict, traffic: dict, schedule_fn, seed: int,
            seconds: float, toy: bool, t_process: float,
            traced: bool = False):
    """Build, probe and warm the system for one cell (``traced``: with the
    engines' phases on, ``fleet.build_fleet``). Returns a namespace
    with ``fleet``, ``params``, ``stats``, ``phases``, ``schedule``,
    ``probe``, ``device``, ``devices``, ``served_faults``, the
    configuration's ``reference`` and ``counts`` modules and ``close()``."""
    import jax

    ctx = SimpleNamespace(phases={}, stats=None, t_process=t_process)
    # Before anything is built: a configuration that names a file which is
    # not there stops here, with the path.
    ctx.reference = names.reference(conf)
    ctx.counts = names.counts(conf)
    chips = int(cell["chips"])
    with phase(ctx, "device"):
        ctx.device = fleet_mod.find_device(chips, toy)
    on_tpu = ctx.device["platform"] == "tpu"
    ctx.stats = fleet_mod.CompileStats()
    with phase(ctx, "native"):
        fleet_mod.build_native_if_missing()
    if not toy:  # a toy run leaves the cache as it is
        from llmd_kv_cache_tpu.utils.compile_cache import (
            enable_compile_cache)

        cache_dir = enable_compile_cache()
        # Small programs too: a run after the first finds all of them.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {cache_dir}")

    kv = conf["kvbench"]
    replicas = int(kv["replicas"])
    if kv["placement"] == "one_chip":
        devices = [None] * replicas
        ctx.devices = jax.devices()[:1]
    elif kv["placement"] == "chip_each":
        ctx.devices = jax.devices()[:replicas]
        devices = list(ctx.devices)
    else:
        raise ValueError(f"unknown placement {kv['placement']!r}")
    if len(ctx.devices) != chips:
        raise ValueError(f"the cell asks for {chips} chip(s), its "
                         f"configuration places on {len(ctx.devices)}")

    ctx.store_root = None
    if kv.get("storage"):
        # Outside the checkout (under TMPDIR) and removed at exit: a store
        # of several GiB left in the tree makes it too large to copy.
        ctx.store_root = Path(tempfile.mkdtemp(prefix="kvbench-store-"))

    def close() -> None:
        if getattr(ctx, "fleet", None) is not None:
            ctx.fleet.shutdown()
        if ctx.store_root is not None:
            shutil.rmtree(ctx.store_root, ignore_errors=True)

    ctx.close = close
    try:
        with phase(ctx, "schedule"):
            from .fleet import model_config

            ctx.cfg = model_config(conf)
            ctx.schedule = schedule_fn(seed, traffic, ctx.cfg.vocab_size,
                                       seconds)
        with phase(ctx, "model"):
            _, ctx.params = fleet_mod.build_model(conf, seed, devices[0])
            jax.block_until_ready(ctx.params)
        with phase(ctx, "fleet"):
            ctx.fleet = fleet_mod.build_fleet(conf, ctx.cfg, ctx.params,
                                              devices, ctx.store_root,
                                              force_pallas=toy,
                                              traced=traced)
            ctx.served_faults = fleet_mod.what_serves(
                ctx.fleet, interpret=not on_tpu)
        with phase(ctx, "probe"):
            ctx.probe = correct.probe(
                ctx.fleet, ctx.params, ctx.reference, seed,
                int(kv["probe"]["prompt_tokens"]),
                int(kv["probe"]["decode_tokens"]))
        with phase(ctx, "warm-up"):
            warm_up(ctx)
        with phase(ctx, "set-up traffic"):
            if ctx.schedule.setup:
                serve_to_completion(ctx.fleet, ctx.schedule.setup)
            for eng in ctx.fleet.engines.values():
                if eng.offload_handlers is not None:
                    eng.flush_offload(timeout_s=300.0)
    except BaseException:
        close()
        raise
    log(f"set-up phases: {ctx.phases}; compile: {ctx.stats.as_dict()}")
    return ctx
