"""The system under test, built from one configuration file.

Copied from ``chip_smoke.py`` (``find_device``, ``CompileStats``,
``build_model``, ``build_fleet``, ``assert_what_serves``) so that the smoke
may change and the yardstick does not: N engines sharing one weight tree,
each with its own page pool and its own event sink into one ``Pool`` over
the native index, one ``KVAwareRouter`` in front, and where the
configuration has a storage tier, the shared-storage offload plane behind
each block manager.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from types import SimpleNamespace

from .names import ROOT


def log(msg: str) -> None:
    print(f"[kvbench] {msg}", flush=True)


class NoDevice(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def find_device(chips: int, toy: bool) -> dict:
    """First touch of JAX: say what was found; refuse anything but a TPU
    with enough chips (a toy run takes what is there and says so)."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    log(f"device: {device} | jax {jax.__version__} python "
        f"{sys.version.split()[0]}")
    if not toy and d0.platform != "tpu":
        print(f"[kvbench] no TPU: JAX found platform {d0.platform!r} "
              f"({len(devs)} device(s), JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); no result.",
              file=sys.stderr, flush=True)
        raise NoDevice(1)
    if len(devs) < chips:
        print(f"[kvbench] the cell needs {chips} chip(s), JAX found "
              f"{len(devs)}; no result.", file=sys.stderr, flush=True)
        raise NoDevice(1)
    return device


def build_native_if_missing() -> float:
    """``make native`` when a library is absent (``.gitignore`` excludes
    them, so a fresh checkout has none). Returns the seconds it took."""
    libs = [ROOT / "csrc" / "kvindex" / "libkvindex.so",
            ROOT / "csrc" / "kvio" / "libkvio.so"]
    if all(p.is_file() for p in libs):
        return 0.0
    t0 = time.perf_counter()
    # The build's chatter goes to stderr: stdout ends with the result line.
    subprocess.run(["make", "native"], cwd=ROOT, check=True,
                   stdout=sys.stderr)
    return time.perf_counter() - t0


class CompileStats:
    """Programs compiled and persistent-cache traffic, from JAX's own
    monitoring events. ``compiles`` is what must not move in the window."""

    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = self.compiles = 0
        self.backend_compile_s = self.trace_s = self.lower_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs
            self.compiles += 1
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.trace_s += secs
        elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lower_s += secs

    def as_dict(self) -> dict:
        return {"programs": self.compiles,
                "compile_s": round(self.backend_compile_s, 2),
                "trace_s": round(self.trace_s, 2),
                "lower_s": round(self.lower_s, 2),
                "cache_hits": self.hits, "cache_misses": self.misses}


def model_config(conf: dict):
    """``LlamaConfig`` through ``hf_loader.config_from_hf`` from a plain
    namespace of the configuration file's published keys: the same route a
    checkpoint takes, without importing ``transformers`` during set-up."""
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.models.hf_loader import config_from_hf

    published = {k: v for k, v in conf.items() if k != "kvbench"}
    return config_from_hf(SimpleNamespace(**published),
                          page_size=int(conf["kvbench"]["engine"]["page_size"]),
                          dtype=jnp.bfloat16)


def key_for(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def cache_payload(cfg) -> tuple[int, int, int]:
    """(streams, heads, width) of one token in one layer of the page pool,
    as the model's config says its cache holds them: K and V of
    ``num_kv_heads`` x ``head_dim`` for GQA; for a latent one stream (the
    pool has no V, ``llama.init_kv_cache``) of one shared head as wide as
    rank + rope key + pad."""
    return (1 if cfg.is_mla else 2, cfg.kv_cache_heads,
            cfg.kv_cache_head_dim)


def build_model(conf: dict, seed: int, device=None):
    """Random weights in the served type, made on the device from the seed,
    fused where the program's own gate says fusing pays (one shared fused
    tree, or each engine would make its own copy)."""
    import jax
    import jax.numpy as jnp

    from llmd_kv_cache_tpu.models.llama import init_params, maybe_fuse_params

    cfg = model_config(conf)
    with jax.default_device(device):
        params = init_params(key_for(seed), cfg)
        params = maybe_fuse_params(params, cfg)
    # A fused tree also holds a plain number (its column interleave).
    n_params = sum(getattr(x, "size", 0)
                   for x in jax.tree_util.tree_leaves(params))
    streams, heads, width = cache_payload(cfg)
    kv_per_token = (streams * cfg.num_layers * heads * width
                    * jnp.dtype(cfg.dtype).itemsize)
    fused = any(k in params["layers"][0] for k in ("w_qkv", "w_mla_in"))
    log(f"model: {conf['kvbench']['model_name']} layers={cfg.num_layers} "
        f"hidden={cfg.hidden_size} heads={cfg.num_heads}/{cfg.num_kv_heads}"
        f"x{cfg.head_dim} mlp={cfg.intermediate_size} vocab={cfg.vocab_size}"
        f" qk_norm={cfg.qk_norm} window={cfg.sliding_window} fused={fused}"
        f" cache={streams}x{heads}x{width} | {n_params / 1e9:.2f}B params, "
        f"{kv_per_token / 1024:g} KiB KV/token")
    return cfg, params


class Fleet:
    """Engines by pod name, the router, and what the harness reads from
    them. ``ingest`` is called with (seconds, pod) around every event batch
    a sink hands to the pool."""

    def __init__(self):
        self.engines: dict = {}
        self.indexer = self.pool = self.router = None
        self.model_name = ""
        self.cfg = None
        self.on_ingest = None  # set by the loop: callable(t0, t1, pod)

    def shutdown(self) -> None:
        """Drain and stop the storage tier; nothing of it prints later."""
        for eng in self.engines.values():
            if eng.offload_handlers is not None:
                eng.flush_offload(timeout_s=60.0)
                eng.offload_handlers.shutdown()


def build_fleet(conf: dict, cfg, params, devices, store_root,
                force_pallas: bool, traced: bool = False) -> Fleet:
    """One engine per entry of ``devices`` (None = JAX's default device),
    wired as ``chip_smoke.build_fleet`` wires them. ``traced``: the engines
    are built with ``EngineConfig.telemetry``, which switches their phases
    on (``telemetry/tracing.py``: a ``TraceAnnotation`` each, on the
    profiler's clock); an untraced run builds them without, as before."""
    from llmd_kv_cache_tpu.core import TokenProcessorConfig
    from llmd_kv_cache_tpu.events.model import EventBatch
    from llmd_kv_cache_tpu.events.pool import Pool, PoolConfig
    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
    from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec
    from llmd_kv_cache_tpu.scoring import Indexer, IndexerConfig
    from llmd_kv_cache_tpu.scoring.router import KVAwareRouter
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
        EngineTelemetryConfig)

    kv = conf["kvbench"]
    ecfg = kv["engine"]
    page = int(ecfg["page_size"])
    fleet = Fleet()
    fleet.cfg, fleet.model_name = cfg, kv["model_name"]
    indexer = Indexer(IndexerConfig(
        token_processor_config=TokenProcessorConfig(block_size_tokens=page)))
    pool = Pool(PoolConfig(concurrency=1), indexer.kv_block_index,
                indexer.token_processor)
    # None = auto: Pallas on a TPU. A toy run insists, to walk the kernels
    # through the interpreter on the CPU.
    pallas = True if force_pallas else None
    for i, dev in enumerate(devices):
        name = f"pod-{i}"

        def sink(events, pod_name=name):
            t0 = time.perf_counter()
            pool.process_event_batch(
                EventBatch(timestamp=time.time(), events=list(events)),
                pod_name, fleet.model_name)
            if fleet.on_ingest is not None:
                fleet.on_ingest(t0, time.perf_counter(), pod_name)

        spec = None
        if kv.get("storage"):
            streams, heads, width = cache_payload(cfg)
            spec = SharedStorageOffloadSpec(
                root=str(store_root), model_name=fleet.model_name,
                page_size=page, num_layers=cfg.num_layers,
                kv_heads=heads, head_dim=width, kv_streams=streams,
                io_threads=int(kv["storage"].get("io_threads", 4)),
                parallel_agnostic=True)
        fleet.engines[name] = MiniEngine(
            EngineConfig(model=cfg, model_name=fleet.model_name,
                         pod_identifier=name,
                         num_pages=int(ecfg["num_pages"]),
                         max_pages_per_seq=int(ecfg["max_pages_per_seq"]),
                         max_batch=int(ecfg["max_batch"]),
                         max_prefill_tokens=int(ecfg["max_prefill_tokens"]),
                         use_pallas_decode=pallas, use_pallas_prefill=pallas,
                         telemetry=(EngineTelemetryConfig() if traced
                                    else None)),
            event_sink=sink, params=params, offload_spec=spec, device=dev)
    fleet.indexer, fleet.pool = indexer, pool
    fleet.router = KVAwareRouter(indexer, list(fleet.engines))
    return fleet


def what_serves(fleet: Fleet, interpret: bool) -> list[str]:
    """Faults in what serves: the kernels (Pallas, compiled on a chip,
    interpreted only in the rehearsal), the native index and hash chain."""
    from llmd_kv_cache_tpu.index.native import NativeIndex

    bad = []
    if not isinstance(fleet.indexer.kv_block_index, NativeIndex):
        bad.append(f"index is {type(fleet.indexer.kv_block_index).__name__}")
    if fleet.indexer.token_processor.hash_backend != "native":
        bad.append("the indexer hashes with the Python chain")
    for name, eng in fleet.engines.items():
        b = eng.attention_backends
        for ph in ("decode", "prefill"):
            if b[ph]["backend"] != "pallas":
                bad.append(f"{name} {ph} attention is {b[ph]['backend']}")
            if b[ph]["interpret"] is not interpret:
                bad.append(f"{name} {ph} interpret={b[ph]['interpret']}")
        if eng.processor.hash_backend != "native":
            bad.append(f"{name} hashes blocks with the Python chain")
    return bad


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip; 0 where the backend keeps no count (the
    CPU of the rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
