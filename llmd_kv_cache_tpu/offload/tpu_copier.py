"""TPU HBM ↔ host transfers for paged KV blocks.

The TPU-native replacement for the reference's CUDA ``TensorCopier``
(``tensor_copier.cu:222-249``): instead of per-block ``cudaMemcpyAsync``
into pinned staging, the paged-KV gather happens **on device** inside one
jitted XLA program (``gather_pages_flat`` over both K and V pools for all
layers), producing one contiguous slab per file, which is then moved to
host memory in a single device→host DMA. The reverse path scatters a host
slab back into the paged pools inside one jit with donation.

Slab layout per offloaded file (dtype = cache dtype):
``[num_layers, 2 (K,V), pages_per_file, kv_heads, page_size, head_dim]``

The device→host leg stages through ``pinned_host`` memory. A runtime that
refuses that memory kind is an error on a TPU; elsewhere (CPU tests on a
runtime without memory kinds) transfers go unpinned and
``pinned_host_active`` says so.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import get_logger

logger = get_logger("offload.copier")


@partial(jax.jit, static_argnames=("streams",))
def _gather_slab(k_cache: jax.Array, v_cache: jax.Array,
                 page_ids: jax.Array, streams: int = 2) -> jax.Array:
    """Gather pages into one contiguous slab.

    k_cache/v_cache: [layers, num_pages, kv_heads, page_size, head_dim]
    page_ids: [n] physical page indices
    returns: [layers, streams, n, kv_heads, page_size, head_dim]

    ``streams=1`` is the MLA layout: the K pool holds the whole per-token
    latent and the V pool is width-0, so block files carry one stream.
    """
    k = k_cache[:, page_ids]  # [layers, n, kvh, page, hd]
    if streams == 1:
        return k[:, None]
    v = v_cache[:, page_ids]
    return jnp.stack([k, v], axis=1)


@partial(jax.jit, donate_argnames=("k_cache", "v_cache"),
         static_argnames=("streams",))
def _scatter_slab(k_cache: jax.Array, v_cache: jax.Array, slab: jax.Array,
                  page_ids: jax.Array,
                  streams: int = 2) -> tuple[jax.Array, jax.Array]:
    """Scatter a slab back into the paged pools (donated, in-place)."""
    k_cache = k_cache.at[:, page_ids].set(slab[:, 0])
    if streams == 2:
        v_cache = v_cache.at[:, page_ids].set(slab[:, 1])
    return k_cache, v_cache


class TPUBlockCopier:
    """Moves groups of KV pages between device pools and host slabs."""

    def __init__(self, k_cache: jax.Array, v_cache: jax.Array):
        # The copier owns the cache references so scatter can donate them.
        self.k_cache = k_cache
        self.v_cache = v_cache
        layers, _, kv_heads, page_size, head_dim = k_cache.shape
        # MLA pools: V is width-0 (values live in the latent K pool), so
        # block files carry a single stream.
        self.streams = 1 if v_cache.shape[-1] == 0 else 2
        self.slab_shape = lambda n: (layers, self.streams, n, kv_heads,
                                     page_size, head_dim)
        self.dtype = k_cache.dtype
        devices = sorted(k_cache.devices(), key=lambda d: d.id)
        # Host slabs follow the pool. A pool committed to one chip (an
        # engine given ``device=``) gets its slabs on that chip — a replica
        # on chip k must not bounce its restores through device 0. An
        # uncommitted pool gets uncommitted slabs: a committed operand
        # would commit the scattered pool, and the engine's jitted steps
        # would then re-lower and recompile for the new signature (on a
        # v5e that turned one restore into minutes, PERF.md PR 22).
        # Sharded pools leave placement to the scatter program.
        pinned_to_one = len(devices) == 1 and k_cache.committed
        self._device = devices[0] if pinned_to_one else None
        self._on_tpu = devices[0].platform == "tpu"
        try:
            self._pinned_sharding = jax.sharding.SingleDeviceSharding(
                devices[0], memory_kind="pinned_host"
            )
        except (ValueError, RuntimeError, NotImplementedError) as exc:
            self._pinned_refused(devices[0], exc)

    def slab_nbytes(self, n_pages: int) -> int:
        return int(np.prod(self.slab_shape(n_pages))) * self.dtype.itemsize

    @property
    def pinned_host_active(self) -> bool:
        """True while the D2H leg routes through ``pinned_host`` memory.
        Surfaced (not just best-effort) so deployments can assert the true
        DMA path instead of silently degrading."""
        return self._pinned_sharding is not None

    def _pinned_refused(self, where, exc: Exception) -> None:
        """The runtime refused ``pinned_host``: on a TPU that is a
        mis-set-up chip, not a mode to serve in; elsewhere transfers go
        unpinned and ``pinned_host_active`` says so."""
        if self._on_tpu:
            raise RuntimeError(
                f"pinned_host memory unavailable on {where}: {exc}") from exc
        logger.warning("pinned_host memory unavailable on %s; D2H "
                       "transfers are unpinned", where)
        self._pinned_sharding = None

    def _to_pinned_host(self, x: jax.Array) -> jax.Array:
        """Route the device→host leg through pinned host memory when the
        runtime supports memory kinds (true DMA staging, the role the
        reference's cudaHostAlloc buffers play); plain transfer otherwise."""
        if self._pinned_sharding is None:
            return x
        try:
            return jax.device_put(x, self._pinned_sharding)
        except (ValueError, RuntimeError, NotImplementedError) as exc:
            self._pinned_refused(x.devices(), exc)
            return x

    def gather_to_host(self, page_ids: list[int]) -> np.ndarray:
        """Device-side page gather + one D2H transfer; returns the host slab."""
        ids = jnp.asarray(page_ids, jnp.int32)
        slab = _gather_slab(self.k_cache, self.v_cache, ids,
                            streams=self.streams)
        return np.asarray(jax.device_get(slab))

    # Cap on pages merged into one device transfer: bounds the transient
    # HBM slab (batching win saturates long before this; a job of hundreds
    # of blocks must not materialize job-sized scratch in already-pressured
    # HBM — offload runs exactly when HBM is tight).
    MAX_BATCH_PAGES = 128

    def gather_many_to_host(
        self, page_id_groups: list[list[int]]
    ) -> list[np.ndarray]:
        """Gather several page groups with few device programs/DMAs.

        Groups are merged into transfers of at most ``MAX_BATCH_PAGES``
        pages. Returns one independent contiguous host array per group
        (copies, not views — safe to hand to the I/O engine)."""
        out: list[np.ndarray] = []
        chunk: list[list[int]] = []
        chunk_pages = 0

        def flush():
            nonlocal chunk, chunk_pages
            if not chunk:
                return
            all_ids = [p for group in chunk for p in group]
            slab = _gather_slab(self.k_cache, self.v_cache,
                                jnp.asarray(all_ids, jnp.int32),
                                streams=self.streams)
            merged = np.asarray(jax.device_get(self._to_pinned_host(slab)))
            pos = 0
            for group in chunk:
                out.append(
                    np.ascontiguousarray(merged[:, :, pos:pos + len(group)])
                )
                pos += len(group)
            chunk, chunk_pages = [], 0

        for group in page_id_groups:
            if chunk and chunk_pages + len(group) > self.MAX_BATCH_PAGES:
                flush()
            chunk.append(group)
            chunk_pages += len(group)
        flush()
        return out

    def scatter_from_host(self, slab: np.ndarray, page_ids: list[int]) -> None:
        """One H2D transfer + device-side scatter into the pools."""
        self.scatter_many_from_host([(slab, page_ids)])

    def scatter_many_from_host(
        self, slabs: list[tuple[np.ndarray, list[int]]]
    ) -> None:
        """Scatter several host slabs with few device programs.

        Per-slab scatters each rewrite the cache arrays; batching turns N
        cache updates into ~1 (measured ~30× on the load path). Merged
        transfers are capped at ``MAX_BATCH_PAGES`` pages to bound the
        transient HBM slab.
        """
        chunk: list[tuple[np.ndarray, list[int]]] = []
        chunk_pages = 0

        def flush():
            nonlocal chunk, chunk_pages
            if not chunk:
                return
            all_ids: list[int] = []
            parts = []
            for slab, page_ids in chunk:
                parts.append(
                    np.asarray(slab).reshape(self.slab_shape(len(page_ids)))
                )
                all_ids.extend(page_ids)
            merged = np.concatenate(parts, axis=2)  # page axis
            device_slab = jax.device_put(merged, self._device)
            self.k_cache, self.v_cache = _scatter_slab(
                self.k_cache, self.v_cache, device_slab.astype(self.dtype),
                jnp.asarray(all_ids, jnp.int32), streams=self.streams,
            )
            chunk, chunk_pages = [], 0

        for slab, page_ids in slabs:
            if chunk and chunk_pages + len(page_ids) > self.MAX_BATCH_PAGES:
                flush()
            chunk.append((slab, page_ids))
            chunk_pages += len(page_ids)
        flush()
