"""Mini TPU serving engine: paged KV cache + prefix caching + KV events.

The in-tree stand-in for vLLM-TPU. One engine instance ≙ one "pod": it
manages a physical page pool with content-addressed prefix caching (block
hashes computed by the same ``ChunkedTokenDatabase`` as the indexer, so
engine keys ARE canonical keys — a 1:1 mapping), runs prefill/decode steps
on the paged Llama model, and emits BlockStored / BlockRemoved /
AllBlocksCleared events exactly like a real engine would, either to a ZMQ
publisher or to any callback.

Prefix caching semantics (mirroring vLLM's): on admission the prompt's
full blocks are hashed along the chain; the longest prefix of blocks
already resident is *reused* — those pages are attached to the new request
and their tokens are never recomputed, which is where the TTFT win comes
from. Evictions are LRU over unreferenced pages and emit BlockRemoved.
"""

from __future__ import annotations

import functools
import heapq
import itertools
import time
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.hma import (
    SPEC_FULL_ATTENTION,
    SPEC_MLA,
    SPEC_SINK_FULL,
    SPEC_SLIDING_WINDOW,
)
from ..core.keys import EMPTY_BLOCK_HASH
from ..core.token_processor import ChunkedTokenDatabase, TokenProcessorConfig
from ..events.model import (
    AllBlocksClearedEvent,
    BlockRemovedEvent,
    BlockStoredEvent,
    GenericEvent,
)
from ..ops.pallas_latent_prefill import per_head_expanded_keys
from ..ops.pallas_paged_attention import (
    head_dim_supported as _pallas_head_dim_supported,
)
from ..resilience.deadline import Deadline, current_deadline
from ..resilience.shedding import (
    BROWNOUT,
    PRIORITY_NORMAL,
    SHED,
    CoDelShedder,
    OverloadShedError,
)
from ..telemetry.tracing import (
    PHASE_ENQUEUE_ADMIT,
    PHASE_ENQUEUE_HASH,
    PHASE_ENQUEUE_LOOKUP,
    PHASE_REQUEST_FIRST_TOKEN,
    PHASE_STEP_COMMIT,
    PHASE_STEP_DISPATCH,
    PHASE_STEP_EMIT,
    PHASE_STEP_FETCH,
    PHASE_STEP_FINISH,
    PHASE_STEP_INPUTS,
    PHASE_STEP_OFFLOAD_POLL,
    PHASE_STEP_SCHEDULE,
    PHASE_STEP_SNAPSHOT,
    PHASE_STEP_WINDOW,
    NOOP_SPAN,
    SPAN_ENGINE_DECODE_STEP,
    EnginePhases,
    phase,
    span_event,
)
from ..utils.logging import get_logger
from .llama import (
    DRAFTING_PROGRAMS,
    LlamaConfig,
    copy_state_slot,
    init_kv_cache,
    init_kv_cache_hybrid,
    init_params,
    init_state_pool,
    pack_inputs,
    prefill_per_head,
    step_decode_pallas,
    step_decode_pallas_paged_state,
    step_decode_pallas_state,
    step_forward,
    step_forward_hybrid,
    step_decode_pallas_pools,
    step_prefill_pallas_pools,
    step_forward_paged_state,
    step_forward_state,
    step_prefill_pallas,
    step_prefill_pallas_paged_state,
    step_prefill_pallas_state,
    step_program,
    step_ragged,
)
from .state_pool import StatePool

logger = get_logger("models.engine")

EventSink = Callable[[list[GenericEvent]], None]

# A step program and its form for a model with linear layers (the state pool
# behind the page pools, the rows' slots behind the per-step arrays).
_WITH_STATE = {
    step_forward: step_forward_state,
    step_decode_pallas: step_decode_pallas_state,
    step_prefill_pallas: step_prefill_pallas_state,
}


# The same for a model whose state layers keep pages too (two mixers a
# layer): the page pools ride in the state (``llama.with_pages_in_state``).
_WITH_PAGES_IN_STATE = {
    step_forward: step_forward_paged_state,
    step_decode_pallas: step_decode_pallas_paged_state,
    step_prefill_pallas: step_prefill_pallas_paged_state,
}


def _with_state(program, paged=False):
    forms = _WITH_PAGES_IN_STATE if paged else _WITH_STATE
    if isinstance(program, functools.partial):
        return functools.partial(forms[program.func], **program.keywords)
    return forms[program]


# Device (as an engine was given it; None: JAX's default) → the count that
# numbers every step program this process sends to it, whichever engine
# sends it, traced or not. ``next()`` on it is one C call: no lock.
_launch_counts: dict = {}


@dataclass
class _Unread:
    """A step program whose tokens the host has not read yet."""

    picked: Any    # int32 [padded (+ the model's counters)], on the device
    rows: list     # the requests it ran, in the order of its rows
    padded: int    # its rows with the padding: the counters lie behind them
    tokens: int    # the real tokens it ran (what the counters are counts of)
    launch: int    # its ordinal on the device (``_launch_counts``)
    program: str   # "decode" or "prefill"
    host: Optional[np.ndarray] = None  # ``picked`` on the host, once read

    def row_of(self, req) -> int:
        """``req``'s row, or -1 (by identity: a ``Request`` compares by
        value, field for field)."""
        return next((i for i, row in enumerate(self.rows) if row is req), -1)


def _resolve_kv_dtype(name: str):
    """EngineConfig.kv_cache_dtype string → jnp dtype (loud on typos)."""
    table = {
        "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
        "f8_e4m3": jnp.float8_e4m3fn, "float8_e4m3fn": jnp.float8_e4m3fn,
    }
    if name not in table:
        raise ValueError(
            f"kv_cache_dtype must be one of {sorted(table)}, got {name!r}")
    return table[name]


@dataclass
class EngineConfig:
    model: LlamaConfig = field(default_factory=LlamaConfig.tiny)
    num_pages: int = 512
    # Hybrid models: size of the SWA group's separate page pool (None →
    # the model's ``window_pages``, else num_pages). SWA pages are allocated just-in-time and reclaimed as
    # slots fall out of the window, so per-request peak demand is
    # window + prefill-chunk pages (+ the decode page), not prompt length
    # — the memory win of hybrid attention.
    num_swa_pages: Optional[int] = None
    max_pages_per_seq: int = 64
    max_batch: int = 8
    hash_seed: str = ""
    model_name: str = "tiny-llama"
    pod_identifier: str = "pod-0"
    # Decode attention backend: None = auto (Pallas flash-decode on TPU,
    # XLA reference elsewhere); True forces Pallas (interpreted on CPU);
    # False forces the XLA path.
    use_pallas_decode: Optional[bool] = None
    # Prefill attention backend: None = auto — the Pallas flash-prefill
    # kernel whenever the Pallas backend is active (TPU + aligned
    # head_dim), XLA paged attention otherwise. False forces XLA prefill;
    # True insists and warns if the Pallas backend is inactive. Pallas
    # prefill and decode serve every accepted cell of PERF_LEDGER.jsonl;
    # the ledger has no pair against the XLA path.
    use_pallas_prefill: Optional[bool] = None
    # Fuse QKV (and gate+up, MLA input) projections into single wider
    # matmuls at startup (models.llama.fuse_params). None = auto: fused
    # where llama.fuse_profitable says so, a gate on the PER-SHARD width
    # (hidden_size / tp >= 4096). It puts mistral-7b-l16 on the fused
    # path and qwen3-1.7b on the unfused; the ledger has no pair across
    # it. Under a tp mesh the engine fuses in
    # the per-rank INTERLEAVED column order (LlamaConfig.fused_interleave
    # = tp) so the fused leaves stay Megatron-column-shardable; auto
    # additionally requires the projection widths to divide tp and
    # skips MLA-under-mesh and pp serving (those stay unfused; explicit
    # True raises there). When sharing one params tree across
    # single-shard pods, pass it through llama.maybe_fuse_params FIRST
    # (profit-gated; a no-op on a fused tree) — otherwise each engine
    # materializes its own fused weight copy; a tp engine re-layouts a
    # pre-fused canonical tree into its interleaved order itself.
    # Checkpoints store the canonical unfused layout either way
    # (models.checkpoint unfuses on save).
    fuse_projections: Optional[bool] = None
    # Paged KV pool element type: None (default — the model's dtype),
    # "bf16", or "f8_e4m3" (float8_e4m3fn). fp8 halves the KV bytes a
    # decode step reads and a token holds, at ~2^-3 relative quantization
    # error per element; it has no line on the ledger. e4m3's per-element
    # exponent needs no scale arrays: the cache keeps its layout,
    # scatter casts on write, attention upcasts on read,
    # offload/checkpoint move 1-byte elements (the store fingerprint's
    # dtype field separates fp8 stores from bf16). fp8 decode rides the
    # merged flash kernel's quantized arm (flat whole-page 1-byte DMAs,
    # needs kv_heads*page_size % 32 == 0); fp8 prefill runs XLA
    # attention — TTFT-bound deployments should keep bf16. Composes
    # with mesh-sharded serving (tp/dp/sp/pp: the cast is elementwise
    # and pools shard exactly like bf16 — token-identity pinned in
    # tests/test_kv_fp8.py); MLA latents refuse fp8 (absorbed-attention
    # latents are more quantization-sensitive).
    kv_cache_dtype: Optional[str] = None
    # Batch rows co-scheduled per flash-decode program (merged-heads
    # kernel): each round issues every row's page DMAs together and the
    # pipeline fills once per program instead of once per batch item.
    # 1 = one program per batch item, which is what every cell runs; a
    # value above 1 has no line on the ledger. Single-shard Pallas decode
    # only; ignored under tp sharding and on the XLA backend.
    decode_batch_rows: int = 1
    # Chunked prefill: the uncached suffix is processed in chunks of at
    # most this many tokens (vLLM-style), bounding per-step activation
    # memory for long prompts. Must be a multiple of the page size.
    max_prefill_tokens: int = 512
    # Ragged single-kernel attention: pack the step's admitted prefill
    # chunk and every active decode row into ONE flat-token-axis dispatch
    # (ops.pallas_paged_attention.pallas_paged_ragged_attention) instead
    # of the batch-1 prefill call plus the pad-to-max_batch decode call.
    # A decode row is a 1-token ragged row, a prefill chunk a longer one;
    # per-sequence padding disappears (the flat axis pads only to a
    # power-of-two token bucket) and mixed traffic stops paying two
    # kernel pipelines' fill/drain per step. Single-shard, non-hybrid
    # only — other configurations warn once and keep the
    # padded two-kernel path; the same fallback serves shapes the kernel
    # cannot take (unaligned head_dim on real TPU, fp8 pages whose
    # kv_heads*page_size is not a 32 multiple). Runs interpreted on CPU.
    # Every cell runs the padded path; ragged has no line on the ledger.
    ragged_attention: bool = False
    # Engine data-plane telemetry (telemetry/engine_telemetry.py): an
    # EngineTelemetryConfig enables TTFT/ITL/TPOT histograms, KV-pool
    # gauges, per-request flight-recorder events, and the on-demand
    # jax.profiler capture surface. None (default) keeps the step path
    # free of every hook — each site costs one attribute load + branch.
    telemetry: Optional[Any] = None
    # Disaggregated serving role (offload.handoff): "both" (default —
    # monolithic pod, prefill and decode), "prefill" (prefill-only pod:
    # each chunk's full blocks commit write-through to the transfer tier
    # as they are computed, the request finishes at first token and
    # decoding happens elsewhere), or "decode" (decode-side pod:
    # ``enqueue(handoff=True)`` requests wait up to ``handoff_wait_s``
    # for transferred blocks before falling back to local prefill).
    # Non-hybrid engines only — hybrid restores are all-or-nothing and
    # cannot pull a transfer in chunk-granular rounds.
    role: str = "both"
    # Decode-side handoff patience: how long a ``handoff=True`` request
    # waits for the prefill peer's blocks to land before recomputing the
    # remainder locally. Decodes keep running the whole time (the wait
    # costs only that request's TTFT, never the running batch).
    handoff_wait_s: float = 10.0
    # CoDel-style overload shedding at admission (resilience.shedding):
    # when admission delay (enqueue → first scheduler pick) stays
    # above the target for a full interval, ``enqueue`` sheds
    # lowest-priority work first instead of letting the queue grow
    # without bound. 0 (default) disables the shedder entirely — no
    # lock, no branch cost beyond one attribute load.
    shed_target_delay_s: float = 0.0
    shed_interval_s: float = 0.1


@dataclass
class _BlockInfo:
    page: int
    ref_count: int = 0
    last_used: float = 0.0
    parent_hash: int = 0
    tokens: tuple[int, ...] = ()
    # Place in ``BlockManager.blocks``' insertion order: among unreferenced
    # blocks of one ``last_used`` the one that entered first leaves first.
    entry_seq: int = 0


@dataclass
class Request:
    request_id: str
    prompt: list[int]
    max_new_tokens: int
    # runtime state
    output: list[int] = field(default_factory=list)
    pages: list[int] = field(default_factory=list)  # physical pages, logical order
    swa_pages: list[int] = field(default_factory=list)  # hybrid: group 1 pages
    # Hybrid: first logical block whose SWA page this request references
    # (earlier slots map to the garbage page — out of window at resume).
    swa_acquired_from: int = 0
    block_hashes: list[int] = field(default_factory=list)  # hash-chained, per full block
    cached_len: int = 0  # tokens skipped via prefix cache at admission
    computed_len: int = 0  # tokens with KV resident (cached + prefilled + decoded)
    # The last prompt position's float32 logits ``[vocab]``, left on the
    # device by the prefill's last chunk (``np.asarray`` fetches them).
    last_logits: Any = None
    done: bool = False
    # Continuous batching: next prompt index to prefill, or None once the
    # request is decoding. ``enqueue`` admits with this set; ``step``
    # advances one chunk at a time interleaved with decode.
    prefill_pos: Optional[int] = None
    # Deferred storage restore (enqueue path): the lookup hasn't run yet /
    # an async load is in flight. ``step`` polls the job across steps so a
    # slow restore never stalls running decodes (a synchronous restore in
    # _admit blocked them for up to the 30 s deadline).
    restore_pending: bool = False
    # enqueue() timestamp, cleared at first prefill schedule — feeds the
    # admission-delay histogram.
    enqueued_at: Optional[float] = None
    # W3C traceparent carried from the scorer (ScoreResponse.traceparent →
    # enqueue()): when set, the engine parents admission/prefill/decode
    # spans under it so one trace covers score→serve. None = no spans.
    traceparent: Optional[str] = None
    # (job_id, first_missing_block, hashes, pages, deadline, started)
    # while loading.
    restore_job: Optional[tuple] = None
    # Prompt blocks registered in the block manager on this request's
    # behalf (acquired prefix at admission, extended by
    # _commit_full_blocks). _release must treat pages past this watermark
    # as unregistered orphans — an aborted mid-prefill request's blocks
    # were never committed, and release()ing unknown hashes would silently
    # leak their pages.
    committed_blocks: int = 0
    # Decode-side handoff wait (enqueue(handoff=True) on a decode-role
    # engine): monotonic deadline until which step() holds this request's
    # local prefill, polling the transfer tier for the prefill peer's
    # blocks in re-armed deferred-restore rounds. None once settled.
    handoff_deadline: Optional[float] = None
    # End-to-end budget carried from the caller (ScoreRequest.deadline_ms
    # → enqueue(deadline_s=...), or the ambient deadline_scope at enqueue
    # time). A deferred storage restore that cannot finish inside the
    # remaining budget is skipped — recompute beats a restore whose
    # result arrives after the caller stopped waiting.
    deadline: Optional[Deadline] = None
    # resilience.shedding priority class: sheds lowest-first under
    # admission overload.
    priority: int = PRIORITY_NORMAL
    # Ground-truth audit (telemetry/audit.py): the ScoreFeedback this
    # request was routed on (duck-typed, None when the scheduler passed
    # none), the HBM prefix hit at admission, and blocks restored from
    # the storage/transfer tier since — together they decompose the
    # realized prefix outcome emitted at prefill finish.
    feedback: Any = None
    hbm_hit_blocks: int = 0
    restored_blocks: int = 0
    # A model with linear layers: the row's working slot in the state pool
    # (0: none), the blocks whose pages matched at admission (the hit is
    # cut back to the deepest snapshot among them; passing their end, the
    # prefill leaves a snapshot there for whoever shares as much), the
    # block hashes its prefill has left snapshots on, not yet announced,
    # and the one of them its periodic checkpoint stands on: the next
    # periodic checkpoint takes that one's slot (``_plan_snapshots``).
    state_slot: int = 0
    page_hit_blocks: int = 0
    snapshots: list[int] = field(default_factory=list)
    checkpoint: Optional[int] = None
    # A model with a prediction module: the module's draft of the token
    # after ``output[-1]``, which the next decode step verifies.
    draft: int = 0

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output)


class BlockManager:
    """Physical page pool with content-addressed prefix caching.

    Page 0 is the reserved garbage page (see ``ops.kv_pages``). Full blocks
    are indexed by chain hash; unreferenced pages stay cached until LRU
    eviction reclaims them.
    """

    def __init__(self, cfg: EngineConfig, processor: ChunkedTokenDatabase,
                 event_sink: Optional[EventSink] = None, group_idx: int = 0,
                 num_pages: Optional[int] = None,
                 spec_kind: Optional[str] = None,
                 spec_window: Optional[int] = None):
        self.cfg = cfg
        self.processor = processor
        self.event_sink = event_sink
        self.group_idx = group_idx
        pool = num_pages if num_pages is not None else cfg.num_pages
        self.num_pages = pool
        self.free_pages: list[int] = list(range(1, pool))  # 0 reserved
        self.blocks: dict[int, _BlockInfo] = {}  # block_hash → info
        self.page_to_hash: dict[int, int] = {}
        # Eviction order, kept and not found: a heap of (last_used,
        # entry_seq, hash) with one entry pushed each time a block's
        # ref_count falls to 0. An entry goes stale when its block is
        # referenced again, evicted or cleared; stale entries are thrown
        # away as they are popped, and the heap is rebuilt from ``blocks``
        # once they outnumber the ``_idle`` (ref_count == 0) blocks.
        self._idle_heap: list[tuple[float, int, int]] = []
        self._idle = 0
        self._entry_seq = 0
        # Lifetime eviction count: a plain int (one add per eviction) that
        # telemetry turns into kvtpu_engine_kv_pool_evictions_total deltas.
        self.evictions = 0
        # Optional eviction tap: called with the victim's age (seconds
        # since last use) — the working-set tracker's eviction-age
        # histogram (engine.attach_workingset wires it).
        self.on_evict: Optional[Callable[[float], None]] = None
        # The owning engine's EnginePhases (None = phases off).
        self.phases: Optional[EnginePhases] = None
        # The engine's StatePool where the model keeps sequence states
        # beside these pages: snapshots stand on blocks, so an eviction
        # here takes the snapshots on or after its victims, in its batch.
        self.state_pool: Optional[StatePool] = None
        # A two-pool engine's window pool's manager, on the global pool's:
        # ``pool_stats`` then tells both. ``reclaimed`` counts, on the
        # window pool's own, the pages a request gave back behind its
        # window (``MiniEngine._swa_reclaim``).
        self.window_manager: Optional["BlockManager"] = None
        self.reclaimed = 0
        if spec_kind is not None:
            self.spec_kind = spec_kind
            self.spec_window = spec_window
        else:
            # KV-cache spec advertised in events. A unified (single-group)
            # pool is sliding_window only when every layer is SWA; any
            # full-attention layer makes full retention the controlling
            # constraint. Hybrid engines construct one manager per group
            # with explicit specs instead. MLA pools advertise
            # mla_attention (events.go:34): block payloads are latents,
            # not per-head K/V, so consumers must not mix them with
            # full_attention blocks of the same tokens.
            mcfg = cfg.model
            if mcfg.is_mla:
                self.spec_kind = SPEC_MLA
                self.spec_window = None
            elif (
                mcfg.sliding_window is not None
                and set(mcfg.swa_layers) >= set(range(mcfg.num_layers))
            ):
                # Uniform SWA; with sinks it is the reference's
                # sink_full_attention kind (events.go:40).
                self.spec_kind = (SPEC_SINK_FULL if mcfg.attention_sinks
                                  else SPEC_SLIDING_WINDOW)
                self.spec_window = mcfg.sliding_window
            else:
                self.spec_kind = SPEC_FULL_ATTENTION
                self.spec_window = None

    # -- accounting --

    def num_free(self) -> int:
        return len(self.free_pages)

    def num_cached_blocks(self) -> int:
        return len(self.blocks)

    def pool_stats(self) -> dict:
        """Occupancy snapshot for telemetry/kvdiag: cheap plain-int reads.

        ``orphan_pages`` are pages neither free nor registered as hashed
        blocks — held by in-flight requests (partial tails, decode room)
        and not reusable as prefix cache until commit.
        """
        free = len(self.free_pages)
        cached_pages = len(self.page_to_hash)
        return {
            "total_pages": self.num_pages,
            "free_pages": free,
            "cached_blocks": len(self.blocks),
            "cached_pages": cached_pages,
            # Page 0 is the reserved garbage page.
            "orphan_pages": max((self.num_pages - 1) - free - cached_pages, 0),
            "evictions": self.evictions,
            **(self.state_pool.stats() if self.state_pool is not None
               else {}),
            **({"window_evictions": self.window_manager.evictions,
                "window_reclaimed": self.window_manager.reclaimed,
                "window_free": len(self.window_manager.free_pages)}
               if self.window_manager is not None else {}),
        }

    def _emit(self, events: list[GenericEvent]) -> None:
        if self.event_sink is not None and events:
            with phase(self.phases, PHASE_STEP_EMIT) as sp:
                sp.set_attribute("events", len(events))
                self.event_sink(events)

    # -- prefix cache --

    def match_prefix(self, block_hashes: Sequence[int]) -> list[int]:
        """Longest resident prefix: returns the pages for matched blocks."""
        pages = []
        for h in block_hashes:
            info = self.blocks.get(h)
            if info is None:
                break
            pages.append(info.page)
        return pages

    def _reference(self, info: _BlockInfo, now: float) -> None:
        if info.ref_count == 0:
            self._idle -= 1  # its heap entry is stale from here on
        info.ref_count += 1
        info.last_used = now

    def acquire_prefix(self, block_hashes: Sequence[int]) -> list[int]:
        """Reference the longest resident prefix; bumps ref counts."""
        pages = self.match_prefix(block_hashes)
        now = time.monotonic()
        for h in block_hashes[: len(pages)]:
            self._reference(self.blocks[h], now)
        return pages

    def try_acquire_blocks(self, block_hashes: Sequence[int]) -> Optional[list[int]]:
        """All-or-nothing reference of specific blocks (SWA trailing-window
        acquisition: the needed set is a window, not a prefix)."""
        infos = []
        for h in block_hashes:
            info = self.blocks.get(h)
            if info is None:
                return None
            infos.append(info)
        now = time.monotonic()
        for info in infos:
            self._reference(info, now)
        return [info.page for info in infos]

    def allocate_page(self) -> Optional[int]:
        """Pop a free page, evicting LRU unreferenced blocks if needed."""
        pages = self.allocate_pages(1)
        return pages[0] if pages else None

    def allocate_pages(self, n: int) -> list[int]:
        """``n`` pages as ``n`` ``allocate_page`` calls would hand them out:
        free pages first (from the end of ``free_pages``), then the pages
        of as many LRU victims as are still needed, told to the sink in
        one batch. A shorter list means the pool ran dry: what was evicted
        stays evicted and reported, and the caller hands the pages back
        (``free_pages``).
        """
        free = self.free_pages
        had = len(free)
        if n <= had:
            pages = free[had - n:][::-1]
            del free[had - n:]
            return pages
        self._evict(n - had)  # the victims' pages follow the free ones
        pages = free[:had][::-1] + free[had:]
        free.clear()
        return pages

    def _evict(self, n: int) -> None:
        """Evict up to ``n`` unreferenced blocks, least recently used first
        (ties: first into ``blocks``); their pages go onto ``free_pages``
        in that order and one BlockRemoved names them all."""
        heap, blocks, free = self._idle_heap, self.blocks, self.free_pages
        victims: list[int] = []
        last_uses: list[float] = []
        while len(victims) < n and heap:
            last_used, seq, h = heapq.heappop(heap)
            info = blocks.get(h)
            if (info is None or info.entry_seq != seq or info.ref_count
                    or info.last_used != last_used):
                continue  # stale: referenced again, or already gone
            del blocks[h]
            self.page_to_hash.pop(info.page, None)
            free.append(info.page)
            victims.append(h)
            last_uses.append(last_used)
        if not victims:
            return
        self._idle -= len(victims)
        self.evictions += len(victims)
        if self.on_evict is not None:
            now = time.monotonic()
            for last_used in last_uses:
                try:
                    self.on_evict(now - last_used)
                except Exception:  # pragma: no cover  # lint: allow-swallow
                    pass
        # Must carry the same group tag as the BlockStored that created the
        # entries, or the index's entry-match eviction is a silent no-op.
        events: list[GenericEvent] = [
            BlockRemovedEvent(block_hashes=victims, group_idx=self.group_idx)
        ]
        if self.state_pool is not None:
            self.state_pool.drop_dependents(victims)
            events += self.state_pool.drain()
        self._emit(events)

    def commit_blocks(
        self,
        block_hashes: Sequence[int],
        pages: Sequence[int],
        tokens_per_block: Sequence[Sequence[int]],
        parent_of_first: int,
    ) -> list[int]:
        """Register newly computed full blocks in the prefix cache.

        Returns the canonical page per block: when a block's content is
        already resident (recomputed duplicate), the existing page wins and
        the redundant page is freed — the KV bytes are identical.

        Emits one BlockStored event per *contiguous run* of newly stored
        blocks, each with its own correct parent hash, so the indexer's
        chained request-key recomputation never spans a gap (a duplicate in
        the middle must not fuse two runs into one false chain).
        """
        now = time.monotonic()
        canonical_pages: list[int] = []
        events: list[GenericEvent] = []
        run_hashes: list[int] = []
        run_tokens: list[int] = []
        run_parent = parent_of_first
        parent = parent_of_first

        def flush_run():
            nonlocal run_hashes, run_tokens
            if run_hashes:
                events.append(
                    BlockStoredEvent(
                        block_hashes=list(run_hashes),
                        tokens=list(run_tokens),
                        parent_hash=run_parent,
                        block_size=self.processor.block_size,
                        group_idx=self.group_idx,
                        kv_cache_spec_kind=self.spec_kind,
                        kv_cache_spec_sliding_window=self.spec_window,
                    )
                )
            run_hashes, run_tokens = [], []

        for h, page, toks in zip(block_hashes, pages, tokens_per_block):
            existing = self.blocks.get(h)
            if existing is None:
                self._entry_seq += 1
                self.blocks[h] = _BlockInfo(
                    page=page, ref_count=1, last_used=now,
                    parent_hash=parent, tokens=tuple(toks),
                    entry_seq=self._entry_seq,
                )
                self.page_to_hash[page] = h
                if not run_hashes:
                    run_parent = parent
                run_hashes.append(h)
                run_tokens.extend(toks)
                canonical_pages.append(page)
            else:
                # Recomputed duplicate: adopt the resident page, free ours.
                self._reference(existing, now)
                if page != existing.page:
                    self.free_pages.append(page)
                canonical_pages.append(existing.page)
                flush_run()
            parent = h
        flush_run()
        self._emit(events)
        return canonical_pages

    def release(self, block_hashes: Sequence[int], orphan_pages: Sequence[int]) -> None:
        """Drop a finished request's references; free unhashed pages."""
        heap = self._idle_heap
        for h in block_hashes:
            info = self.blocks.get(h)
            if info is not None and info.ref_count > 0:
                info.ref_count -= 1
                if info.ref_count == 0:
                    self._idle += 1
                    heapq.heappush(
                        heap, (info.last_used, info.entry_seq, h))
        self.free_pages.extend(orphan_pages)
        if len(heap) > 2 * self._idle + 64:
            # More stale entries than live ones: start over from the blocks.
            heap[:] = [(info.last_used, info.entry_seq, h)
                       for h, info in self.blocks.items()
                       if info.ref_count == 0]
            heapq.heapify(heap)

    def clear(self, emit: bool = True) -> None:
        """Drop the whole prefix cache (weight rollout) and emit the reset.

        AllBlocksCleared is pod-wide (clears every group at the index), so
        a hybrid engine emits it from one manager only (``emit=False`` on
        the other).
        """
        for info in self.blocks.values():
            self.free_pages.append(info.page)
        self.blocks.clear()
        self.page_to_hash.clear()
        self._idle_heap.clear()
        self._idle = 0
        if self.state_pool is not None:
            self.state_pool.clear()
        if emit:
            self._emit([AllBlocksClearedEvent()])


class MiniEngine:
    """Single-pod batched serving engine over the paged Llama model."""

    def __init__(
        self,
        cfg: Optional[EngineConfig] = None,
        event_sink: Optional[EventSink] = None,
        params=None,
        seed: int = 0,
        offload_spec=None,
        mesh=None,
        device=None,
    ):
        self.cfg = cfg or EngineConfig()
        mcfg = self.cfg.model
        # Replica placement: ``device`` pins this engine's weights, pools
        # and every step input to one chip, so a host runs one replica per
        # chip in one process. None keeps JAX's default device; a mesh
        # places by sharding instead.
        if device is not None and mesh is not None:
            raise ValueError("pass a device or a mesh, not both")
        self._device = device
        # Tensor-parallel serving: with a mesh carrying a ``tp`` axis, the
        # params take the Megatron layout and the KV pools shard their
        # kv-heads axis (MLA: heads shard instead and the single shared
        # latent pool replicates); the same jitted forwards then run SPMD
        # (XLA inserts the per-block all-reduces). Paging stays host-side
        # and replicated — identical on every shard.
        self.mesh = mesh
        self._tp = 1
        self._sp = 1
        self._pp = 1
        if mesh is not None:
            from ..parallel.serve import mesh_tp_size, validate_tp_config

            # MLA shards on the head axis (wq/w_uk/w_uv/wo split per
            # head, latent projections + latent cache replicated) —
            # validate_tp_config checks the per-family divisibility.
            validate_tp_config(mcfg, mesh)
            self._tp = mesh_tp_size(mesh)
            # Sequence parallelism for prefill: with an ``sp`` mesh axis,
            # chunk tokens are placed sharded on the sequence dim and XLA
            # propagates — per-token projections/MLP/attention-q compute
            # splits sp-ways (one long prompt's prefill FLOPs spread over
            # sp chips), with the collectives (scatter all-gathers, one
            # logits all-reduce) derived from the shardings. Verified
            # bit-exact vs single-device and predominantly seq-sharded in
            # the compiled HLO (tests/test_sp_serve.py). Decode (seq=1)
            # is unaffected.
            self._sp = mesh.shape.get("sp", 1)
            # Pipeline-parallel serving: layer blocks + the layer axis of
            # the paged caches shard over ``pp``; prefill chunks and
            # decode batches stream through the stages as microbatches
            # (parallel.pp_serve). v1 scope: dense models, XLA attention,
            # no tp on the same mesh, single-token decode.
            self._pp = mesh.shape.get("pp", 1)
            if self._pp > 1:
                from ..parallel.pp_serve import validate_pp_serve_config

                if self._sp > 1:
                    raise NotImplementedError(
                        "pp serving does not yet compose with sp on one "
                        "mesh (tp composes: Megatron within each stage)")
                if self.cfg.max_batch % self._pp == 0:
                    self._pp_decode_mb = self._pp
                else:
                    # Surface the idle stages instead of silently running
                    # the unpipelined M=1 schedule (same policy as the sp
                    # divisibility warning below).
                    logger.warning(
                        "max_batch=%d does not divide by pp=%d: decode "
                        "runs unpipelined (one microbatch; %d of %d "
                        "stages idle each tick) — size max_batch to a "
                        "pp multiple", self.cfg.max_batch, self._pp,
                        self._pp - 1, self._pp)
                    self._pp_decode_mb = 1
                validate_pp_serve_config(mcfg, mesh, self._pp_decode_mb,
                                         self.cfg.max_batch)
            if self._sp > 1 and mcfg.page_size % self._sp != 0:
                # Chunk buckets are 2^k × page_size; a chunk shards only
                # when sp divides its bucket. sp ∤ page_size means short
                # chunks (and, for non-power-of-two sp, EVERY chunk) run
                # unsharded — surface it instead of silently idling chips.
                logger.warning(
                    "sp=%d does not divide page_size=%d: prefill chunks "
                    "whose bucketed length is not a multiple of sp run "
                    "unsharded (non-power-of-two sp never shards)",
                    self._sp, mcfg.page_size)
        if self.cfg.max_pages_per_seq * self.cfg.max_batch > self.cfg.num_pages:
            logger.warning("page pool smaller than worst-case demand; requests may stall")
        self.processor = ChunkedTokenDatabase(
            TokenProcessorConfig(
                block_size_tokens=mcfg.page_size, hash_seed=self.cfg.hash_seed
            )
        )
        # Hybrid (mixed full/SWA layers): two cache groups with separate
        # page pools and block managers; events carry group tags + specs so
        # the indexer's GroupCatalog and HybridAwareScorer see the real
        # layout (reference hma.go:32-66 from the producer side).
        self.hybrid = mcfg.is_hybrid
        with jax.default_device(device):
            self.params = params if params is not None else init_params(
                jax.random.PRNGKey(seed), mcfg
            )
        if device is not None:
            # Commit the (possibly shared) tree to this replica's chip: a
            # no-op when it already lives there, a device-to-device copy
            # otherwise. Committed weights and pools are what make the
            # jitted steps run here rather than on device 0.
            self.params = jax.device_put(self.params, device)
        self.requests: dict[str, Request] = {}
        self._running: list[str] = []
        self.swa_manager: Optional[BlockManager] = None
        kv_dtype = (mcfg.dtype if self.cfg.kv_cache_dtype is None
                    else _resolve_kv_dtype(self.cfg.kv_cache_dtype))
        self._kv_dtype = kv_dtype
        self._fp8_cache = jnp.dtype(kv_dtype).itemsize == 1
        if self._fp8_cache:
            if mcfg.is_mla:
                raise ValueError(
                    "kv_cache_dtype=f8_e4m3 does not support MLA latent "
                    "pools yet (absorbed-attention latents are more "
                    "quantization-sensitive; keep bf16); an indexer's key "
                    "stream shares the pool's type and stays bf16 with it")
        if mcfg.is_dsa:
            # A page of this model is two slabs (the latent and the
            # indexer's key) under one id; what cannot carry both refuses
            # here rather than serve half a page.
            if offload_spec is not None:
                raise ValueError(
                    "a model with an indexer keeps two streams a page "
                    f"(latent {mcfg.kv_cache_head_dim} + index key "
                    f"{mcfg.index_head_dim} lanes); the storage tier's "
                    "blocks hold one, so a restored block would come back "
                    "without its index keys: serve it without an offload "
                    "spec")
            if mesh is not None:
                raise ValueError(
                    "learned sparse attention (index_topk) is served on "
                    "one device: selection is not sharded over a mesh")
        self.state_pool: Optional[StatePool] = None
        self.state: tuple = ()
        if mcfg.linear_layers:
            # A sequence of this model is pages and a state; what cannot
            # carry both refuses here rather than serve half of it.
            for unfit, why in (
                    (offload_spec is not None,
                     "an offload spec (the storage tier's blocks are pages; "
                     "a restored prefix would come back without its state)"),
                    (mesh is not None,
                     "a mesh (the state pool is not sharded)"),
                    (self.cfg.ragged_attention,
                     "ragged_attention (the recurrence is served by the "
                     "padded step programs)"),
                    (self._fp8_cache,
                     "an fp8 cache (the states are float32)"),
                    (mcfg.state_slots <= self.cfg.max_batch,
                     f"state_slots {mcfg.state_slots} for max_batch "
                     f"{self.cfg.max_batch} (every running row holds a "
                     f"working slot, and a snapshot needs one more)")):
                if unfit:
                    raise ValueError(
                        "a model with linear layers keeps a state a "
                        "sequence beside its pages and is not served "
                        "with " + why)
            self.state_pool = StatePool(mcfg.state_slots, mcfg.page_size)
            with jax.default_device(device):
                self.state = init_state_pool(mcfg)
            if device is not None:
                self.state = jax.device_put(self.state, device)
        if self.hybrid:
            num_swa = (self.cfg.num_swa_pages or mcfg.window_pages
                       or self.cfg.num_pages)
            self.block_manager = BlockManager(
                self.cfg, self.processor, event_sink, group_idx=0,
                spec_kind=SPEC_FULL_ATTENTION, spec_window=None,
            )
            self.swa_manager = BlockManager(
                self.cfg, self.processor, event_sink, group_idx=1,
                num_pages=num_swa, spec_kind=SPEC_SLIDING_WINDOW,
                spec_window=mcfg.sliding_window,
            )
            # The one manager a caller reads (``pool_stats``) tells the
            # window pool's counts with its own.
            self.block_manager.window_manager = self.swa_manager
            with jax.default_device(device):
                pools = init_kv_cache_hybrid(mcfg, self.cfg.num_pages,
                                             num_swa, dtype=kv_dtype)
        else:
            self.block_manager = BlockManager(self.cfg, self.processor, event_sink)
            self.block_manager.state_pool = self.state_pool
            with jax.default_device(device):
                pools = init_kv_cache(mcfg, self.cfg.num_pages,
                                      dtype=kv_dtype) + (None, None)
        if device is not None:
            pools = jax.device_put(pools, device)
        self.k_cache, self.v_cache, self.k_swa, self.v_swa = pools

        fuse = self.cfg.fuse_projections
        # Fusion composes with tp/dp/sp meshes via the per-rank
        # interleaved column layout (fused_interleave = tp below). Two
        # mesh modes stay unfused: MLA (the fused input block mixes
        # head-sharded and replicated columns — no uniform interleave
        # shards that) and pp (the stacked-layer pspec derivation only
        # covers the canonical layout).
        fuse_mesh_blocked = mesh is not None and (mcfg.is_mla
                                                  or self._pp > 1)
        if fuse is None:
            from .llama import fuse_profitable

            # Width-divisibility for the interleave needs no extra gate
            # here: validate_tp_config (above) already requires every
            # projection width to divide tp — the unfused Megatron
            # shards have the identical constraint. The profit gate sees
            # per-shard widths: tp divides each rank's matmul columns, so
            # a model above the crossover at tp=1 can sit below it here.
            fuse = (fuse_profitable(mcfg, tp=self._tp)
                    and not fuse_mesh_blocked)
        if fuse and fuse_mesh_blocked:
            raise ValueError(
                "fuse_projections=True is incompatible with "
                + ("MLA under a mesh (head-sharded and replicated "
                   "columns cannot interleave uniformly)"
                   if mcfg.is_mla else
                   "pp serving (stacked layers keep the canonical "
                   "layout)"))
        if fuse:
            from .llama import fuse_params, unfuse_params

            if self._tp > 1:
                # Interleave the fused columns per tp rank so the
                # Megatron uniform column split hands each shard its
                # local fused block; the forward's split sites consult
                # cfg.fused_interleave (checkpoint save canonicalizes
                # back to the unfused layout). A COPY of the engine
                # config carries it — the caller's object is not
                # mutated.
                if "w_qkv" in self.params["layers"][0]:
                    # A shared pre-fused tree (maybe_fuse_params) is in
                    # CANONICAL column order; re-layout it into this
                    # engine's interleaved order (fuse_params below is
                    # a no-op on fused keys and would leave the split
                    # sites silently permuting q/k/v).
                    self.params = unfuse_params(self.params, mcfg)
                mcfg = dataclasses.replace(mcfg,
                                           fused_interleave=self._tp)
                self.cfg = dataclasses.replace(self.cfg, model=mcfg)
            self.params = fuse_params(self.params, mcfg)

        if mesh is not None and self._pp > 1:
            from ..parallel.pp_serve import shard_pp_state

            # self.params becomes the STACKED layer tree (layer axis over
            # pp); checkpoint save unstacks back to the canonical layout.
            self.params, self.k_cache, self.v_cache = shard_pp_state(
                mesh, mcfg, self.params, self.k_cache, self.v_cache)
        elif mesh is not None:
            from ..parallel.serve import shard_engine_params, shard_kv_pool

            self.params = shard_engine_params(mesh, self.params)
            self.k_cache, self.v_cache = shard_kv_pool(
                mesh, self.k_cache, self.v_cache)
            if self.hybrid:
                self.k_swa, self.v_swa = shard_kv_pool(
                    mesh, self.k_swa, self.v_swa)

        # Resolve the decode attention backend once (the platform cannot
        # change over the engine's lifetime).
        use_pallas = self.cfg.use_pallas_decode
        dev0 = (device if device is not None
                else mesh.devices.flat[0] if mesh is not None
                else jax.devices()[0])
        on_tpu = dev0.platform == "tpu"
        # Pallas kernels compile through Mosaic on a TPU and run in the
        # interpreter everywhere else (the CPU tests) — never interpreted
        # on a chip. The choice is recorded in ``attention_backends``.
        interpret = not on_tpu
        if use_pallas is None:
            use_pallas = on_tpu
        if self._pp > 1:
            if self.cfg.use_pallas_decode:
                logger.warning("pp serving v1 runs the XLA attention "
                               "backend; use_pallas_decode ignored")
            use_pallas = False
        # The kernels' per-page DMA width is the cache payload width:
        # head_dim for standard/GQA attention, the latent width
        # (rank + rope + latent_pad) for absorbed MLA — which runs as the
        # kernels' kv_heads=1 multi-query case. Sink masks apply in-kernel
        # (StreamingLLM first-S positions), so neither family gates Pallas
        # off anymore; only Mosaic's 128-lane alignment does.
        kernel_width = mcfg.kv_cache_head_dim
        if use_pallas and on_tpu and not _pallas_head_dim_supported(
                kernel_width):
            # Mosaic lane-tiling constraint (see ops.pallas_paged_attention
            # .head_dim_supported); interpreter-mode tests still cover such
            # shapes, on-chip serving falls back to XLA paged attention.
            hint = (" (LlamaConfig.latent_pad aligns the latent width; "
                    "hf_loader.config_from_hf sets it for a DeepSeek "
                    "config, a LlamaConfig built by hand sets its own)"
                    if mcfg.is_mla else "")
            logger.warning(
                "cache payload width %d is not 128-aligned: Pallas "
                "paged attention cannot compile on TPU, using XLA "
                "paged attention%s", kernel_width, hint)
            use_pallas = False
        fp8_cache = self._fp8_cache
        if fp8_cache and use_pallas:
            # fp8 rides the merged-heads decode kernel's quant arm (flat
            # whole-page [kvh*ps, hd] DMAs + in-VMEM upcast), which needs
            # kv_heads > 1 and kv_heads*page_size % 32 == 0 for Mosaic's
            # 8-bit tiling; other shapes fall back to XLA attention.
            # Under tp the kernel runs per shard on kv_heads/tp local
            # heads (validate_tp_config guarantees divisibility), so the
            # gate must check the LOCAL shape — the kernel re-validates
            # per shard and would raise at serve time otherwise.
            local_kvh = mcfg.kv_cache_heads // self._tp
            if local_kvh <= 1 or (local_kvh * mcfg.page_size) % 32:
                logger.warning(
                    "fp8 cache shape (kv_heads=%d/tp=%d, page_size=%d)"
                    " cannot ride the quantized flash-decode kernel; "
                    "using XLA attention",
                    mcfg.kv_cache_heads, self._tp, mcfg.page_size)
                use_pallas = False
        if self.hybrid and mesh is not None:
            # A two-pool (window + global) model's kernel forms
            # (``llama.step_decode_pallas_pools``) are not sharded: under a
            # mesh it steps through the XLA grouped forward over both pools.
            use_pallas = False
        rows = max(1, self.cfg.decode_batch_rows)
        if mcfg.kv_cache_heads == 1:
            # The multi-row path rides the merged-heads kernel, which the
            # wrapper only engages for kv_heads > 1 (MLA/MQA pools run the
            # per-head grid) — clamp instead of crashing, matching the
            # knob's documented ignore-when-unavailable behavior.
            rows = 1
        if use_pallas:
            # Under tp the kernels run per-shard over the kv-heads
            # sharding via shard_map (the decode grid is per-kv-head
            # independent, so no cross-shard traffic in attention itself).
            pallas_mesh = mesh if self._tp > 1 else None
            if pallas_mesh is not None:
                rows = 1  # sharded path keeps one row per program
            self._decode_forward = functools.partial(
                step_decode_pallas, interpret=interpret,
                mesh=pallas_mesh, batch_rows=rows,
            )
            if self.hybrid:
                self._decode_forward = functools.partial(
                    step_decode_pallas_pools, interpret=interpret,
                    batch_rows=rows)
        else:
            pallas_mesh = None
            self._decode_forward = step_forward
        # Prefill backend is independent of decode: auto (None) follows
        # the Pallas backend's platform/head-dim gating
        # (EngineConfig.use_pallas_prefill).
        # Auto engages only on real TPU: interpret-mode flash prefill on
        # CPU is orders slower than XLA with no fidelity gain (tests that
        # want it opt in with use_pallas_prefill=True).
        prefill_pallas = (use_pallas and on_tpu
                          if self.cfg.use_pallas_prefill is None
                          else self.cfg.use_pallas_prefill)
        if fp8_cache and prefill_pallas:
            # The prefill kernel's per-head grid DMAs [page_size, hd]
            # sub-slices, misaligned for 8-bit tiling — fp8 prefill runs
            # XLA attention (gathers 1-byte pages, upcasts on read). fp8
            # trades prefill kernel speed for decode bandwidth + 2x KV
            # capacity; TTFT-bound deployments should keep bf16.
            logger.warning(
                "kv_cache_dtype=f8_e4m3: flash prefill unavailable "
                "(8-bit DMA tiling); using XLA prefill")
            prefill_pallas = False
        if prefill_pallas and use_pallas:
            self._prefill_forward = functools.partial(
                step_prefill_pallas, interpret=interpret, mesh=pallas_mesh
            )
            if self.hybrid:
                self._prefill_forward = functools.partial(
                    step_prefill_pallas_pools, interpret=interpret)
        else:
            if self.cfg.use_pallas_prefill and not use_pallas:
                logger.warning(
                    "use_pallas_prefill=True ignored: the Pallas backend is "
                    "inactive (platform/head-dim/mesh gating above); using "
                    "XLA prefill")
            self._prefill_forward = step_forward
        if self.hybrid:
            # Without the kernels a two-pool model's steps and chunks run
            # the XLA grouped forward over both pools (the form the CPU
            # tests hold the kernel forms to).
            if self._decode_forward is step_forward:
                self._decode_forward = step_forward_hybrid
            if self._prefill_forward is step_forward:
                self._prefill_forward = step_forward_hybrid
        if self.state_pool is not None:
            self._decode_forward = _with_state(
                self._decode_forward, bool(mcfg.parallel_layers))
            self._prefill_forward = _with_state(
                self._prefill_forward, bool(mcfg.parallel_layers))
        if self._pp > 1:
            from ..parallel.pp_serve import make_pp_serve_forward

            # Prefill runs per request (batch 1 → the sequential M=1
            # schedule); decode pads to max_batch and streams pp
            # microbatches through the stages.
            self._prefill_forward = step_program(
                make_pp_serve_forward(mesh, mcfg, self.params,
                                      microbatches=1), ("last_only",))
            self._decode_forward = (
                self._prefill_forward if self._pp_decode_mb == 1
                else step_program(make_pp_serve_forward(
                    mesh, mcfg, self.params,
                    microbatches=self._pp_decode_mb)))

        # Ragged single-kernel scheduling (EngineConfig.ragged_attention):
        # resolve eligibility ONCE — the blockers are all engine-lifetime
        # facts, so the step path branches on a plain bool. Ineligible
        # configurations warn here and keep the padded two-kernel path.
        self._ragged = False
        self._ragged_interpret = interpret
        if self.cfg.ragged_attention:
            blockers = []
            if self.hybrid:
                blockers.append("hybrid attention groups (two page pools)")
            if mcfg.is_dsa:
                blockers.append("learned sparse attention (selection runs "
                                "in the padded step programs)")
            if mesh is not None:
                blockers.append("mesh-sharded serving (tp/sp/pp)")
            if on_tpu and not _pallas_head_dim_supported(kernel_width):
                blockers.append(
                    f"cache payload width {kernel_width} is not "
                    "128-aligned")
            if (self._fp8_cache and on_tpu
                    and (mcfg.kv_cache_heads * mcfg.page_size) % 32):
                blockers.append(
                    "fp8 page shape (kv_heads*page_size % 32 != 0 breaks "
                    "Mosaic's 8-bit tiling)")
            if blockers:
                logger.warning(
                    "ragged_attention=True unavailable (%s): using the "
                    "padded two-kernel path", "; ".join(blockers))
            else:
                self._ragged = True

        # The device's launch count (``_launch_input``), the ordinal of the
        # last program this engine sent, and how many padded decode
        # programs it has sent since another engine's went in between.
        self._launches = _launch_counts.setdefault(
            self._device, itertools.count(1))
        self._launch = self._lone_decodes = 0
        # The padded decode program whose tokens are still unread
        # (``step()``), and the tokens its form returned last, on the
        # device: the operand ``prev`` of every such program, so that one
        # is compiled whether anything is in flight or not.
        self._unread: Optional[_Unread] = None
        # The last prefill chunk of a step that read nothing
        # (``_bound_chunks``).
        self._chunk_ahead: Optional[_Unread] = None
        # Not a sharded engine (its tokens come back laid out over a mesh
        # and ``prev`` would be a second form of every program): that one
        # reads each program before the next is built, and takes no
        # ``prev``. A two-pool engine defers like any other: a program
        # launched ahead has its window page ensured from the context it
        # will write at (``_window_tables``), and what the read of the
        # program before it reclaims lies behind both programs' windows.
        self._defers = mesh is None
        # (Every padded decode form counts what the model counts.)
        self._prev = jax.device_put(
            np.zeros((self.cfg.max_batch + len(mcfg.step_counters),),
                     np.int32), self._device) if self._defers else None
        # The most tokens a decode step gives a row: 2 where the model
        # brings a prediction module and every step verifies its draft.
        self._step_tokens = 1
        if mcfg.num_nextn_predict_layers:
            self._serve_drafting(use_pallas, bool(prefill_pallas),
                                 interpret, offload_spec)

        # What actually serves each phase, resolved above for the engine's
        # lifetime; read through ``attention_backends``.
        def phase(pallas: bool) -> dict:
            return {"backend": "pallas" if pallas else "xla",
                    "interpret": bool(pallas and interpret)}

        self._attention_backends = {
            "platform": dev0.platform,
            "device_kind": dev0.device_kind,
            "decode": phase(use_pallas),
            "prefill": phase(bool(prefill_pallas and use_pallas)),
            "ragged": phase(True) if self._ragged else None,
        }
        logger.info("engine %s attention backends: %s",
                    self.cfg.pod_identifier, self._attention_backends)

        # Optional shared-storage offload tier (offload.SharedStorageOffloadSpec):
        # write-through on commit, restore on prefix miss at admission.
        self.offload_manager = None
        self.offload_handlers = None
        self._pending_store_jobs: dict[int, list[int]] = {}
        # Deferred-restore bookkeeping: results for these job ids must be
        # stashed by ANY drain (poll_offload's untargeted drain would
        # otherwise swallow a completion before the owning request polls).
        self._restore_job_ids: set[int] = set()
        self._restore_results: dict[int, Any] = {}
        self._offload_medium = ""
        if offload_spec is not None:
            # Works under pp too: the copier's gather/scatter programs
            # run SPMD over the layer-sharded pools (GSPMD inserts the
            # collectives; scatter preserves the pp sharding) — pinned by
            # tests/test_pp_serve.py's offload round-trip.
            if getattr(offload_spec, "attention_sinks", 0) != (
                    mcfg.attention_sinks):
                # The sink mask changes deeper layers' KV past the window;
                # a spec that disagrees would fingerprint to the wrong
                # store directory and resume byte-incompatible blocks.
                raise ValueError(
                    f"offload spec attention_sinks="
                    f"{getattr(offload_spec, 'attention_sinks', 0)} does "
                    f"not match the model's {mcfg.attention_sinks}")
            spec_dtype = getattr(offload_spec, "dtype", "bfloat16")
            cache_dtype_name = jnp.dtype(self._kv_dtype).name
            if spec_dtype != cache_dtype_name:
                # The dtype is a fingerprint field: a mismatched spec
                # would resume stores whose bytes are a different element
                # type (e.g. bf16 blocks into an fp8 pool).
                raise ValueError(
                    f"offload spec dtype={spec_dtype!r} does not match "
                    f"the engine's KV cache dtype {cache_dtype_name!r} "
                    f"(set OffloadSpec dtype accordingly)")
            self.offload_manager = offload_spec.get_manager()
            self.offload_handlers = offload_spec.get_handlers(
                self.k_cache, self.v_cache
            )
            if self.hybrid:
                # Hybrid: group 1 (SWA) gets its own copier bound to the
                # SWA pool; both groups store/restore, keyed by group_idx
                # into per-group store directories/key prefixes. Both the
                # POSIX and object-store backends route per-group copiers.
                if not hasattr(self.offload_handlers, "copiers"):
                    raise NotImplementedError(
                        "hybrid models need per-group offload copiers; the "
                        f"{offload_spec.backend!r} backend has none")
                from ..offload.tpu_copier import TPUBlockCopier

                self.offload_handlers.copiers[1] = TPUBlockCopier(
                    self.k_swa, self.v_swa
                )
            # Canonical medium label (matches KV-event medium strings).
            self._offload_medium = offload_spec.medium

        # Disaggregated serving (offload.handoff): a coordinator attached
        # via attach_handoff turns a "prefill"-role engine into the
        # transfer's producer (per-chunk write-through commits notify it)
        # and a "decode"-role engine into its consumer (handoff=True
        # enqueues wait on it). on_restore_latency is an optional tap fed
        # each successful deferred-restore's wall time — the serving
        # assembly wires it into the index's observe_tier_latency so
        # residency scoring learns the transfer tier's real restore cost.
        if self.cfg.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {self.cfg.role!r} "
                "(expected 'both', 'prefill', or 'decode')")
        if self.cfg.role != "both" and self.hybrid:
            raise ValueError(
                "prefill/decode disaggregation needs a non-hybrid model "
                "(hybrid restores are all-or-nothing, not chunk-granular)")
        if self.cfg.role != "both" and self.offload_manager is None:
            raise ValueError(
                f"role={self.cfg.role!r} needs an offload spec — the "
                "handoff moves KV through the shared transfer tier")
        self.handoff = None
        # store job id → (request_id, block hashes) for jobs the handoff
        # coordinator must hear about when they settle.
        self._handoff_store_jobs: dict[int, tuple[str, list[int]]] = {}
        self.on_restore_latency: Optional[Callable[[float], None]] = None
        # Streaming EMA of successful restore wall time (both restore
        # paths feed it): the deferred-restore deadline gate skips the
        # storage tier when the remaining budget is smaller than what a
        # restore typically costs — recompute is the faster path then.
        self._restore_latency_ema = 0.0

        # Admission overload shedding (CoDel over admission delay).
        # None unless configured — the disabled path costs one attribute
        # load per enqueue/step.
        self.shedder: Optional[CoDelShedder] = None
        if self.cfg.shed_target_delay_s > 0:
            self.shedder = CoDelShedder(
                "engine.admission",
                target_delay_s=self.cfg.shed_target_delay_s,
                interval_s=self.cfg.shed_interval_s,
            )

        # Engine data-plane telemetry: request-lifecycle histograms
        # (TTFT/ITL/TPOT), decimated KV-pool gauge scrapes, per-request
        # flight-recorder events. None when the config leaves it off —
        # every hook site below guards on that, so the disabled step path
        # pays one attribute load + branch per site.
        self.telemetry = None
        # Working-set analytics: None until attach_workingset wires a
        # telemetry.workingset.WorkingSetTracker (same guard style).
        self.workingset = None
        # Ground-truth audit: None until attach_audit wires a
        # telemetry.audit.AuditLog (same guard style).
        self.audit = None
        self._telemetry_pools: list[tuple[str, BlockManager]] = []
        tcfg = self.cfg.telemetry
        if tcfg is not None and getattr(tcfg, "enabled", True):
            from ..telemetry.engine_telemetry import EngineTelemetry

            self.telemetry = EngineTelemetry(
                tcfg, group=self.cfg.pod_identifier)
            self.telemetry.attention_backends = self.attention_backends
            self._telemetry_pools = [("full", self.block_manager)]
            if self.hybrid:
                self._telemetry_pools.append(("swa", self.swa_manager))
            self.telemetry.scrape_pools(self._telemetry_pools)
        # Engine phases (telemetry.tracing.phase): on with the telemetry
        # above, else None — every phase site is then the shared no-op.
        self._phases: Optional[EnginePhases] = None
        if self.telemetry is not None:
            self._phases = EnginePhases(self.cfg.pod_identifier)
            for _, manager in self._telemetry_pools:
                manager.phases = self._phases

    @property
    def attention_backends(self) -> dict:
        """The backend each phase resolved to at construction (a copy):
        ``platform``/``device_kind`` of the serving device, and for
        ``decode``/``prefill``/``ragged`` a ``{"backend": "pallas"|"xla",
        "interpret": bool}`` entry (``ragged`` is None when the ragged
        scheduler is off). ``interpret`` is never true on a TPU."""
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in self._attention_backends.items()}

    def _to_dev(self, x, dtype=None):
        """Host value → array on this replica's device (JAX's default
        device, uncommitted, when the engine was given none)."""
        x = np.asarray(x, dtype)
        ph = self._phases
        if ph is not None:
            ph.transfers += 1
            ph.bytes += x.nbytes
        return jax.device_put(x, self._device)

    def _pools(self) -> tuple:
        """The page pools a step program is handed (and donated)."""
        if self.hybrid:
            return (self.k_cache, self.v_cache, self.k_swa, self.v_swa)
        return (self.k_cache, self.v_cache, *self.state)

    def _take_pools(self, pools: tuple) -> None:
        """The pools a step program handed back, in ``_pools``'s order."""
        self.k_cache, self.v_cache = pools[:2]
        if self.hybrid:
            self.k_swa, self.v_swa = pools[2:]
        elif self.state:
            self.state = pools[2:]

    # -- admission --

    def attach_handoff(self, coordinator) -> None:
        """Wire a :class:`~..offload.handoff.HandoffCoordinator`.

        On a "prefill"-role engine every chunk-commit store job reports
        chunk start/landed/failed to it; on a "decode"-role engine
        ``enqueue(handoff=True)`` requests consult it to decide between
        waiting, pulling, and falling back to local prefill.
        """
        self.handoff = coordinator

    def set_role(self, role: str) -> str:
        """Re-role a running engine (the fleet controller's
        prefill↔decode flip); returns the previous role.

        Same invariants as construction: a non-"both" role needs a
        non-hybrid model and an offload spec. The flip affects requests
        admitted *after* it — in-flight requests finish under the role
        they were admitted with (their handoff state machine is already
        chosen), which is exactly the drain semantics the controller
        wants.
        """
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {role!r} "
                "(expected 'both', 'prefill', or 'decode')")
        if role != "both" and self.hybrid:
            raise ValueError(
                "prefill/decode disaggregation needs a non-hybrid model "
                "(hybrid restores are all-or-nothing, not chunk-granular)")
        if role != "both" and self.offload_manager is None:
            raise ValueError(
                f"role={role!r} needs an offload spec — the handoff moves "
                "KV through the shared transfer tier")
        old = self.cfg.role
        self.cfg = dataclasses.replace(self.cfg, role=role)
        return old

    def attach_workingset(self, tracker) -> None:
        """Wire a telemetry.workingset.WorkingSetTracker into this
        engine's cache paths: admission feeds the "hbm" reuse stream
        (every request's block keys, hit count = resident prefix), the
        block manager's evictions feed the eviction-age histogram, and
        the offload manager's lookups/stores feed the storage-tier
        stream plus the written-never-read ledger. Also declares the
        real HBM pool capacity so the what-if table has its 1x anchor.
        """
        self.workingset = tracker
        self.block_manager.on_evict = tracker.record_eviction_age
        tracker.set_capacity("hbm", self.block_manager.num_pages)
        if self.offload_manager is not None:
            self.offload_manager.workingset = tracker

    def attach_audit(self, audit_log) -> None:
        """Wire a :class:`~..telemetry.audit.AuditLog`: every admitted
        request's realized prefix outcome (HBM hit vs restored vs
        recomputed blocks) is recorded at prefill finish, tagged with the
        request's traceparent and the :class:`ScoreFeedback` it was
        routed on, for the fleet collector's score-vs-reality join
        (``/debug/audit``)."""
        self.audit = audit_log

    def add_request(self, request_id: str, prompt: Sequence[int],
                    max_new_tokens: int = 16) -> Request:
        """Admit a request: acquire cached prefix pages, allocate the rest,
        and run the prefill step for the uncached suffix (synchronously —
        the request returns ready to decode)."""
        req = self._admit(request_id, prompt, max_new_tokens)
        self._finish_prefill(req, int(self._fetch(self._prefill(req))[0]))
        return req

    def _dispatch_phase(self, req: Optional[Request], rows: int,
                        tokens: int, padded: int, program=None):
        """The ``step.dispatch`` phase of one jitted call: the transfer of
        its packed inputs and the call returning, with the sizes that
        explain its length and, as ``program``, the name the jit gives
        ``program`` (the callable about to be called: a device trace calls
        its execution ``jit_<name>``). ``req`` is the request whose
        prefill chunk rides it (None for a pure decode program): its
        ``traceparent`` makes the phase the trace's
        ``engine.prefill_chunk`` span."""
        ph = self._phases
        traceparent = None if req is None else req.traceparent
        if ph is None and traceparent is None:
            return phase(None, PHASE_STEP_DISPATCH)
        if ph is not None:
            # (Phases come with the telemetry.) Whose chunk the launch
            # about to be numbered is, or that it is a decode program.
            self.telemetry.on_dispatch(
                None if req is None else req.request_id)
        named = {} if ph is None or program is None else {
            "program": getattr(program, "func", program).__name__}
        if req is None:
            return phase(ph, PHASE_STEP_DISPATCH, programs=1, rows=rows,
                         tokens=tokens, padded=padded, **named)
        if named and self.cfg.model.is_mla:
            named["expanded_keys"] = self._expanded_keys(
                program, req.prefill_pos, tokens, padded)
        if named and self.hybrid:
            # What the chunk's kernel streams a layer: every key in a full
            # layer, the chunk's own and the window before them in a
            # window layer.
            named["full_keys"] = req.prefill_pos + tokens
            named["window_keys"] = tokens + min(
                req.prefill_pos, self.cfg.model.sliding_window - 1)
        return phase(ph, PHASE_STEP_DISPATCH, traceparent, programs=1,
                     rows=rows, tokens=tokens, padded=padded,
                     request_id=req.request_id, prefill_pos=req.prefill_pos,
                     process=self.cfg.pod_identifier, **named)

    def _expanded_keys(self, program, pos: int, tokens: int,
                       padded: int) -> int:
        """``expanded_keys`` of a latent model's prefill dispatch: the key
        positions a head expands a latent layer where ``program``, handed
        ``tokens`` tokens at ``pos`` padded to ``padded``, attends per head
        (``llama.prefill_per_head``: the chunk program's rule, from its
        shapes); 0 where it holds the absorbed kernel."""
        if (program is not self._prefill_forward or self._pp > 1
                or self._attention_backends["prefill"]["backend"] != "pallas"
                or not prefill_per_head(
                    self.cfg.model, padded,
                    self.mesh if self._tp > 1 else None)):
            return 0
        return per_head_expanded_keys(
            pos + tokens, self.cfg.model.page_size,
            self.cfg.max_pages_per_seq)

    def _launch_input(self, packed, sp, decode: bool = False):
        """A step program's packed inputs on the device, the program
        numbered (``self._launch``, and ``launch`` on its dispatch phase
        ``sp``). An argument of the jitted call, so the number is taken as
        the last thing before the call: the device runs what one process
        sends it in that order, whichever replica sent it.

        The numbers are also how this engine sees whether it has the chip
        to itself: ``_lone_decodes`` counts the padded decode programs
        (``decode``) it launched since a number last went to another
        engine."""
        x = self._to_dev(packed)
        n = next(self._launches)
        if n != self._launch + 1:
            self._lone_decodes = 0
        self._launch = n
        if decode:
            self._lone_decodes += 1
        if sp is not NOOP_SPAN:
            sp.set_attribute("launch", n)
        if self.telemetry is not None:
            self.telemetry.on_launch(n)
        return x

    def _fetch_phase(self, launch: int):
        """The ``step.fetch`` phase of program ``launch`` of this device:
        the blocking read of its tokens."""
        ph = self._phases
        if ph is None:
            return phase(None, PHASE_STEP_FETCH)
        return phase(ph, PHASE_STEP_FETCH, launch=launch)

    def _fetch(self, rec: _Unread) -> np.ndarray:
        """``rec``'s tokens on the host: read once, inside its
        ``step.fetch``, which also takes what the program counted."""
        if rec.host is None:
            with self._fetch_phase(rec.launch) as sp:
                rec.host = np.asarray(rec.picked)
                self._device_counts(sp, rec.host[rec.padded:], rec.tokens,
                                    rec.program)
                if self._step_tokens > 1:
                    self._read_drafts(sp, rec)
        return rec.host

    def _drain(self, cause: str) -> None:
        """Wait for the decode program in flight, if one is, before what
        ends or moves a request under it (``cause``). Its tokens stay with
        the record; the next ``step()`` returns them as it would have."""
        rec = self._unread
        if rec is not None and rec.host is None:
            self._fetch(rec)
            if self.telemetry is not None:
                self.telemetry.on_lookahead_drained(cause)

    def _device_counts(self, sp, counts: np.ndarray, tokens: int,
                       program: str) -> None:
        """What a step program counted on the device (the model's
        ``step_counters``, behind its sampled tokens in the same array)
        onto the phase that read them, with the program (``decode`` or
        ``prefill``: of a prefill only the last chunk's tokens are read)
        and the real tokens they are counts of."""
        if len(counts) and sp is not NOOP_SPAN:
            sp.set_attribute("counted_program", program)
            sp.set_attribute("counted_tokens", tokens)
            for name, n in zip(self.cfg.model.step_counters, counts):
                sp.set_attribute(name, int(n))

    def _record_shed(self, outcome: str, priority: int) -> None:
        """Best-effort shed accounting: metric family + flight recorder.
        Never lets telemetry failures interfere with admission."""
        try:
            from ..metrics.collector import record_shed

            record_shed("engine.admission", outcome)
        except Exception:  # pragma: no cover  # lint: allow-swallow
            pass
        try:
            from ..telemetry.flight_recorder import KIND_SHED, record

            record(KIND_SHED, {
                "site": "engine.admission",
                "outcome": outcome,
                "priority": priority,
            })
        except Exception:  # pragma: no cover  # lint: allow-swallow
            pass

    def enqueue(self, request_id: str, prompt: Sequence[int],
                max_new_tokens: int = 16,
                traceparent: Optional[str] = None,
                handoff: bool = False,
                deadline_s: Optional[float] = None,
                priority: int = PRIORITY_NORMAL,
                feedback=None) -> Request:
        """Admit a request for continuous batching: pages are acquired and
        the storage tier consulted from ``step()``, where prefill runs
        chunk-at-a-time interleaved with decode — a long prompt stalls
        running decodes by at most one chunk (``max_prefill_tokens``), not
        its whole prefill (vLLM chunked-prefill scheduling). The storage
        restore is likewise deferred and polled across steps, so a slow
        storage tier costs the restored request latency, never the
        running decodes'.

        ``traceparent`` (e.g. ``ScoreResponse.traceparent`` from the pod
        that scored this request) parents the engine's admission/prefill/
        decode-step spans under the scorer's trace — one trace covers
        score→serve. Requests without one create no spans at all.

        ``handoff=True`` (decode-role engines) marks this request as the
        receiving end of a prefill→decode handoff: ``step()`` holds its
        local prefill for up to ``cfg.handoff_wait_s``, re-arming the
        deferred-restore probe as the prefill peer's chunks land on the
        transfer tier — the KV pull overlaps queueing and the running
        decode batch. A failed or timed-out transfer falls back to local
        prefill (the request is never lost).

        ``deadline_s`` attaches an end-to-end budget (falls back to the
        ambient :func:`deadline_scope` when omitted): a deferred storage
        restore that cannot land inside the remaining budget is skipped
        in favor of recompute. When the admission shedder is configured
        (``cfg.shed_target_delay_s``), sustained admission delay sheds
        non-critical requests (:class:`OverloadShedError`) and browns out
        the rest — admitted, but without the storage-restore attempt.

        ``feedback`` (a ``services.indexer_service.ScoreFeedback``, or
        any object with its fields) is the prediction this request was
        routed on; with an :meth:`attach_audit` log it rides the realized
        outcome record so the fleet collector can score the prediction
        even when the scorer's own ring already evicted it.
        """
        brownout = False
        if self.shedder is not None:
            verdict = self.shedder.admit(priority)
            if verdict == SHED:
                self._record_shed("shed", priority)
                raise OverloadShedError(
                    "engine.admission", self.shedder.last_delay_s)
            if verdict == BROWNOUT:
                brownout = True
                self._record_shed("brownout", priority)
        with phase(self._phases, PHASE_ENQUEUE_ADMIT, traceparent) as sp:
            if sp is not NOOP_SPAN:
                sp.set_attribute("request_id", request_id)
                sp.set_attribute("prompt_tokens", len(prompt))
                sp.set_attribute("process", self.cfg.pod_identifier)
            req = self._admit(request_id, prompt, max_new_tokens,
                              defer_restore=True)
            sp.set_attribute("prefix_hit_blocks",
                             req.cached_len // self.cfg.model.page_size)
        if traceparent is not None:
            req.traceparent = traceparent
            if self.telemetry is not None:
                self.telemetry.set_traceparent(request_id, traceparent)
        req.deadline = (
            Deadline.after(deadline_s) if deadline_s is not None
            else current_deadline()
        )
        req.priority = priority
        req.feedback = feedback
        if brownout and req.restore_pending:
            # Brownout: admitted, but skip the storage-tier restore —
            # under queue pressure the offload round trip is the first
            # cost to drop (recompute keeps the scheduler moving).
            req.restore_pending = False
        # Admission delay (enqueue → first scheduler pick) is observed at
        # first schedule (kvcache_engine_admission_delay_seconds).
        req.enqueued_at = time.monotonic()
        if handoff:
            if self.hybrid or self.offload_manager is None:
                raise ValueError(
                    "handoff=True needs a non-hybrid engine with an "
                    "offload spec (the transfer arrives via the tier)")
            req.handoff_deadline = time.monotonic() + self.cfg.handoff_wait_s
        return req

    def _admit(self, request_id: str, prompt: Sequence[int],
               max_new_tokens: int, defer_restore: bool = False) -> Request:
        """Shared admission: prefix-cache acquisition, storage restore,
        page allocation, registration. No model compute."""
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        req = Request(request_id=request_id, prompt=prompt,
                      max_new_tokens=max_new_tokens)
        page_size = self.cfg.model.page_size
        total_needed = (req.total_len + max_new_tokens + page_size - 1) // page_size + 1
        if total_needed > self.cfg.max_pages_per_seq:
            raise ValueError(
                f"request needs {total_needed} pages "
                f"(prompt {len(prompt)} + {max_new_tokens} new tokens) but "
                f"max_pages_per_seq is {self.cfg.max_pages_per_seq}"
            )
        with phase(self._phases, PHASE_ENQUEUE_HASH) as sp:
            sp.set_attribute("tokens", len(prompt))
            req.block_hashes = self.processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, prompt, self.cfg.model_name
            )
        with phase(self._phases, PHASE_ENQUEUE_LOOKUP) as sp:
            evictions = self.block_manager.evictions
            self._acquire_pages(req, total_needed, defer_restore)
            sp.set_attribute("blocks", len(req.block_hashes))
            sp.set_attribute("hit_blocks", req.hbm_hit_blocks)
            sp.set_attribute(
                "evicted", self.block_manager.evictions - evictions)
            if self.state_pool is not None or self.hybrid:
                # What the pages' chain matched, and what was kept of it:
                # cut back to the deepest snapshot, or (two pools) to the
                # deepest depth whose trailing window still stands.
                page = page_size * req.page_hit_blocks
                sp.set_attribute("page_hit_tokens", page)
                sp.set_attribute(
                    "window_hit_tokens" if self.hybrid
                    else "state_hit_tokens", req.cached_len)
        return req

    def _acquire_pages(self, req: Request, total_needed: int,
                       defer_restore: bool) -> None:
        """The rest of admission: prefix-cache probe, storage restore (or
        its deferral), page allocation with eviction, registration."""
        request_id, page_size = req.request_id, self.cfg.model.page_size
        cached_pages = self.block_manager.acquire_prefix(req.block_hashes)
        if self.hybrid:
            # A resume at depth d needs group 0's FULL chain [0, d) but
            # only group 1's trailing window — blocks covering the last
            # ``sliding_window`` tokens (earlier SWA blocks are dropped
            # out-of-window and never needed again). Find the deepest d
            # whose trailing SWA window is resident; out-of-window slots
            # map to the garbage page (attention masks them anyway).
            page_sz = self.cfg.model.page_size
            window = self.cfg.model.sliding_window
            req.page_hit_blocks = d = len(cached_pages)
            swa_map: dict[int, int] = {}
            start_blk = 0
            while d > 0:
                start_blk = max(0, (d * page_sz - window) // page_sz)
                pages = self.swa_manager.try_acquire_blocks(
                    req.block_hashes[start_blk:d])
                if pages is not None:
                    swa_map = dict(zip(range(start_blk, d), pages))
                    break
                d -= 1
            if d < len(cached_pages):
                self.block_manager.release(req.block_hashes[d:len(cached_pages)], [])
            cached_pages = cached_pages[:d]
            req.swa_pages = [swa_map.get(i, 0) for i in range(d)]
            req.swa_acquired_from = start_blk if d > 0 else 0
        snapshot = None
        if self.state_pool is not None:
            # The hit is the deepest snapshot standing inside the matched
            # pages (and short of the prompt's last token, whose logits
            # need a step): pages beyond it are given back and computed
            # again, which the commit finds resident.
            req.page_hit_blocks = matched = len(cached_pages)
            depth, snapshot = self.state_pool.lookup(
                req.block_hashes,
                min(matched, (len(req.prompt) - 1) // page_size))
            self.block_manager.release(req.block_hashes[depth:matched], [])
            cached_pages = cached_pages[:depth]
            try:
                req.state_slot = self.state_pool.acquire(
                    request_id,
                    keep=req.block_hashes[depth - 1] if depth else None)
            except RuntimeError:
                self.block_manager.release(req.block_hashes[:depth], [])
                raise
        req.pages = list(cached_pages)
        req.cached_len = len(cached_pages) * page_size
        req.computed_len = req.cached_len
        req.hbm_hit_blocks = len(cached_pages)
        if self.workingset is not None:
            # Admission is the HBM tier's reuse stream: one access per
            # prompt block, hits = the resident prefix length.
            self.workingset.record_accesses(
                "hbm", req.block_hashes, hits=len(cached_pages))

        # Storage tier: extend the HBM prefix hit with blocks resident on
        # shared storage. add_request (synchronous serving) restores here —
        # one high-priority read, far below a prefill. enqueue (continuous
        # batching) defers: the lookup+load start inside step() and the job
        # is polled across steps, because a restore blocking _admit would
        # stall every running decode for up to the load deadline (the
        # hybrid two-pool restore is all-or-nothing and stays synchronous —
        # its window coupling makes a half-restored resume unusable).
        if self.offload_manager is not None:
            if defer_restore and not self.hybrid:
                req.restore_pending = True
            else:
                self._restore_from_storage(req)

        # Pages for the uncached remainder (incl. partial tail + decode
        # room). Group 1 (SWA) pages are NOT pre-allocated: _prefill and
        # decode allocate them lazily per chunk and reclaim out-of-window
        # slots as the context advances, so peak SWA-pool demand stays
        # window-bounded instead of prompt-length-bounded.
        # One call for all of them: what it evicts leaves in one ordered
        # pop and reaches the index as one batch, ahead of every
        # BlockStored this request will commit.
        lacking = max(total_needed - len(req.pages), 0)
        new_pages = self.block_manager.allocate_pages(lacking)
        if len(new_pages) < lacking:
            # Return popped pages and drop the refs on every block this
            # request holds — the HBM prefix AND any blocks just restored
            # from storage — so a failed admission cannot shrink the pool
            # or pin blocks against eviction.
            n_cached = req.cached_len // page_size
            self.block_manager.free_pages.extend(new_pages)
            self.block_manager.release(req.block_hashes[:n_cached], [])
            if self.hybrid:
                self.swa_manager.release(
                    req.block_hashes[req.swa_acquired_from:n_cached], [])
            if self.state_pool is not None:
                self.state_pool.release(request_id)
                self._emit_state_events()
            raise RuntimeError("out of KV pages")
        req.pages.extend(new_pages)
        if self.state_pool is not None:
            # What the working slot's eviction removed, if the pages'
            # eviction has not already taken it along in its batch.
            self._emit_state_events()
            if snapshot is not None:
                self.state = copy_state_slot(
                    self.state, self._to_dev([snapshot, req.state_slot],
                                             np.int32))

        # Everything acquired/restored so far is registered+refcounted in
        # the block manager; later pages stay private until commit.
        req.committed_blocks = req.cached_len // page_size
        # Prefill cursor (a full-prefix hit still recomputes the last
        # prompt token for logits, hence the min with len-1); add_request
        # drains it synchronously, enqueue leaves it for step().
        req.prefill_pos = min(req.cached_len, len(req.prompt) - 1)
        if self.cfg.model.num_nextn_predict_layers and req.cached_len:
            # A model that drafts: the module's row at the hit's first new
            # slot is made from the hidden state of the position before it,
            # which no page holds. The hit's last position is computed
            # again (its latents written as they stand): one token a hit.
            req.prefill_pos = min(req.prefill_pos, req.cached_len - 1)
        self.requests[request_id] = req
        self._running.append(request_id)
        if self.telemetry is not None:
            self.telemetry.on_admitted(
                request_id, req.cached_len // page_size)

    def _emit_state_events(self) -> None:
        """What the state pool has to tell the index, as one batch."""
        events = self.state_pool.drain()
        if events:
            self.block_manager._emit(events)

    def _plan_snapshots(self, req: Request, pos: int, n: int) -> tuple:
        """The snapshots the chunk ``[pos, pos + n)`` of ``req``'s prefill
        leaves: ``(snap, taken)`` with ``snap = [block, slot, end_slot]``
        as the step program takes it (``llama.with_state``) and ``taken``
        the ``(boundary, slot)`` pairs to register once it has run. Wanted
        are the boundaries in ``(pos, pos + n]`` that are (a) the last one
        a repeat of this prompt could resume from, (c) the end of the pages
        that matched at admission beyond the snapshot it was admitted on,
        (b) the newest multiple of ``state_checkpoint_tokens``. The chunk's
        end is free (the state is there); of the boundaries inside it the
        scan gives one, the first of a, c, b.

        A boundary that is only (b) is a periodic checkpoint, and a prefill
        holds one: the next is written over the last (``StatePool.reserve``:
        unannounced until the prefill ends, so nobody was admitted on pages
        up to it; where a prefill-role engine has committed them already, a
        reader's copy was dispatched at its admission, ahead of the program
        that overwrites the slot). A prompt of any length thus costs the
        pool three slots at most, and the others' resume points stay. The
        one that trails the prefill's end is announced with (a) and (c): a
        prompt that parts from this one resumes within a spacing of where
        it parts if that is inside the last spacing, else from its deepest
        snapshot, and leaves (c) for the next."""
        page = self.cfg.model.page_size
        pool = self.state_pool
        every = self.cfg.model.state_checkpoint_tokens
        last, end = (len(req.prompt) - 1) // page * page, pos + n
        shared = req.page_hit_blocks * page
        kept = (last, shared if shared > req.cached_len else 0)
        wanted = [b for b in (*kept, end // every * every if every else 0)
                  if pos < b <= end and b % page == 0]
        snap, taken = [-1, 0, 0], []
        with phase(self._phases, PHASE_STEP_SNAPSHOT) as sp:
            evicted, replaced = pool.evictions, pool.replaced
            for b in dict.fromkeys(wanted):
                inner = b != end
                if inner and snap[1]:
                    continue  # the scan gives one state inside a chunk
                h = req.block_hashes[b // page - 1]
                periodic = b not in kept
                slot = pool.reserve(h, req.checkpoint if periodic else None)
                if slot is None:
                    continue
                if periodic:
                    if req.checkpoint not in pool.snapshots:
                        # Replaced just now, or evicted since.
                        req.snapshots = [s for s in req.snapshots
                                         if s != req.checkpoint]
                    req.checkpoint = h
                taken.append((b, slot))
                if inner:
                    snap[0], snap[1] = (b - pos) // page - 1, slot
                else:
                    snap[2] = slot
            sp.set_attribute("snapshots", len(taken))
            sp.set_attribute("state_evicted", pool.evictions - evicted)
            sp.set_attribute("replaced", pool.replaced - replaced)
        return snap, taken

    def _finish_prefill(self, req: Request, first_token: int) -> None:
        """Prefill done: register the prompt's full blocks in the prefix
        cache and bootstrap decoding with the first generated token (the
        last chunk's program sampled it from its final logits — vLLM
        semantics: even a full-prefix hit recomputes the last prompt token
        for logits)."""
        before = req.committed_blocks
        with phase(self._phases, PHASE_STEP_COMMIT) as sp:
            self._commit_full_blocks(req)
            if req.snapshots:
                # The blocks they stand on are in the index from here on.
                self.state_pool.announce(req.snapshots)
                req.snapshots = []
                self._emit_state_events()
            sp.set_attribute("request_id", req.request_id)
            sp.set_attribute("blocks", req.committed_blocks - before)
            req.output.append(first_token)
            if self.telemetry is not None:
                self._first_token_phase(req)
            if self.audit is not None:
                self._emit_audit_outcome(req)
            if self.cfg.role == "prefill" and self.handoff is not None:
                # Prefill pod: the request's life here ends at first token —
                # every full block is now committed (the final chunk's store
                # job just entered the plane), the decode pod recomputes the
                # partial tail and the bootstrap token itself, so this token
                # is discarded. Mark the transfer complete-when-settled
                # before finishing so the coordinator flips ``done`` as the
                # last store job lands.
                self.handoff.prefill_finished(req.request_id)
                req.done = True
                self._finish(req)
            elif req.max_new_tokens <= 1:
                req.done = True
                self._finish(req)

    def _first_token_phase(self, req: Request) -> None:
        """``req``'s first token stands: the telemetry's clock of it, and
        ``request.first_token`` (phases come with the telemetry), opened
        and closed at once: a marker at this point of the capture that
        carries the request's way here as the telemetry kept it
        (``_ReqState.first_token_split``)."""
        st = self.telemetry.on_first_token(
            req.request_id, len(req.prompt), req.cached_len)
        if st is not None:
            with phase(self._phases, PHASE_REQUEST_FIRST_TOKEN,
                       **st.first_token_split()):
                pass

    def _emit_audit_outcome(self, req: Request) -> None:
        """Best-effort ground-truth emission at prefill finish: the
        realized prefix decomposition (HBM hit at admission, restored
        since, recomputed remainder) into the attached AuditLog plus a
        KIND_AUDIT flight record. Never interferes with serving."""
        page_size = self.cfg.model.page_size
        total = len(req.block_hashes)
        realized = min(req.cached_len // page_size, total)
        hbm = min(req.hbm_hit_blocks, realized)
        restored = min(req.restored_blocks, realized - hbm)
        recomputed = max(total - realized, 0)
        try:
            self.audit.record_outcome(
                traceparent=req.traceparent,
                request_id=req.request_id,
                pod=self.cfg.pod_identifier,
                total_blocks=total,
                hbm_blocks=hbm,
                restored_blocks=restored,
                recomputed_blocks=recomputed,
                feedback=req.feedback,
            )
        except Exception:  # pragma: no cover  # lint: allow-swallow
            pass
        try:
            from ..telemetry.flight_recorder import KIND_AUDIT, record

            record(KIND_AUDIT, {
                "op": "outcome",
                "request_id": req.request_id,
                "pod": self.cfg.pod_identifier,
                "total_blocks": total,
                "hbm_blocks": hbm,
                "restored_blocks": restored,
                "recomputed_blocks": recomputed,
            })
        except Exception:  # pragma: no cover  # lint: allow-swallow
            pass

    def _sync_caches_to_copier(self) -> None:
        """Hand the current (possibly donated-and-replaced) cache arrays to
        the offload copiers; forward() replaces the cache arrays every
        step, so the copiers must never hold stale references."""
        self._drain("offload")
        self.offload_handlers.copier.k_cache = self.k_cache
        self.offload_handlers.copier.v_cache = self.v_cache
        if self.hybrid:
            self.offload_handlers.copiers[1].k_cache = self.k_swa
            self.offload_handlers.copiers[1].v_cache = self.v_swa

    def _sync_caches_from_copier(self) -> None:
        self.k_cache = self.offload_handlers.copier.k_cache
        self.v_cache = self.offload_handlers.copier.v_cache
        if self.hybrid:
            self.k_swa = self.offload_handlers.copiers[1].k_cache
            self.v_swa = self.offload_handlers.copiers[1].v_cache

    def _restore_from_storage(self, req: Request) -> None:
        """Load storage-resident blocks that extend the HBM prefix hit."""
        if self.hybrid:
            self._restore_from_storage_hybrid(req)
            return
        page_size = self.cfg.model.page_size
        first_missing = req.cached_len // page_size
        remaining = req.block_hashes[first_missing:]
        if not remaining:
            return
        n_stored = self.offload_manager.lookup(remaining)
        if n_stored == 0:
            return
        restore_hashes = remaining[:n_stored]
        pages: list[int] = []
        for _ in restore_hashes:
            page = self.block_manager.allocate_page()
            if page is None:
                break
            pages.append(page)
        if not pages:
            return
        restore_hashes = restore_hashes[: len(pages)]

        from ..metrics.collector import (
            record_engine_restore,
            record_offload_restore,
        )

        self._sync_caches_to_copier()
        started = time.monotonic()
        job = self.offload_handlers.async_load_blocks(
            [(h, [p]) for h, p in zip(restore_hashes, pages)]
        )
        result = None
        deadline = started + 30.0
        while result is None and time.monotonic() < deadline:
            result = self._drain_offload(target_job=job)
            if result is None:
                time.sleep(0.002)

        if result is None:
            # Timed out: cancel so a late completion can never scatter into
            # pages we are about to recycle.
            self.offload_handlers.wait_job(job, timeout_s=5.0)
        if result is None or not result.success:
            record_engine_restore("timeout" if result is None else "failure")
            logger.warning("storage restore failed for %d blocks", len(pages))
            self.block_manager.free_pages.extend(pages)
            return
        elapsed = time.monotonic() - started
        record_engine_restore("success", elapsed)
        record_offload_restore(self._offload_medium, elapsed)
        self._observe_restore_latency(elapsed)
        if self.on_restore_latency is not None:
            try:
                self.on_restore_latency(elapsed)
            except Exception:  # pragma: no cover  # lint: allow-swallow
                pass

        # Register restored blocks in the prefix cache (no re-store event:
        # the blocks are already on the storage tier; the HBM BlockStored
        # is emitted through commit so the index learns the HBM copy).
        canonical = self._commit_restored_blocks(
            req, first_missing, restore_hashes, pages
        )
        req.pages.extend(canonical)
        req.cached_len += len(canonical) * page_size
        req.computed_len = req.cached_len
        req.restored_blocks += len(canonical)

    def _observe_restore_latency(self, elapsed: float) -> None:
        """Fold a successful restore's wall time into the EMA the
        deadline gate consults (first sample seeds it directly)."""
        ema = self._restore_latency_ema
        self._restore_latency_ema = (
            elapsed if ema == 0.0 else ema + 0.2 * (elapsed - ema))

    def _commit_restored_blocks(self, req: Request, first_missing: int,
                                hashes: list, pages: list[int]) -> list[int]:
        """Adopt storage-restored blocks into the prefix cache — the shared
        commit tail of the synchronous and deferred restore paths. Returns
        the canonical pages (``commit_blocks`` may swap duplicates)."""
        page_size = self.cfg.model.page_size
        tokens_per_block = [
            req.prompt[(first_missing + i) * page_size:
                       (first_missing + i + 1) * page_size]
            for i in range(len(hashes))
        ]
        parent = (
            req.block_hashes[first_missing - 1] if first_missing > 0
            else EMPTY_BLOCK_HASH
        )
        return self.block_manager.commit_blocks(
            hashes, pages, tokens_per_block, parent
        )

    def _start_deferred_restore(self, req: Request) -> None:
        """Kick off the enqueue-path storage restore (non-hybrid).

        Unlike the synchronous path, the load lands in the pages the
        request already owns for those blocks (allocated at admission for
        the uncached remainder), so no extra pages are taken; on success
        ``commit_blocks`` adopts canonical pages and frees duplicates.

        Deadline gate: a request whose budget has expired — or whose
        remaining budget is smaller than what a restore typically costs
        (streaming EMA of past successes) — skips the storage tier and
        recomputes. A restore that lands after the caller stopped
        waiting is pure waste; prefill compute at least keeps the pages
        warm for the next caller.
        """
        req.restore_pending = False
        dl = req.deadline
        if dl is not None:
            remaining = dl.remaining_s()
            if remaining <= 0 or (0 < self._restore_latency_ema
                                  and remaining < self._restore_latency_ema):
                from ..metrics.collector import record_engine_restore

                record_engine_restore("deadline_skip")
                self._record_shed("restore_skip", req.priority)
                logger.debug(
                    "skipping storage restore for %s: %.3fs budget left, "
                    "restores take ~%.3fs", req.request_id,
                    max(0.0, remaining), self._restore_latency_ema)
                return
        page_size = self.cfg.model.page_size
        first_missing = req.cached_len // page_size
        remaining = req.block_hashes[first_missing:]
        if not remaining:
            return
        n_stored = self.offload_manager.lookup(remaining)
        if n_stored == 0:
            return
        restore_hashes = remaining[:n_stored]
        pages = req.pages[first_missing:first_missing + len(restore_hashes)]
        self._sync_caches_to_copier()
        job = self.offload_handlers.async_load_blocks(
            [(h, [p]) for h, p in zip(restore_hashes, pages)]
        )
        self._restore_job_ids.add(job)
        started = time.monotonic()
        req.restore_job = (job, first_missing, restore_hashes, pages,
                           started + 30.0, started)

    def _poll_deferred_restore(self, req: Request) -> bool:
        """Advance an in-flight deferred restore. Returns True once settled
        (success, failure, or timeout) — prefill may proceed; False while
        the load is still in flight (the step goes on decoding)."""
        from ..metrics.collector import (
            record_engine_restore,
            record_offload_restore,
        )

        job, first_missing, hashes, pages, deadline, started = req.restore_job
        result = self._restore_results.pop(job, None)
        if result is None:
            result = self._drain_offload(target_job=job)
        if result is not None:
            self._restore_job_ids.discard(job)
        if result is None:
            if time.monotonic() < deadline:
                return False
            # Timed out: non-blocking cancel (timeout 0) — kvio marks the
            # job cancelled so it can never scatter, and parks its staging
            # buffers; blocking here would stall every running decode for
            # exactly the degraded-storage case deferral exists to absorb.
            self.offload_handlers.wait_job(job, timeout_s=0.0)
            self._restore_job_ids.discard(job)
            self._restore_results.pop(job, None)
            req.restore_job = None
            record_engine_restore("timeout")
            logger.warning("deferred storage restore timed out; recomputing")
            return True
        req.restore_job = None
        if not result.success:
            record_engine_restore("failure", time.monotonic() - started)
            logger.warning("deferred storage restore failed; recomputing")
            return True
        elapsed = time.monotonic() - started
        record_engine_restore("success", elapsed)
        record_offload_restore(self._offload_medium, elapsed)
        self._observe_restore_latency(elapsed)
        if self.on_restore_latency is not None:
            # Residency scoring's tier-discount feed (index.cost_aware
            # .observe_tier_latency when the serving assembly wired it).
            try:
                self.on_restore_latency(elapsed)
            except Exception:  # pragma: no cover  # lint: allow-swallow
                pass
        page_size = self.cfg.model.page_size
        canonical = self._commit_restored_blocks(
            req, first_missing, hashes, pages
        )
        req.pages[first_missing:first_missing + len(canonical)] = canonical
        req.cached_len = (first_missing + len(canonical)) * page_size
        req.computed_len = max(req.computed_len, req.cached_len)
        req.restored_blocks += len(canonical)
        req.committed_blocks = max(req.committed_blocks,
                                   first_missing + len(canonical))
        req.prefill_pos = min(req.cached_len, len(req.prompt) - 1)
        return True

    def _commit_prefill_chunk(self, req: Request) -> None:
        """Prefill-role mid-prefill commit: push the blocks this chunk
        completed into the prefix cache and the transfer tier."""
        before = req.committed_blocks
        with phase(self._phases, PHASE_STEP_COMMIT) as sp:
            self._commit_full_blocks(
                req, upto=req.computed_len // self.cfg.model.page_size)
            sp.set_attribute("request_id", req.request_id)
            sp.set_attribute("blocks", req.committed_blocks - before)

    def _handoff_gate(self, req: Request) -> bool:
        """Decide whether a handoff-admitted request may prefill locally.

        True once the handoff settled — transfer complete, peer failed
        (fallback), or deadline hit (timeout) — and prefill proceeds from
        whatever prefix is resident; False while the wait is live, in
        which case this step skips the prefill and keeps decoding.
        """
        target = len(req.prompt) // self.cfg.model.page_size
        if req.cached_len // self.cfg.model.page_size >= target:
            # Every full prompt block is resident; only the partial tail
            # and the last prompt token remain, and those always
            # recompute locally.
            self._handoff_settle(req, "complete")
            return True
        st = (self.handoff.state(req.request_id)
              if self.handoff is not None else None)
        if st is not None and st.failed:
            # Prefill peer died mid-handoff (PR 4 recovery semantics):
            # fall back to local prefill — landed blocks still count,
            # the request is re-prefilled here, never lost.
            self._handoff_settle(req, "fallback")
            return True
        if time.monotonic() >= req.handoff_deadline:
            self._handoff_settle(req, "timeout")
            return True
        # Re-arm the transfer probe: more peer chunks may have landed
        # since the last round. The lookup is cheap and a load job starts
        # only when the stored prefix actually grew.
        if req.restore_job is None:
            self._start_deferred_restore(req)
        if req.restore_job is not None:
            return False  # pull in flight — polled next step
        if st is not None and st.done:
            # Transfer settled and everything restorable was pulled; any
            # remainder (shed chunks) recomputes locally.
            self._handoff_settle(req, "complete")
            return True
        return False

    def _handoff_settle(self, req: Request, outcome: str) -> None:
        req.handoff_deadline = None
        if self.handoff is not None:
            self.handoff.decode_settled(req.request_id, outcome)

    def _restore_from_storage_hybrid(self, req: Request) -> None:
        """Storage restore for hybrid models.

        A valid resume state needs group 0's full chain [0, d) AND group
        1's trailing window of d — and ONLY the window: earlier SWA blocks
        are masked for every future position, so recomputation cannot be
        avoided anywhere the window is incomplete (SWA KV depends on
        activations that depend on the missing keys). Group 1 stores are
        exactly the in-window-at-commit blocks, so a full-chain resume
        normally finds its window; anything less skips the restore
        conservatively (all-or-nothing, no partial hybrid restores).
        """
        page_size = self.cfg.model.page_size
        window = self.cfg.model.sliding_window
        wb = -(-window // page_size)
        d = req.cached_len // page_size  # HBM-resident depth
        remaining = req.block_hashes[d:]
        if not remaining:
            return
        n_stored = self.offload_manager.lookup(remaining)
        if n_stored == 0:
            return
        depth_end = d + n_stored
        win_start = max(0, depth_end - wb)
        # Window slots below d are already HBM-resident (trailing-window
        # acquisition guaranteed them); only [load_from, depth_end) loads.
        load_from = max(win_start, d)
        win_hashes = req.block_hashes[load_from:depth_end]
        if self.offload_manager.lookup(win_hashes, group_idx=1) < len(win_hashes):
            logger.info(
                "hybrid restore skipped: SWA window of depth %d not fully "
                "stored", depth_end)
            return

        g0_hashes = req.block_hashes[d:depth_end]
        g0_pages = [self.block_manager.allocate_page() for _ in g0_hashes]
        g1_pages = [self.swa_manager.allocate_page() for _ in win_hashes]
        if any(p is None for p in g0_pages) or any(p is None for p in g1_pages):
            self.block_manager.free_pages.extend(p for p in g0_pages if p)
            self.swa_manager.free_pages.extend(p for p in g1_pages if p)
            return

        self._sync_caches_to_copier()
        job0 = self.offload_handlers.async_load_blocks(
            [(h, [p]) for h, p in zip(g0_hashes, g0_pages)])
        job1 = self.offload_handlers.async_load_blocks(
            [(h, [p]) for h, p in zip(win_hashes, g1_pages)], group_idx=1)
        targets = {job0, job1}
        results: dict = {}
        deadline = time.monotonic() + 30.0
        while len(results) < 2 and time.monotonic() < deadline:
            results.update(self._drain_offload_multi(targets))
            if len(results) < 2:
                time.sleep(0.002)
        for job in targets - set(results):
            # Timed out: cancel so a late completion can never scatter
            # into pages we are about to recycle.
            self.offload_handlers.wait_job(job, timeout_s=5.0)
            results[job] = None
        if any(r is None or not r.success for r in results.values()):
            logger.warning("hybrid storage restore failed; recomputing")
            self.block_manager.free_pages.extend(g0_pages)
            self.swa_manager.free_pages.extend(g1_pages)
            return

        def toks(i):
            return req.prompt[i * page_size:(i + 1) * page_size]

        g0_parent = req.block_hashes[d - 1] if d > 0 else EMPTY_BLOCK_HASH
        canonical0 = self.block_manager.commit_blocks(
            g0_hashes, g0_pages, [toks(d + i) for i in range(n_stored)],
            g0_parent,
        )
        req.pages.extend(canonical0)
        g1_parent = (
            req.block_hashes[load_from - 1] if load_from > 0 else EMPTY_BLOCK_HASH
        )
        canonical1 = self.swa_manager.commit_blocks(
            win_hashes, g1_pages,
            [toks(load_from + i) for i in range(len(win_hashes))],
            g1_parent,
        )
        req.swa_pages.extend([0] * (load_from - len(req.swa_pages)))
        req.swa_pages.extend(canonical1)
        req.cached_len = depth_end * page_size
        req.computed_len = req.cached_len
        req.restored_blocks += len(canonical0)
        # Blocks acquired for the OLD depth that now sit out of window
        # return to the pool (refs drop; table slots go to garbage).
        self._swa_reclaim(req)

    def _page_table_for(self, req: Request) -> np.ndarray:
        table = np.zeros((self.cfg.max_pages_per_seq,), np.int32)
        table[: len(req.pages)] = req.pages
        return table

    def _swa_table_for(self, req: Request) -> np.ndarray:
        table = np.zeros((self.cfg.max_pages_per_seq,), np.int32)
        table[: len(req.swa_pages)] = req.swa_pages
        return table

    def _window_tables(self, chunk: list[Request], ctx) -> tuple:
        """The window pool's page table of a decode step of ``chunk``,
        padded as the global pool's: each row's window pages, with a live
        page under the block its new token's keys and values are written to
        (``ctx[i]``: ``computed_len``, or one more where the row's last
        token is still unread on the device)."""
        table = np.zeros((len(ctx), self.cfg.max_pages_per_seq), np.int32)
        for i, req in enumerate(chunk):
            self._swa_ensure(req, int(ctx[i]) // self.cfg.model.page_size)
            table[i] = self._swa_table_for(req)
        return (table,)

    def _pool_keys(self, sp, keys) -> None:
        """Onto a two-pool model's decode dispatch phase ``sp``: the keys
        a full layer attends over the step's live rows (``keys``: each
        row's) and the keys a window layer does. (A chunk's two sums are
        ``_dispatch_phase``'s.)"""
        keys = np.asarray(keys)
        sp.set_attribute("full_keys", int(keys.sum()))
        sp.set_attribute("window_keys", int(np.minimum(
            keys, self.cfg.model.sliding_window).sum()))

    def _swa_ensure(self, req: Request, upto_block: int) -> None:
        """Lazily extend the request's SWA page list through ``upto_block``
        (inclusive). SWA pages are allocated just-in-time so peak pool
        demand is window + chunk, not prompt length."""
        lacking = upto_block + 1 - len(req.swa_pages)
        if lacking <= 0:
            return
        with phase(self._phases, PHASE_STEP_WINDOW) as sp:
            evictions = self.swa_manager.evictions
            pages = self.swa_manager.allocate_pages(lacking)
            sp.set_attribute("ensured", len(pages))
            sp.set_attribute(
                "evicted", self.swa_manager.evictions - evictions)
            req.swa_pages.extend(pages)
            if len(pages) < lacking:
                raise RuntimeError("out of SWA KV pages")

    def _swa_reclaim(self, req: Request) -> None:
        """Return the request's out-of-window SWA pages to the pool.

        Slots below the current window start are never read again by this
        request (attention masks them). Private not-yet-committed pages
        free directly. Committed blocks drop this request's reference but
        STAY CACHED: a committed SWA block i always serves a resume at
        block boundary i+1 (whose trailing window covers it), so no
        committed block is ever resume-worthless — the live window slides
        past it, cache value does not. Space comes back through normal
        LRU pressure eviction (which emits BlockRemoved, keeping the
        index honest), exactly as for full-attention blocks. Reclaimed
        slots map to the garbage page.
        """
        page_size = self.cfg.model.page_size
        window = self.cfg.model.sliding_window
        first_in_window = max(0, req.computed_len - window) // page_size
        start = req.swa_acquired_from
        limit = min(first_in_window, len(req.swa_pages))
        if limit <= start:
            return
        committed: list[int] = []
        with phase(self._phases, PHASE_STEP_WINDOW) as sp:
            freed = 0
            for i in range(start, limit):
                page = req.swa_pages[i]
                if not page:
                    continue
                h = req.block_hashes[i] if i < len(req.block_hashes) else None
                info = (self.swa_manager.blocks.get(h) if h is not None
                        else None)
                if info is not None and info.page == page:
                    committed.append(h)
                else:
                    self.swa_manager.free_pages.append(page)
                    freed += 1
                req.swa_pages[i] = 0
            if committed:
                self.swa_manager.release(committed, [])
            self.swa_manager.reclaimed += freed + len(committed)
            sp.set_attribute("reclaimed", freed + len(committed))
        req.swa_acquired_from = limit

    def _prefill(self, req: Request) -> _Unread:
        """Run the model over the whole uncached prompt suffix, chunked;
        returns the last chunk's program, the first generated token unread.

        Chunks of at most ``max_prefill_tokens`` bound activation memory on
        long prompts (vLLM-style chunked prefill); each chunk's KV lands in
        the paged cache so the next chunk attends over it.
        """
        while req.prefill_pos is not None:
            first = self._prefill_chunk(req)
        return first

    def _prefill_chunk(self, req: Request) -> _Unread:
        """One prefill chunk at ``req.prefill_pos``; advances it (None once
        the prompt is fully prefilled) and returns the chunk's program,
        unread. The program samples its last position; only the last
        chunk's token is a token of the request, read by whoever is handed
        the program (``_fetch``): a host read waits for the device, so
        ``step()`` launches its decode program first. That chunk also
        leaves the position's logits row in ``req.last_logits``, on the
        device. An earlier chunk's is read only to wait for it
        (``step()``: an engine with nothing to decode)."""
        page_size = self.cfg.model.page_size
        ph = self._phases
        with phase(ph, PHASE_STEP_INPUTS):
            chunk_cap = max(page_size, self.cfg.max_prefill_tokens
                            // page_size * page_size)
            pos = req.prefill_pos
            chunk = req.prompt[pos:pos + chunk_cap]
            # (Two locals where ``threshold_keys`` had two until PR 59, so
            # the frame keeps its 41 slots and ``hack/frame_sizes.py`` reads
            # no difference here. Whether 39 would move set-up was not
            # measured: ROADMAP S7 only has it that a frame which grew did.)
            new, end = len(chunk), pos + len(chunk)
            # Bucket the padded length to powers of two (in pages) so the
            # jit cache holds O(log max_prefill) shapes instead of one per
            # suffix length — compiles are 20-40 s each on TPU.
            pages_needed = max(1, (new + page_size - 1) // page_size)
            bucket = 1
            while bucket < pages_needed:
                bucket *= 2
            seq = bucket * page_size
            tokens = np.zeros((1, seq), np.int32)
            tokens[0, :new] = chunk
            tables = [self._page_table_for(req)[None, :]]
            if self.hybrid:
                # SWA pages arrive just-in-time for this chunk's blocks and
                # out-of-window slots return to the pool after it, so a
                # long prompt's peak SWA demand is window + chunk.
                self._swa_ensure(req, (end - 1) // page_size)
                tables.append(self._swa_table_for(req)[None, :])
            last = end >= len(req.prompt)
            token_sharding = None
            if self._sp > 1 and seq % self._sp == 0:
                # Sequence-parallel prefill: the chunk's tokens are held
                # sharded on seq inside the program; XLA splits the
                # per-token compute sp-ways (see __init__).
                from jax.sharding import NamedSharding, PartitionSpec as P

                token_sharding = NamedSharding(self.mesh, P(None, "sp"))
            state_args, taken = (), ()
            if self.state_pool is not None:
                snap, taken = self._plan_snapshots(req, pos, new)
                state_args = ([req.state_slot], snap)
            packed, shapes = pack_inputs(
                (tokens, *tables, [pos], [new], *state_args))

        # Every program of a step is dispatched the same way, inside its
        # dispatch phase: its inputs go in one transfer (an argument of the
        # call: built first and held in a local, the arrays made the first
        # call of every program 0.3 s slower on the chip, PERF.md §6, PR 25
        # — why is not known), the pools are donated and taken back, and
        # the copy of the tokens to the host is started behind the call
        # where the host will read them, so that the blocking read in
        # ``step.fetch`` finds it under way. The call stands at each site,
        # not in a helper: through one (a frame more, the pools splatted)
        # every 28-layer program took 1.5-2 s longer to trace and lower on
        # the chip's host (PERF.md §6, PR 31) — why is not known either.
        with self._dispatch_phase(req, 1, new, seq,
                                  self._prefill_forward) as sp:
            token, row, pools = self._prefill_forward(
                self.params, self.cfg.model, self._launch_input(packed, sp),
                self._pools(), shapes=shapes, last_only=True, keep_row=True,
                token_sharding=token_sharding)
            self._take_pools(pools)
            if last:
                token.copy_to_host_async()
            if self.state_pool is not None:
                sp.set_attribute("scan_tokens", new)
        for boundary, slot in taken:
            blocks = boundary // page_size
            self.state_pool.store(
                req.block_hashes[blocks - 1], slot,
                req.block_hashes[:blocks],
                req.block_hashes[blocks - 2] if blocks > 1
                else EMPTY_BLOCK_HASH,
                req.prompt[boundary - page_size:boundary])
            req.snapshots.append(req.block_hashes[blocks - 1])
        req.computed_len = end
        if self.hybrid:
            self._swa_reclaim(req)  # reads computed_len
        if self.telemetry is not None:
            # Padding-waste accounting: ``new`` real tokens rode a
            # seq-token padded dispatch (the power-of-two page bucket).
            self.telemetry.on_dispatch_tokens(new, seq)
        if last:
            req.last_logits = row
        req.prefill_pos = None if last else end
        return _Unread(token, [req], 1, new, self._launch, "prefill")

    def _commit_full_blocks(self, req: Request,
                            upto: Optional[int] = None) -> None:
        """Register newly computed full prompt blocks in the prefix cache.

        ``upto`` (prefill-role chunk commits) caps the commit at that many
        leading blocks: each prefill chunk's full blocks enter the prefix
        cache and the write-through store as they are computed instead of
        at prefill end, so a decode peer can start pulling chunk 1 while
        chunk 2 is still on the device.
        """
        page_size = self.cfg.model.page_size
        n_full = len(req.prompt) // page_size
        if upto is not None:
            n_full = min(n_full, upto)
        # committed_blocks, not cached_len: incremental chunk commits
        # advance it past the admission prefix (they never touch
        # cached_len — prefill_pos still walks the raw prompt).
        first_new = max(req.committed_blocks, req.cached_len // page_size)
        if n_full <= first_new:
            return
        new_hashes = req.block_hashes[first_new:n_full]
        new_pages = req.pages[first_new:n_full]
        tokens_per_block = [
            req.prompt[i * page_size:(i + 1) * page_size]
            for i in range(first_new, n_full)
        ]
        parent = (
            req.block_hashes[first_new - 1] if first_new > 0 else EMPTY_BLOCK_HASH
        )
        canonical = self.block_manager.commit_blocks(
            new_hashes, new_pages, tokens_per_block, parent
        )
        # Adopt canonical pages (duplicates swapped to the resident copy).
        req.pages[first_new:n_full] = canonical
        req.committed_blocks = max(req.committed_blocks, n_full)
        if self.hybrid:
            # Commit only slots still holding pages: blocks that already
            # fell out of the window were reclaimed mid-prefill and are
            # gone from group 1 by design.
            swa_first = max(first_new, req.swa_acquired_from)
            if swa_first < n_full:
                swa_parent = (
                    req.block_hashes[swa_first - 1] if swa_first > 0
                    else EMPTY_BLOCK_HASH
                )
                swa_canonical = self.swa_manager.commit_blocks(
                    req.block_hashes[swa_first:n_full],
                    req.swa_pages[swa_first:n_full],
                    [req.prompt[i * page_size:(i + 1) * page_size]
                     for i in range(swa_first, n_full)],
                    swa_parent,
                )
                req.swa_pages[swa_first:n_full] = swa_canonical

        # Write-through to the storage tier (async; writes may be shed under
        # pressure, degrading to future cache misses).
        if self.offload_handlers is not None:
            self._sync_caches_to_copier()
            to_store = self.offload_manager.prepare_store(new_hashes)
            if to_store:
                page_of = dict(zip(new_hashes, canonical))
                job = self.offload_handlers.async_store_blocks(
                    [(h, [page_of[h]]) for h in to_store]
                )
                self._pending_store_jobs[job] = list(to_store)
                if self.handoff is not None and self.cfg.role == "prefill":
                    # One handoff chunk per store job: the coordinator
                    # hears landed/failed from the drain that settles it.
                    self._handoff_store_jobs[job] = (
                        req.request_id, list(to_store))
                    self.handoff.on_chunk_start(req.request_id, to_store)
            if self.hybrid and swa_first < n_full:
                # Group 1: only the in-window committed blocks exist; they
                # are exactly what a trailing-window restore needs.
                # Deliberately NOT registered in _pending_store_jobs: the
                # storage BlockStored advertisement is group-untagged and
                # must assert a RESTORABLE state, which for hybrid means
                # the group-0 chain — whose own store job publishes it.
                # A group-1 file without its group-0 chain (e.g. the
                # group-0 write shed) must not be advertised.
                swa_hashes = req.block_hashes[swa_first:n_full]
                to_store1 = self.offload_manager.prepare_store(
                    swa_hashes, group_idx=1)
                if to_store1:
                    spage_of = dict(
                        zip(swa_hashes, req.swa_pages[swa_first:n_full]))
                    self.offload_handlers.async_store_blocks(
                        [(h, [spage_of[h]]) for h in to_store1], group_idx=1,
                    )

    # -- decode --

    def _pick_prefill(self) -> Optional[Request]:
        """The scheduler's pick: the FIFO head among requests still in
        prefill, or None while its restore or handoff gate holds it."""
        tel = self.telemetry
        prefill_req: Optional[Request] = None
        # Start every pending deferred restore up front, not just the FIFO
        # head's: the loads are independent DMA jobs, so a younger request's
        # storage fetch overlaps the older request's restore+prefill instead
        # of paying for it serially (kvio tracks multiple outstanding jobs).
        for rid in list(self._running):
            req = self.requests[rid]
            if req.prefill_pos is not None and req.restore_pending:
                self._start_deferred_restore(req)
        for rid in list(self._running):
            req = self.requests[rid]
            if req.prefill_pos is not None:
                if req.enqueued_at is not None:
                    # First scheduler pick: the wait is the queueing
                    # behind older prefills and the step in flight. A
                    # deferred storage restore may still follow — that wait
                    # is a storage cost (kv_offload_*), deliberately not
                    # part of this scheduling metric.
                    from ..metrics.collector import record_admission_delay

                    admission_delay = time.monotonic() - req.enqueued_at
                    record_admission_delay(admission_delay)
                    if self.shedder is not None:
                        # CoDel signal: sustained admission delay above
                        # the target trips brownout/shed at enqueue.
                        self.shedder.observe_delay(admission_delay)
                    if tel is not None:
                        tel.on_first_schedule(rid, req.enqueued_at)
                    req.enqueued_at = None
                # Deferred storage restore (enqueue path): started above on
                # the request's first step, polled here across steps —
                # decodes keep running below while the load is in flight.
                if req.restore_job is not None:
                    if not self._poll_deferred_restore(req):
                        break
                # Handoff wait (decode role): hold this request's local
                # prefill while the prefill peer's transfer is live,
                # re-arming the restore probe as chunks land. Decodes
                # below keep running the whole time.
                if req.handoff_deadline is not None:
                    if not self._handoff_gate(req):
                        break
                prefill_req = req
                break
        return prefill_req

    def step(self) -> dict[str, int]:
        """One scheduling step: advance at most one prefill chunk, then one
        decode step for every decoding request.

        Returns {request_id: newest_token}. Decode is batched into a single
        jit call with padding up to max_batch. ``enqueue``d requests
        prefill here, chunk-at-a-time — a long prompt delays running
        decodes by one chunk per step, never its whole prefill.
        """
        tel = self.telemetry
        step_t0 = tel.begin_step() if tel is not None else 0.0
        ph = self._phases
        if ph is not None:
            ph.begin_step()
        with phase(ph, PHASE_STEP_OFFLOAD_POLL):
            self.poll_offload()
        emitted: dict[str, int] = {}
        # Continuous batching: one prefill chunk for the oldest admitted-
        # but-not-yet-decoding request (FIFO — finish one prefill before
        # starting the next so TTFTs don't all pay for each other).
        with phase(ph, PHASE_STEP_SCHEDULE):
            prefill_req = self._pick_prefill()
            # (Before the chunk below takes its last request out of it.)
            prefilling = any(self.requests[rid].prefill_pos is not None
                             for rid in self._running)
        if self._ragged:
            # Ragged scheduling: the prefill chunk and every active decode
            # row pack into one flat-axis dispatch (the prefill bootstrap
            # token still lands next step, exactly as on the padded path).
            emitted.update(self._ragged_step(prefill_req))
        else:
            chunk = first = None  # this step's chunk; it, if it finishes
            if prefill_req is not None:
                req = prefill_req
                chunk = self._prefill_chunk(req)
                if req.prefill_pos is None:
                    first = chunk
                if (req.prefill_pos is not None and self.handoff is not None
                        and self.cfg.role == "prefill"):
                    # Prefill pod: commit this chunk's full blocks NOW so
                    # the transfer streams chunk-granular (the final
                    # chunk commits in _finish_prefill as usual).
                    self._commit_prefill_chunk(req)
            # A request whose prefill finishes here starts to decode next
            # step: in this step's decode batch it would overwrite the
            # bootstrap token emitted below (a streaming caller would lose
            # one token).
            active = [req for req in map(self.requests.get, self._running)
                      if not req.done and req.prefill_pos is None
                      and (first is None or req is not first.rows[0])]
            # (The jitted call stays one frame under ``step()``, as it
            # was: see ``_prefill_chunk``.)
            cur, self._unread = self._unread, None
            one = len(active) <= self.cfg.max_batch
            if cur is None and not (self._defers and one):
                # Sharded engines (``_defers``), several chunks: each
                # program is read before the next is built.
                emitted.update(self._read_first(first))
                for at in range(0, len(active), self.cfg.max_batch):
                    chunk = active[at:at + self.cfg.max_batch]
                    emitted.update(
                        self._read_decode(self._launch_decode(chunk)))
            else:
                # ``cur``: the decode program this step returns the tokens
                # of, launched a step ago or now. While this engine only
                # decodes and has the chip to itself, the next one is
                # launched before ``cur`` is read and takes its tokens on
                # the device: the way back, this step's bookkeeping, the
                # caller's and the next step's inputs then run beside a
                # busy chip. Nothing else of step N+1 depends on N: a
                # request stops by count, its pages were allocated at
                # admission, its context grows by one. Both conditions are
                # read from what the engine sees:
                # - no request of its own in prefill (``prefilling``): a
                #   program launched ahead never stands beside a chunk of
                #   this engine, so the engine is at most one decode
                #   program ahead of the device and never a chunk (a
                #   chunk is twenty decode programs long, and whatever
                #   arrives at the other replica waits behind it). A
                #   request admitted while a program is in flight finds
                #   that program read here as ``cur``: no drain;
                # - no other engine's program between this engine's last
                #   two decode programs or behind them (the launch
                #   numbers: ``_launch_input``). Two busy replicas each
                #   keep one program in the queue.
                if cur is None and active:
                    cur = self._launch_decode(active)
                if (cur is not None and one and not prefilling
                        and self._lone_decodes >= 2):
                    # A row whose unread token(s) may be its last is left out.
                    ahead = [req for req in active if not (
                        len(req.output) + self._step_tokens
                        >= req.max_new_tokens
                        and cur.row_of(req) >= 0)]
                    if ahead:
                        self._unread = self._launch_decode(ahead, cur)
                # A finishing chunk's token is read, and its blocks are
                # committed, beside the decode program just launched.
                emitted.update(self._read_first(first))
                if cur is not None:
                    emitted.update(self._read_decode(cur))
            # (In a method of its own: with these lines here, every
            # 28-layer program took 0.15 s longer to lower on the chip's
            # host, 6 s of set-up: PERF.md section 6, PR 43; as with the
            # helper frame of PR 31, why is not known.)
            self._bound_chunks(chunk, bool(emitted))
        with phase(ph, PHASE_STEP_FINISH) as sp:
            for rid in list(self._running):
                req = self.requests[rid]
                if req.done:
                    self._finish(req)
            if tel is not None:
                tel.on_step(time.monotonic() - step_t0, bool(emitted),
                            self._telemetry_pools, prefill_req is not None)
                # The step's counters ride its last phase.
                sp.set_attribute("programs", ph.programs)
                sp.set_attribute("transfers", ph.transfers)
        return emitted

    def _bound_chunks(self, chunk: Optional[_Unread], read: bool) -> None:
        """The end of a padded ``step()`` that launched ``chunk`` (or none)
        and ``read`` something or nothing. A step that read nothing behind
        its chunk (nothing to decode, the prompt not finished) has waited
        for nothing: left so, an engine sends a whole document's chunks
        ahead of the device, as many as the runtime keeps in flight (32 of
        113-180 ms on the chip: PERF.md section 6, PR 43), and whatever the
        other replica launches next stands seconds behind them. It waits
        for the chunk before this one: one chunk runs, one is queued behind
        it, and the engine is no further ahead."""
        behind, self._chunk_ahead = self._chunk_ahead, None
        if chunk is not None and not read:
            self._chunk_ahead = chunk
            if behind is not None:
                self._fetch(behind)

    def _drain_offload(self, target_job: Optional[int] = None):
        results = self._drain_offload_multi(
            {target_job} if target_job is not None else frozenset())
        return results.get(target_job)

    def _drain_offload_multi(self, targets) -> dict:
        """Single dispatcher for offload completions.

        Every finished job is routed here exactly once: store jobs publish
        their storage events (minus shed blocks); results of awaited jobs
        (ids in ``targets``) are returned — a multi-job await must pass
        ALL its ids in one set, or the drain that surfaces one job drops
        the others' results. Cache references are re-synced after the
        drain because load scatters donate-and-replace the pools.
        """
        from ..metrics.collector import (
            record_io_pool_placement,
            record_offload_result,
        )

        results: dict = {}
        # Placement gauges exist only for backends with a native I/O pool
        # (the object-store backend transfers through its client library).
        io_pool = getattr(self.offload_handlers, "io", None)
        if io_pool is not None:
            record_io_pool_placement(io_pool)
        self._sync_caches_to_copier()
        try:
            for res in self.offload_handlers.get_finished():
                record_offload_result(self._offload_medium, res)
                hashes = self._pending_store_jobs.pop(res.job_id, None)
                if hashes is not None:
                    if res.success:
                        shed = set(res.shed_hashes)
                        stored = [h for h in hashes if h not in shed]
                        if stored:
                            self.offload_manager.complete_store(stored)
                    else:
                        logger.warning("write-through store job %d failed", res.job_id)
                ho = self._handoff_store_jobs.pop(res.job_id, None)
                if ho is not None and self.handoff is not None:
                    # Prefill-role chunk commit settled: stream the chunk
                    # completion (or its failure) to the coordinator so the
                    # decode peer's next probe sees the landed blocks.
                    ho_rid, ho_hashes = ho
                    if res.success:
                        shed = set(res.shed_hashes)
                        landed = [h for h in ho_hashes if h not in shed]
                        if landed:
                            self.handoff.on_chunk_landed(
                                ho_rid, landed,
                                shed=[h for h in ho_hashes if h in shed])
                        else:
                            self.handoff.on_chunk_failed(ho_rid, ho_hashes)
                    else:
                        self.handoff.on_chunk_failed(ho_rid, ho_hashes)
                if res.corrupt_hashes and self.offload_manager is not None:
                    # Checksum-failed files are already quarantined by the
                    # worker; de-advertise the blocks so no index view keeps
                    # routing to the storage tier for them.
                    self.offload_manager.complete_load_failure(res.corrupt_hashes)
                if res.job_id in targets:
                    results[res.job_id] = res
                elif res.job_id in self._restore_job_ids:
                    self._restore_results[res.job_id] = res
        finally:
            self._sync_caches_from_copier()
        return results

    def poll_offload(self) -> None:
        """Reap finished offload jobs (called each step)."""
        if self.offload_handlers is None:
            return
        self._drain_offload()

    def flush_offload(self, timeout_s: float = 30.0) -> None:
        """Block until all pending store jobs complete (testing/shutdown)."""
        deadline = time.monotonic() + timeout_s
        while self._pending_store_jobs and time.monotonic() < deadline:
            self.poll_offload()
            time.sleep(0.005)

    def _finish(self, req: Request, outcome: str = "finished") -> None:
        rec = self._unread
        if rec is not None and rec.row_of(req) >= 0:
            # Ended with its token unread (an abort, a reset): the token is
            # dropped, and the program has run before the pages go back.
            self._drain(outcome)
            if all(row.done for row in rec.rows):
                self._unread = None
        if self.telemetry is not None:
            self.telemetry.on_finish(req.request_id, outcome)
        if req.handoff_deadline is not None:
            # Aborted while waiting on a transfer: settle the ledger so
            # the coordinator never holds a ghost entry.
            self._handoff_settle(req, "failed")
        if (self.handoff is not None and self.cfg.role == "prefill"
                and req.prefill_pos is not None):
            # Prefill-role death/abort mid-prefill: no more chunks will
            # ever commit — flip the transfer failed so the decode peer
            # stops waiting and re-prefills the remainder itself.
            self.handoff.fail(req.request_id, outcome)
        if req.restore_job is not None:
            # Abort with a deferred restore in flight: non-blocking cancel —
            # kvio marks the job cancelled (never scatters) and parks its
            # staging buffers, so recycling the pages is safe immediately.
            self.offload_handlers.wait_job(req.restore_job[0], timeout_s=0.0)
            self._restore_job_ids.discard(req.restore_job[0])
            self._restore_results.pop(req.restore_job[0], None)
            req.restore_job = None
        if req.request_id in self._running:
            self._running.remove(req.request_id)
        self._release(req)
        # Drop the bookkeeping entry: callers keep the Request object they
        # got from add_request; retaining every finished request would grow
        # host memory unboundedly on a serving pod.
        self.requests.pop(req.request_id, None)

    def _ragged_step(self, prefill_req: Optional[Request]) -> dict[str, int]:
        """One scheduling step on the ragged single-kernel path.

        Active decode rows still group into chunks of ``max_batch`` (the
        same per-dispatch activation bound as the padded path); the FIFO
        head's prefill chunk rides the first dispatch as one extra long
        row. A request that finishes prefill here was assembled BEFORE
        its bootstrap token existed, so it cannot also decode this step —
        the padded path's ``just_prefilled`` exclusion, structurally.
        """
        emitted: dict[str, int] = {}
        active = [self.requests[rid] for rid in self._running
                  if not self.requests[rid].done
                  and self.requests[rid].prefill_pos is None]
        b = self.cfg.max_batch
        chunks = [active[i:i + b] for i in range(0, len(active), b)]
        if not chunks:
            chunks = [[]]
        for ci, chunk in enumerate(chunks):
            p_req = prefill_req if ci == 0 else None
            if not chunk and p_req is None:
                continue
            emitted.update(self._ragged_dispatch(chunk, p_req))
        return emitted

    def _ragged_dispatch(self, decode_rows: list[Request],
                         prefill_req: Optional[Request]) -> dict[str, int]:
        """One mixed prefill+decode dispatch over the flat ragged axis.

        Decode rows are 1-token rows; the prefill chunk (when present) is
        the last, longer row. The flat token axis buckets to a power of
        two (min 8 — the ragged q tile) and the row axis to a power of
        two, so the jit cache stays O(log max_batch · log tokens); padding
        rows are empty (``row_starts[r] == row_starts[r+1]``) and never
        enter the kernel's row loop — the per-token waste the pool
        counters measure is the bucket tail, not ``max_batch`` dead rows.
        """
        ph = self._phases
        with phase(ph, PHASE_STEP_INPUTS):
            page_size = self.cfg.model.page_size
            q_lens: list[int] = []
            ctxs: list[int] = []
            tables_list: list[np.ndarray] = []
            flat_tokens: list[int] = []
            for req in decode_rows:
                flat_tokens.append(
                    req.output[-1] if req.output else req.prompt[-1])
                q_lens.append(1)
                ctxs.append(req.computed_len)
                tables_list.append(self._page_table_for(req))
            p_chunk: list[int] = []
            p_pos = 0
            if prefill_req is not None:
                chunk_cap = max(page_size, self.cfg.max_prefill_tokens
                                // page_size * page_size)
                p_pos = prefill_req.prefill_pos
                p_chunk = list(prefill_req.prompt[p_pos:p_pos + chunk_cap])
                flat_tokens.extend(p_chunk)
                q_lens.append(len(p_chunk))
                ctxs.append(p_pos)
                tables_list.append(self._page_table_for(prefill_req))

            rows = len(q_lens)
            t_real = len(flat_tokens)
            t_pad = 8
            while t_pad < t_real:
                t_pad *= 2
            rows_pad = 1
            while rows_pad < rows:
                rows_pad *= 2

            tokens = np.zeros((1, t_pad), np.int32)
            tokens[0, :t_real] = flat_tokens
            # Padding rows are empty: start == end == t_real, zero tables,
            # ctx 0 — the kernel's block metadata never reaches them.
            row_starts = np.full((rows_pad + 1,), t_real, np.int32)
            row_starts[:rows + 1] = np.concatenate(
                [[0], np.cumsum(q_lens)]).astype(np.int32)
            ctx = np.zeros((rows_pad,), np.int32)
            ctx[:rows] = ctxs
            tables = np.zeros((rows_pad, self.cfg.max_pages_per_seq), np.int32)
            for i, t in enumerate(tables_list):
                tables[i] = t
            packed, shapes = pack_inputs((tokens, tables, row_starts, ctx))

        # The prefill row's logits ARE its final valid token's (the ragged
        # forward computes one row per ragged row), so its token is the
        # row's like any other's.
        finishing = (prefill_req is not None
                     and p_pos + len(p_chunk) >= len(prefill_req.prompt))
        # Dispatched as every program of a step is: see _prefill_chunk.
        with self._dispatch_phase(prefill_req, rows, t_real, t_pad,
                                  step_ragged) as sp:
            picked, last_row, pools = step_ragged(
                self.params, self.cfg.model, self._launch_input(packed, sp),
                self._pools(), shapes=shapes,
                keep_row=prefill_req is not None,
                interpret=self._ragged_interpret)
            self._take_pools(pools)
            if decode_rows or finishing:
                picked.copy_to_host_async()

        tel = self.telemetry
        if tel is not None:
            tel.on_dispatch_tokens(t_real, t_pad)

        out: dict[str, int] = {}
        if decode_rows or finishing:
            with self._fetch_phase(self._launch):
                next_tokens = np.asarray(picked)
        if decode_rows:
            now = time.monotonic() if tel is not None else 0.0
            for i, req in enumerate(decode_rows):
                req.computed_len += 1
                tok = int(next_tokens[i])
                req.output.append(tok)
                out[req.request_id] = tok
                self._row_emitted(req, now)

        if prefill_req is not None:
            req = prefill_req
            req.computed_len = p_pos + len(p_chunk)
            if finishing:
                req.prefill_pos = None
                req.last_logits = last_row
                self._finish_prefill(req, int(next_tokens[rows - 1]))
                if req.output:
                    out[req.request_id] = req.output[-1]
            else:
                req.prefill_pos = p_pos + len(p_chunk)
                if self.handoff is not None and self.cfg.role == "prefill":
                    self._commit_prefill_chunk(req)
        return out

    def _row_emitted(self, req: Request, now: float, n: int = 1) -> None:
        """Bookkeeping of one row's ``n`` newly decoded tokens."""
        if self.telemetry is not None:
            self.telemetry.on_decode_tokens(req.request_id, n, now)
        if req.traceparent is not None:
            # Event-style span: marks the emission point in the trace.
            span_event(SPAN_ENGINE_DECODE_STEP, req.traceparent,
                       request_id=req.request_id, tokens=n,
                       computed_len=req.computed_len,
                       process=self.cfg.pod_identifier)
        if len(req.output) >= req.max_new_tokens:
            req.done = True

    def _decode_batch_arrays(self, chunk: list[Request], rows: int = 0):
        """Padded per-row decode inputs: (last tokens, computed context,
        page tables). The last token may have come from sampling with its
        KV not yet computed — that is why positions derive from
        ``computed_len``. ``rows`` overrides the ``max_batch`` padding
        target (the unpipelined-pp decode bucket)."""
        b = rows or self.cfg.max_batch
        last = np.zeros((b,), np.int32)
        ctx = np.zeros((b,), np.int32)
        tables = np.zeros((b, self.cfg.max_pages_per_seq), np.int32)
        for i, req in enumerate(chunk):
            last[i] = req.output[-1] if req.output else req.prompt[-1]
            ctx[i] = req.computed_len
            tables[i] = self._page_table_for(req)
        return last, ctx, tables

    def _decode_chunk(self, chunk: list[Request]) -> dict[str, int]:
        """One decode step of ``chunk``, read at once."""
        return self._read_decode(self._launch_decode(chunk))

    def _launch_decode(self, chunk: list[Request],
                       unread: Optional[_Unread] = None) -> _Unread:
        """Launch one decode step of ``chunk`` and leave its tokens on the
        device. ``unread``: the decode program before it, whose tokens the
        host has not taken into ``req.output`` yet: a row of it takes its
        token from there, on the device, and stands one token further."""
        # Pad to max_batch so decode compiles exactly once regardless of the
        # active-request count; padded rows have new_lens=0 (all writes go
        # to the garbage page, logits ignored).
        b = self.cfg.max_batch
        if self._pp > 1 and self._pp_decode_mb == 1:
            # Unpipelined pp decode (max_batch % pp != 0 — warned at
            # construction): the M=1 schedule accepts ANY batch size, so
            # padding dead rows to max_batch only burns per-stage FLOPs.
            # Pad to the power-of-two bucket instead (O(log max_batch)
            # compiled shapes); the pipelined schedule keeps the fixed
            # max_batch shape its microbatch split requires.
            b = 1
            while b < len(chunk):
                b *= 2
            b = min(b, self.cfg.max_batch)
        ph = self._phases
        with phase(ph, PHASE_STEP_INPUTS):
            last, ctx, tables = self._decode_batch_arrays(chunk, rows=b)
            new_lens = np.zeros((b,), np.int32)
            for i in range(len(chunk)):
                new_lens[i] = 1
            state_args = ()
            if self.state_pool is not None:
                slots = np.zeros((b,), np.int32)  # rows without: the spare
                slots[:len(chunk)] = [req.state_slot for req in chunk]
                state_args = (slots, [0, 0, 0])
            # Launched ahead: the program before it is still unread.
            ahead = unread is not None and unread.host is None
            operand = {}
            if self._defers:
                # Every program of this form takes the last one's tokens,
                # whether any row reads them or not: one program compiled.
                src = np.full((b,), -1, np.int32)
                if unread is not None:
                    src[:len(chunk)] = [unread.row_of(req) for req in chunk]
                    ctx[src >= 0] += 1
                state_args += (src,)
                operand["prev"] = self._prev
            swa_tables = self._window_tables(chunk, ctx) if self.hybrid else ()
            packed, shapes = pack_inputs(
                (last[:, None], tables, *swa_tables, ctx, new_lens,
                 *state_args))

        # Dispatched as every program of a step is: see _prefill_chunk.
        with self._dispatch_phase(None, len(chunk), len(chunk), b,
                                  self._decode_forward) as sp:
            picked, _, pools = self._decode_forward(
                self.params, self.cfg.model,
                self._launch_input(packed, sp, decode=True),
                self._pools(), shapes=shapes, **operand)
            self._take_pools(pools)
            picked.copy_to_host_async()
            if self._defers:
                self._prev = picked
                sp.set_attribute("ahead", int(ahead))
            if self.state_pool is not None:
                sp.set_attribute("state_rows", len(chunk))
            if self.hybrid and sp is not NOOP_SPAN:
                self._pool_keys(sp, ctx[:len(chunk)] + 1)
            topk = self.cfg.model.index_topk
            if topk and sp is not NOOP_SPAN:
                # What the step's selection reads a layer, from the rows'
                # lengths: the index keys of the rows that score (more
                # than index_topk keys) and the latents every row attends.
                keys = ctx[:len(chunk)] + 1
                sp.set_attribute(
                    "index_keys", int(keys[keys > topk].sum()))
                sp.set_attribute(
                    "selected_keys", int(np.minimum(keys, topk).sum()))
        tel = self.telemetry
        if tel is not None:
            # Padding-waste accounting for the padded path: len(chunk)
            # real tokens ride a b-row dispatch. The same counters feed
            # from the ragged path, so the waste ratio directly compares
            # the two schedulers.
            tel.on_dispatch_tokens(len(chunk), b)
            if ahead:
                tel.on_launched_ahead()
        return _Unread(picked, chunk, b, len(chunk), self._launch, "decode")

    def _read_decode(self, rec: _Unread) -> dict[str, int]:
        """Take a decode program's tokens into its rows: what ``step()``
        returns of them. A row aborted since the launch drops its token."""
        next_tokens = self._fetch(rec)
        out = {}
        now = time.monotonic() if self.telemetry is not None else 0.0
        for i, req in enumerate(rec.rows):
            if req.done:
                continue
            req.computed_len += 1
            tok = int(next_tokens[i])
            req.output.append(tok)
            out[req.request_id] = tok
            self._row_emitted(req, now)
            if self.hybrid:
                self._swa_reclaim(req)
        return out

    # -- a model that drafts (``LlamaConfig.num_nextn_predict_layers``) --
    # Its step programs are ``llama.DRAFTING_PROGRAMS`` and the three
    # methods that build, launch and read a program have forms of their own
    # below, bound over the others at construction: ``step()`` and the frames
    # under every other model's programs stay what they are.

    def _serve_drafting(self, use_pallas: bool, prefill_pallas: bool,
                        interpret: bool, offload_spec) -> None:
        """Construction's last part for a model with a prediction module:
        what is not built beside it refuses by name, and the speculative
        programs and step methods take the others' places."""
        for unfit, why in (
                (self.mesh is not None,
                 "a mesh (the verify step is one program on one device)"),
                (self.cfg.ragged_attention,
                 "ragged_attention (the draft is verified by the padded "
                 "decode program)"),
                (offload_spec is not None,
                 "an offload spec (a restored prefix moves the hit, and the "
                 "module's row at its first new slot needs the hidden state "
                 "of the position before it)")):
            if unfit:
                raise ValueError(
                    "a model with a prediction module verifies its draft "
                    "in every decode step and is not served with " + why)
        self._step_tokens = 2
        self._decode_forward = functools.partial(
            DRAFTING_PROGRAMS[(use_pallas, False)], interpret=interpret)
        self._prefill_forward = functools.partial(
            DRAFTING_PROGRAMS[(prefill_pallas and use_pallas, True)],
            interpret=interpret)
        self._prefill_chunk = self._prefill_chunk_drafting
        self._launch_decode = self._launch_decode_drafting
        self._read_decode = self._read_decode_drafting
        # A row of ``prev``: two tokens, how many count, the next draft.
        self._prev = jax.device_put(
            np.zeros((4 * self.cfg.max_batch
                      + len(self.cfg.model.step_counters),), np.int32),
            self._device)

    def _prefill_chunk_drafting(self, req: Request) -> _Unread:
        """``_prefill_chunk`` of a model that drafts: the chunk's program
        also runs the module over it, handed the token after the chunk (-1
        at the prompt's end: the one it samples), and returns the first
        draft behind the sampled token."""
        page_size = self.cfg.model.page_size
        with phase(self._phases, PHASE_STEP_INPUTS):
            chunk_cap = max(page_size, self.cfg.max_prefill_tokens
                            // page_size * page_size)
            pos = req.prefill_pos
            chunk = req.prompt[pos:pos + chunk_cap]
            end = pos + len(chunk)
            seq = page_size  # a power of two of pages: see _prefill_chunk
            while seq < len(chunk):
                seq *= 2
            tokens = np.zeros((1, seq), np.int32)
            tokens[0, :len(chunk)] = chunk
            last = end >= len(req.prompt)
            packed, shapes = pack_inputs(
                (tokens, self._page_table_for(req)[None, :], [pos],
                 [len(chunk)], [-1 if last else req.prompt[end]]))
        with self._dispatch_phase(req, 1, len(chunk), seq,
                                  self._prefill_forward) as sp:
            picked, row, pools = self._prefill_forward(
                self.params, self.cfg.model, self._launch_input(packed, sp),
                self._pools(), shapes=shapes)
            self._take_pools(pools)
            if last:
                picked.copy_to_host_async()
        req.computed_len = end
        if self.telemetry is not None:
            self.telemetry.on_dispatch_tokens(len(chunk), seq)
        if last:
            req.last_logits = row
        req.prefill_pos = None if last else end
        return _Unread(picked, [req], 2, len(chunk), self._launch, "prefill")

    def _launch_decode_drafting(self, chunk: list[Request],
                                unread: Optional[_Unread] = None) -> _Unread:
        """``_launch_decode`` of a model that drafts: every row runs its
        last token and its draft (two positions). A row of ``unread`` takes
        its token, its draft AND how far its context has grown from that
        program's result on the device: the host knows none of them yet."""
        b = self.cfg.max_batch
        with phase(self._phases, PHASE_STEP_INPUTS):
            last, ctx, tables = self._decode_batch_arrays(chunk, rows=b)
            new_lens = np.zeros((b,), np.int32)
            new_lens[:len(chunk)] = 1
            drafts = np.zeros((b,), np.int32)
            drafts[:len(chunk)] = [req.draft for req in chunk]
            ahead = unread is not None and unread.host is None
            src = np.full((b,), -1, np.int32)
            if unread is not None:
                src[:len(chunk)] = [unread.row_of(req) for req in chunk]
            packed, shapes = pack_inputs(
                (last[:, None], tables, ctx, new_lens, drafts, src))
        with self._dispatch_phase(None, len(chunk), 2 * len(chunk), b,
                                  self._decode_forward) as sp:
            picked, _, pools = self._decode_forward(
                self.params, self.cfg.model,
                self._launch_input(packed, sp, decode=True),
                self._pools(), shapes=shapes, prev=self._prev)
            self._take_pools(pools)
            picked.copy_to_host_async()
            self._prev = picked
            sp.set_attribute("ahead", int(ahead))
            sp.set_attribute("spec_drafted", len(chunk))
        tel = self.telemetry
        if tel is not None:
            tel.on_dispatch_tokens(2 * len(chunk), 2 * b)
            if ahead:
                tel.on_launched_ahead()
        return _Unread(picked, chunk, 4 * b, 2 * len(chunk), self._launch,
                       "decode")

    def _read_drafts(self, sp, rec: _Unread) -> None:
        """What a drafting model's program says of drafts, as it is read.
        A prefill chunk: the first draft, behind the token (the last
        chunk's is the request's). A decode program, onto its
        ``step.fetch``: the drafts it verified (one a row) and those it
        accepted (rows that count two tokens), where that is first known."""
        if rec.program == "prefill":
            rec.rows[0].draft = int(rec.host[1])
            return
        b = rec.padded // 4
        accepted = int((rec.host[2 * b:2 * b + len(rec.rows)] == 2).sum())
        if sp is not NOOP_SPAN:
            sp.set_attribute("spec_drafted", len(rec.rows))
            sp.set_attribute("spec_accepted", accepted)
        if self.telemetry is not None:
            self.telemetry.on_drafts_verified(len(rec.rows), accepted)

    def _read_decode_drafting(self, rec: _Unread) -> dict[str, int]:
        """``_read_decode`` of a model that drafts: a row takes the one or
        two tokens its program counted, never past ``max_new_tokens`` (a
        second token beyond it is dropped), and the next draft."""
        host = self._fetch(rec)
        b = rec.padded // 4
        out = {}
        now = time.monotonic() if self.telemetry is not None else 0.0
        for i, req in enumerate(rec.rows):
            if req.done:
                continue
            took = [int(host[i]), int(host[b + i])][:min(
                int(host[2 * b + i]), req.max_new_tokens - len(req.output))]
            req.computed_len += len(took)
            req.output.extend(took)
            req.draft = int(host[3 * b + i])
            out[req.request_id] = took[-1]
            self._row_emitted(req, now, len(took))
        return out

    def _read_first(self, first: Optional[_Unread]) -> dict[str, int]:
        """A finishing prefill chunk's token read and its request
        bootstrapped (``_finish_prefill``): what ``step()`` returns of it."""
        if first is None:
            return {}
        req = first.rows[0]
        self._finish_prefill(req, int(self._fetch(first)[0]))
        return {req.request_id: req.output[-1]}

    def _release(self, req: Request) -> None:
        page_size = self.cfg.model.page_size
        n_hashed = min(len(req.prompt) // page_size, len(req.block_hashes))
        # Only blocks up to the committed watermark are registered in the
        # block manager; an aborted mid-prefill request's later pages are
        # private and must be freed directly (releasing their hashes would
        # no-op on the unknown keys and leak the pages).
        n_comm = min(req.committed_blocks, n_hashed)
        committed_pages = set(req.pages[:n_comm])
        orphans = [p for p in req.pages[n_comm:] if p not in committed_pages]
        self.block_manager.release(req.block_hashes[:n_comm], orphans)
        if self.state_pool is not None:
            self.state_pool.release(req.request_id)
            # Left by a prefill that never committed the blocks under them.
            self.state_pool.forget(req.snapshots)
        if self.hybrid:
            # SWA group: this request references blocks from
            # swa_acquired_from onward (earlier slots were reclaimed as
            # the window slid, their refs already dropped). Committed
            # blocks stay cached — a committed SWA block i always serves
            # a resume at boundary i+1, so none is resume-worthless (see
            # _swa_reclaim); LRU pressure eviction reclaims space and
            # emits BlockRemoved.
            start = req.swa_acquired_from
            swa_committed_pages = set(req.swa_pages[:n_comm])
            swa_orphans = [p for p in req.swa_pages[n_comm:]
                           if p and p not in swa_committed_pages]
            self.swa_manager.release(
                req.block_hashes[start:n_comm], swa_orphans)

    # -- lifecycle --

    def abort_request(self, request_id: str) -> bool:
        """Preempt a running request: release its pages and references.

        The offload analogue of the reference's wait_job cancellation path
        (request aborted mid-transfer): pending write-through stores for
        its blocks are harmless (content-addressed, idempotent) and are
        left to complete; an in-flight deferred restore is cancelled-and-
        waited in ``_finish`` before its pages are released.
        Returns False for unknown/finished requests.
        """
        req = self.requests.get(request_id)
        if req is None or req.done:
            return False
        req.done = True
        self._finish(req, outcome="aborted")
        return True

    def reset_cache(self) -> None:
        """Drop all KV state (e.g. after a weight update).

        In-flight requests are aborted and *released* first so their
        unhashed pages (partial tail + decode room) return to the pool —
        ``clear()`` only frees pages registered in the block map.
        """
        for rid in list(self._running):
            req = self.requests[rid]
            req.done = True
            self._finish(req, outcome="aborted")
        self.block_manager.clear()
        if self.hybrid:
            self.swa_manager.clear(emit=False)

    def generate(self, request_id: str, prompt: Sequence[int],
                 max_new_tokens: int = 16) -> list[int]:
        """Convenience: admit one request and run it to completion."""
        req = self.add_request(request_id, prompt, max_new_tokens)
        while not req.done:
            self.step()
        return req.output
