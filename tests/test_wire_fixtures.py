"""Foreign-wire golden fixtures: committed bytes → adapters → pool → index.

VERDICT r2 missing #1: the adapter suite encoded its own fixtures with the
same msgpack library the adapters decode with, so a shared quirk would pass
here and fail in the fleet. These tests decode **committed .bin payloads
assembled byte-by-byte from the msgpack spec** (tests/wire_spec.py), which
replicate msgspec's (vLLM) and vmihailenco/msgpack's (the reference's Go
tests, ``vllm_adapter_test.go:25-56``) encoding decisions — shortest-form
ints, trailing-default omission, float64 timestamps, bin digests, nested
blobs. The full-fixture vector mirrors the reference Go test's semantic
values so parity is line-checkable.
"""

import itertools
import pathlib
import struct
import time

import pytest
import zmq

import wire_spec
from test_zmq_integration import wait_until

from llmd_kv_cache_tpu.core import ChunkedTokenDatabase, TokenProcessorConfig
from llmd_kv_cache_tpu.events import Pool, PoolConfig, ZMQSubscriber
from llmd_kv_cache_tpu.events.adapters.sglang import SGLangAdapter
from llmd_kv_cache_tpu.events.adapters.vllm import VLLMAdapter
from llmd_kv_cache_tpu.events.model import (
    AllBlocksClearedEvent,
    BlockRemovedEvent,
    BlockStoredEvent,
    RawMessage,
)
from llmd_kv_cache_tpu.index import InMemoryIndex, InMemoryIndexConfig

WIRE_DIR = pathlib.Path(__file__).parent / "assets" / "wire"


def load(name: str) -> bytes:
    return (WIRE_DIR / name).read_bytes()


def parse(name: str, adapter=None, topic="kv@pod-1@m"):
    adapter = adapter or VLLMAdapter()
    return adapter.parse_message(
        RawMessage(topic=topic, sequence=1, payload=load(name)))


class TestFixtureBytesFrozen:
    def test_committed_bytes_match_spec_assembly(self):
        """The .bin files ARE the golden contract; wire_spec regenerates
        them deterministically. Divergence means someone edited one side."""
        expected = wire_spec.fixtures()
        on_disk = {p.name for p in WIRE_DIR.glob("*.bin")}
        assert on_disk == set(expected)
        for name, payload in expected.items():
            assert load(name) == payload, f"{name} drifted from spec assembly"

    def test_wide_int_fixture_is_not_a_msgpack_python_artifact(self):
        """vllm_wide_ints.bin uses spec-legal fixed-width integer forms
        (0xcd/0xce for small values) that typed foreign encoders emit but
        msgpack-python's packb never does — so re-encoding the decoded
        object provably cannot reproduce the committed bytes, i.e. this
        fixture cannot have been produced by the decode library itself."""
        import msgpack

        raw = load("vllm_wide_ints.bin")
        decoded = msgpack.unpackb(raw, raw=False)
        assert msgpack.packb(decoded, use_bin_type=True) != raw
        # ...and the adapter still decodes the wide forms correctly.
        _, _, batch = parse("vllm_wide_ints.bin")
        (ev,) = batch.events
        assert ev == BlockStoredEvent(
            block_hashes=[77], tokens=[1, 2], parent_hash=0, block_size=16)


class TestVLLMForeignDecode:
    def test_full_block_stored_mirrors_reference_vector(self):
        pod, model, batch = parse("vllm_block_stored_full.bin",
                                  topic="kv@pod-1@llama-2-7b")
        assert (pod, model) == ("pod-1", "llama-2-7b")
        assert batch.timestamp == wire_spec.TS
        assert batch.data_parallel_rank is None
        (ev,) = batch.events
        assert ev == BlockStoredEvent(
            block_hashes=[100, 101], tokens=[1, 2, 3], parent_hash=99,
            block_size=16, device_tier="gpu")

    def test_omit_defaults_short_arrays(self):
        _, _, batch = parse("vllm_omit_defaults.bin")
        (ev,) = batch.events
        assert ev == BlockStoredEvent(
            block_hashes=[7], tokens=[5, 6], parent_hash=0, block_size=4)
        assert batch.data_parallel_rank is None  # 2-element batch tolerated

    def test_integer_encoding_edges(self):
        _, _, batch = parse("vllm_int_edges.bin")
        assert batch.data_parallel_rank == 3
        (ev,) = batch.events
        # uint64 (0xcf), negative fixint, int64 (0xd3) — all → uint64 space.
        assert ev.block_hashes == [
            0xFFFFFFFFFFFFFFFE,
            (-3) & 0xFFFFFFFFFFFFFFFF,
            (-(2**63) + 8) & 0xFFFFFFFFFFFFFFFF,
        ]
        assert ev.parent_hash == 0x8000000000000001
        assert ev.tokens == [255, 65535, 70000]  # uint8/16/32 forms

    def test_bytes_digest_hashes_take_last8_bigendian(self):
        _, _, batch = parse("vllm_bytes_hashes.bin")
        (ev,) = batch.events
        assert ev.block_hashes == [
            int.from_bytes(wire_spec.DIGEST_A[-8:], "big"),
            int.from_bytes(wire_spec.DIGEST_B[-8:], "big"),
        ]

    def test_hma_trailing_fields(self):
        _, _, batch = parse("vllm_hma_fields.bin")
        (ev,) = batch.events
        assert ev.group_idx == 1
        assert ev.kv_cache_spec_kind == "sliding_window"
        assert ev.kv_cache_spec_sliding_window == 1024
        assert ev.extra_keys == [["lora", 4]]

    def test_removed_and_cleared(self):
        _, _, batch = parse("vllm_removed_cleared.bin")
        removed, cleared = batch.events
        assert removed == BlockRemovedEvent(
            block_hashes=[100, 101], device_tier="gpu")
        assert isinstance(cleared, AllBlocksClearedEvent)

    def test_nested_bin_embedded_event(self):
        """Bin-wrapped event blob decodes identically to the flat form."""
        _, _, nested = parse("vllm_nested_bin.bin")
        _, _, flat = parse("vllm_block_stored_full.bin")
        assert nested.events == flat.events


class TestSGLangForeignDecode:
    def test_schema_clamped_at_extra_keys(self):
        _, _, batch = parse("sglang_block_stored.bin", adapter=SGLangAdapter())
        (ev,) = batch.events
        assert ev.block_hashes == [300]
        assert ev.device_tier == "gpu"
        # Positions 9-11 are vLLM HMA extensions; SGLang must not leak them.
        assert ev.group_idx is None
        assert ev.kv_cache_spec_kind == ""
        assert ev.kv_cache_spec_sliding_window is None


class TestScoreWireCompat:
    """ScoreRequest/ScoreResponse shard-metadata tolerance: old peers'
    bytes decode with defaults, new fields round-trip, unknown future
    keys are ignored (the ``degraded``/``traceparent`` arrival pattern)."""

    def test_legacy_request_decodes_with_empty_shard(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_legacy.bin"))
        assert req.tokens == [1, 2, 3]
        assert req.model_name == "llama-2-7b"
        assert req.pod_identifiers == ["pod-1", "pod-2"]
        assert req.shard == ""

    def test_shard_request_decodes_and_ignores_future_keys(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_shard.bin"))
        assert req.tokens == [7, 8]
        assert req.shard == "shard-1"  # future_hint silently ignored

    def test_legacy_response_decodes_with_shard_defaults(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_legacy.bin"))
        assert resp.scores == {"pod-1": 0.5}
        assert resp.error == ""
        assert resp.degraded is False
        assert resp.shard == ""
        assert resp.degraded_shards == []

    def test_shard_response_round_trips(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_shard.bin"))
        assert resp.scores == {"pod-1": 0.75, "pod-2": 0.25}
        assert resp.degraded is True
        assert resp.traceparent == wire_spec.TRACEPARENT
        assert resp.shard == "shard-0"
        assert resp.degraded_shards == ["shard-2"]
        # Re-encode → re-decode keeps the shard metadata intact.
        again = ScoreResponse.from_bytes(resp.to_bytes())
        assert again == resp

    def test_legacy_request_decodes_with_empty_role(self):
        """Role-agnostic peers predate prefill/decode disaggregation —
        their bytes must keep decoding with ``role=\"\"``."""
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_legacy.bin"))
        assert req.role == ""

    def test_role_request_decodes_and_ignores_future_keys(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_role.bin"))
        assert req.tokens == [1, 2, 3, 4]
        assert req.pod_identifiers == ["decode-1", "decode-2"]
        assert req.role == "decode"  # handoff_hint silently ignored
        # Re-encode → re-decode keeps the role.
        assert ScoreRequest.from_bytes(req.to_bytes()).role == "decode"

    def test_legacy_response_decodes_with_empty_residency(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_legacy.bin"))
        assert resp.residency == {}

    def test_residency_response_round_trips(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_residency.bin"))
        assert resp.scores == {"decode-1": 1.5, "decode-2": 0.25}
        assert resp.traceparent == wire_spec.TRACEPARENT
        assert resp.residency == {"decode-1": 1.25}
        again = ScoreResponse.from_bytes(resp.to_bytes())
        assert again == resp

    def test_old_peer_view_of_residency_bytes(self):
        """An old decoder reading residency-bearing bytes simply never
        looks at the new key — the legacy fields stay well-typed."""
        import msgpack

        d = msgpack.unpackb(load("score_response_residency.bin"), raw=False)
        assert d["scores"] == {"decode-1": 1.5, "decode-2": 0.25}
        assert d["error"] == ""

    def test_old_peer_view_of_new_bytes(self):
        """What an old decoder does with new bytes: msgpack map decode via
        ``.get`` means the extra keys are simply never read. Simulate by
        decoding the new-style response and projecting the legacy keys."""
        import msgpack

        d = msgpack.unpackb(load("score_response_shard.bin"), raw=False)
        assert d["scores"] == {"pod-1": 0.75, "pod-2": 0.25}
        assert d["error"] == ""  # legacy fields present and well-typed

    def test_legacy_request_decodes_without_deadline(self):
        """Deadline-unaware peers predate the gray-failure plane — their
        bytes keep decoding with no budget and normal priority."""
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_legacy.bin"))
        assert req.deadline_ms == 0
        assert req.priority == 1

    def test_deadline_request_decodes_and_ignores_future_keys(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_deadline.bin"))
        assert req.tokens == [11, 12, 13]
        assert req.deadline_ms == 250
        assert req.priority == 2  # hedge_hint silently ignored
        again = ScoreRequest.from_bytes(req.to_bytes())
        assert (again.deadline_ms, again.priority) == (250, 2)

    def test_legacy_response_decodes_without_degraded_reason(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_legacy.bin"))
        assert resp.degraded_reason == ""

    def test_brownout_response_round_trips(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_brownout.bin"))
        assert resp.scores == {"pod-1": 0.5}
        assert resp.degraded is True
        assert resp.degraded_reason == "brownout"
        again = ScoreResponse.from_bytes(resp.to_bytes())
        assert again == resp

    def test_old_peer_view_of_deadline_bytes(self):
        """An old decoder reading deadline-bearing bytes never looks at
        the new keys — the legacy fields stay well-typed."""
        import msgpack

        d = msgpack.unpackb(load("score_request_deadline.bin"), raw=False)
        assert d["tokens"] == [11, 12, 13]
        assert d["model_name"] == "llama-2-7b"

    def test_lookup_frame_deadline_and_hedge_markers(self):
        """The shard-RPC lookup frame carries ``deadline_ms``/``hedge``
        the same tolerant way: new servers read them via ``.get``, old
        servers never look."""
        import msgpack

        d = msgpack.unpackb(load("lookup_request_deadline.bin"), raw=False)
        assert d["keys"] == [100, 101]
        assert d["pods"] == ["pod-1"]
        assert d["deadline_ms"] == 40
        assert d["hedge"] is True
        # An old peer's projection: the legacy keys alone are enough.
        assert {k: d[k] for k in ("keys", "pods")} == {
            "keys": [100, 101], "pods": ["pod-1"]}


class TestEpochWireCompat:
    """Epoch-fence wire tolerance (the membership plane's stamp): epoch
    rides every frame the same tolerant way ``deadline_ms`` did. Legacy
    bytes decode to epoch 0 — the "unstamped" value that is never fenced
    — so an un-upgraded peer interoperates by construction; in ``warn``
    mode even genuinely stale stamps pass through (flagged, counted)."""

    def test_legacy_request_decodes_unstamped(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_legacy.bin"))
        assert req.epoch == 0

    def test_epoch_request_decodes_and_ignores_future_keys(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreRequest

        req = ScoreRequest.from_bytes(load("score_request_epoch.bin"))
        assert req.tokens == [1, 2, 3]
        assert req.epoch == 7  # lease_hint silently ignored
        again = ScoreRequest.from_bytes(req.to_bytes())
        assert again.epoch == 7

    def test_legacy_response_decodes_unstamped(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_legacy.bin"))
        assert resp.epoch == 0

    def test_fenced_response_round_trips(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreResponse

        resp = ScoreResponse.from_bytes(load("score_response_fenced.bin"))
        assert resp.scores == {}
        assert resp.degraded is True
        assert resp.degraded_reason == "fenced"
        assert resp.epoch == 7  # the piggyback the stale sender learns
        again = ScoreResponse.from_bytes(resp.to_bytes())
        assert again == resp

    def test_old_peer_view_of_epoch_bytes(self):
        """A pre-epoch decoder reading stamped bytes never looks at the
        new key — the legacy fields stay well-typed."""
        import msgpack

        d = msgpack.unpackb(load("score_request_epoch.bin"), raw=False)
        assert d["tokens"] == [1, 2, 3]
        assert d["model_name"] == "llama-2-7b"
        assert {k: d[k] for k in ("tokens", "pod_identifiers")} == {
            "tokens": [1, 2, 3], "pod_identifiers": ["pod-1"]}

    def test_lookup_frame_epoch_marker(self):
        import msgpack

        d = msgpack.unpackb(load("lookup_request_epoch.bin"), raw=False)
        assert d["keys"] == [100, 101]
        assert d["epoch"] == 7
        # An old shard's projection: the legacy keys alone are enough.
        assert {k: d[k] for k in ("keys", "pods")} == {
            "keys": [100, 101], "pods": ["pod-1"]}

    def test_event_batch_epoch_element(self):
        """KV-event wire element [4] after traceparent carries the
        publisher's epoch; every shorter (pre-epoch) fixture decodes to
        epoch 0."""
        _, _, batch = parse("vllm_epoch_stamped.bin")
        assert batch.epoch == 7
        assert batch.traceparent == wire_spec.TRACEPARENT
        _, _, legacy = parse("vllm_block_stored_full.bin")
        assert legacy.epoch == 0

    def test_warn_mode_interop_with_old_peers(self):
        """The rollout contract: a fleet in ``fenceMode: warn`` accepts
        an old peer's unstamped traffic clean, and even a stale stamp is
        let through flagged — nothing breaks before the knob flips."""
        from llmd_kv_cache_tpu.cluster.membership import MembershipTable

        table = MembershipTable(fence_mode="warn", epoch=7)
        unstamped = table.check_request(0, "score")  # legacy peer
        assert unstamped.allowed and not unstamped.flagged
        stale = table.check_request(6, "score")
        assert stale.allowed and stale.flagged
        assert stale.reason == "stale_epoch"
        # Same stamp under reject mode is refused — the knob is the only
        # difference between rollout and enforcement.
        hard = MembershipTable(fence_mode="reject", epoch=7)
        assert hard.check_request(6, "score").allowed is False


class TestScoreFeedbackWire:
    """ScoreFeedback tolerance (the audit plane's score→engine hop):
    a minimal/older peer's bytes decode with defaults, the full field
    set round-trips, unknown future keys are ignored, and an old peer
    reading new bytes never sees a type change in the keys it knows."""

    def test_full_feedback_decodes_and_round_trips(self):
        from llmd_kv_cache_tpu.services.indexer_service import ScoreFeedback

        fb = ScoreFeedback.from_bytes(load("score_feedback_full.bin"))
        assert fb.traceparent == wire_spec.TRACEPARENT
        assert fb.chosen_pod == "pod-1"
        assert fb.predicted_blocks == 3.5
        assert fb.total_blocks == 8
        assert fb.scores == {"pod-1": 3.5, "pod-2": 1.0}
        assert fb.residency == {"pod-1": 0.5}
        assert fb.staleness_s == 0.25
        assert ScoreFeedback.from_bytes(fb.to_bytes()) == fb

    def test_legacy_feedback_decodes_with_defaults(self):
        """Minimal bytes: absent fields default, an integer-typed
        prediction coerces to float, the unknown ``audit_hint`` key is
        silently ignored."""
        from llmd_kv_cache_tpu.services.indexer_service import ScoreFeedback

        fb = ScoreFeedback.from_bytes(load("score_feedback_legacy.bin"))
        assert fb.traceparent == wire_spec.TRACEPARENT
        assert fb.chosen_pod == "pod-1"
        assert fb.predicted_blocks == 3.0
        assert isinstance(fb.predicted_blocks, float)
        assert fb.total_blocks == 0
        assert fb.scores == {}
        assert fb.residency == {}
        assert fb.staleness_s == 0.0

    def test_old_peer_view_of_feedback_bytes(self):
        """An old decoder reading full feedback bytes via ``.get`` never
        looks at the fields it predates — the keys it knows stay
        well-typed."""
        import msgpack

        d = msgpack.unpackb(load("score_feedback_full.bin"), raw=False)
        assert d["traceparent"] == wire_spec.TRACEPARENT
        assert d["chosen_pod"] == "pod-1"

    def test_from_response_builds_the_routed_prediction(self):
        from llmd_kv_cache_tpu.services.indexer_service import (
            ScoreFeedback,
            ScoreResponse,
        )

        resp = ScoreResponse.from_bytes(load("score_response_residency.bin"))
        fb = ScoreFeedback.from_response(
            resp, "decode-1", total_blocks=4, staleness_s=0.1)
        assert fb.traceparent == resp.traceparent
        assert fb.chosen_pod == "decode-1"
        assert fb.predicted_blocks == 1.5  # the chosen pod's score
        assert fb.scores == resp.scores
        assert fb.residency == resp.residency
        assert (fb.total_blocks, fb.staleness_s) == (4, 0.1)


class TestWireToIndex:
    def test_committed_bytes_through_zmq_pool_index(self):
        """The foreign payload rides a real ZMQ PUB/SUB hop, then
        subscriber → pool → index; scores come from recomputed canonical
        keys, proving the whole ingest stack accepts foreign bytes."""
        processor = ChunkedTokenDatabase(TokenProcessorConfig(block_size_tokens=4))
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        pool = Pool(PoolConfig(concurrency=2), index, processor)
        pool.start()
        ctx = zmq.Context.instance()
        pub = ctx.socket(zmq.PUB)
        # A free port: tests/test_telemetry.py binds 15733 too, and the two
        # files run in different workers at once.
        port = pub.bind_to_random_port("tcp://127.0.0.1")
        endpoint = f"tcp://127.0.0.1:{port}"
        sub = ZMQSubscriber(endpoint, "kv@", pool.add_task, bind=False)
        sub.start()
        time.sleep(0.3)  # PUB/SUB slow-joiner settle
        try:
            keys = processor.tokens_to_kv_block_keys(0, list(range(1, 9)), "m")
            # Republish the idempotent payload until it lands instead of
            # trusting one fixed slow-joiner sleep on a loaded machine
            # (same pattern as test_zmq_integration.py).
            seq = itertools.count(1)

            def publish_and_check():
                pub.send_multipart([
                    b"kv@pod-1@m", struct.pack(">Q", next(seq)),
                    load("vllm_wire_to_index.bin"),
                ])
                return index.lookup(keys) != {}

            assert wait_until(publish_and_check, timeout=10.0, interval=0.1)
            hits = index.lookup(keys)
            assert set(hits) == set(keys)
            assert any(e.pod_identifier == "pod-1"
                       for e in hits[keys[0]])
        finally:
            sub.stop()
            pool.shutdown()
            pub.close(0)


class TestBatchedLookupWire:
    """The framed multi-chunk LookupBlocksBatch wire (the native data
    plane): committed bytes through the real server handler and the
    real client parser, plus old-frame tolerance in both directions."""

    def _service(self):
        from llmd_kv_cache_tpu.core import PodEntry
        from llmd_kv_cache_tpu.services.indexer_service import IndexerService

        svc = IndexerService()
        # Keys 100-102 resident; 103 (chunk 1's second key) missing, so
        # the batch fixture exercises the server-side early exit.
        svc.indexer.kv_block_index.add(
            None, [100, 101, 102], [PodEntry("pod-1", "tpu-hbm")])
        return svc

    def test_batch_request_frame_layout(self):
        import msgpack

        d = msgpack.unpackb(load("lookup_batch_request.bin"), raw=False)
        assert d["chunks"] == [[100, 101], [102, 103]]
        assert d["pods"] == ["pod-1"]
        assert d["deadline_ms"] == 40
        assert d["hedge"] is True

    def test_batch_request_through_service_handler(self):
        """Committed request bytes drive the real handler: chunk 0 is
        complete, chunk 1 misses key 103 → early exit, ``cont=[1,0]``."""
        import msgpack

        svc = self._service()
        resp = svc.lookup_blocks_batch_rpc(
            msgpack.unpackb(load("lookup_batch_request.bin"), raw=False))
        assert resp["cont"] == [1, 0]
        assert len(resp["chunks"]) == 2
        assert sorted(k for k, _ in resp["chunks"][0]) == [100, 101]
        assert [k for k, _ in resp["chunks"][1]] == [102]

    def test_flat_frame_tolerated_as_one_chunk(self):
        """An old peer's flat LookupBlocks frame reaching the batch
        handler decodes as one implicit chunk; the deadline/hedge
        metadata keys ride along untouched."""
        import msgpack

        svc = self._service()
        resp = svc.lookup_blocks_batch_rpc(
            msgpack.unpackb(load("lookup_request_deadline.bin"), raw=False))
        assert resp["cont"] == [1]
        assert len(resp["chunks"]) == 1
        assert sorted(k for k, _ in resp["chunks"][0]) == [100, 101]

    def _stub_client(self, response: dict):
        """A ShardClient whose batch RPC returns the given already-
        unpacked body — the parsing under test is the client's, the
        transport is out of scope here."""
        from llmd_kv_cache_tpu.cluster.remote import ShardClient
        from llmd_kv_cache_tpu.services.indexer_service import (
            DEFAULT_RPC_RETRY_POLICY,
        )

        c = object.__new__(ShardClient)
        c.address = "stub"
        c._timeout = 1.0
        c.retry_policy = DEFAULT_RPC_RETRY_POLICY
        c._lookup_blocks_batch = (
            lambda frame, timeout=None, metadata=None: response)
        return c

    def test_batch_response_client_parsing(self):
        import msgpack

        resp = msgpack.unpackb(load("lookup_batch_response.bin"), raw=False)
        out = self._stub_client(resp).lookup_blocks_batch(
            [[100, 101], [102, 103]])
        assert out["cont"] == [True, False]
        assert sorted(out["hits"]) == [100, 101, 102]
        assert out["hits"][100][0].pod_identifier == "pod-1"
        assert out["hits"][102][0].pod_identifier == "pod-2"
        assert out["shard"] == "shard-0"

    def test_flat_response_tolerated_by_batch_client(self):
        """A flat pre-batch response body parses as one implicit chunk
        with no continuation flags — safe, because the router truncates
        from its own merged map rather than trusting ``cont``."""
        import msgpack

        resp = msgpack.unpackb(
            load("lookup_batch_response_flat.bin"), raw=False)
        out = self._stub_client(resp).lookup_blocks_batch([[100]])
        assert out["cont"] == []
        assert sorted(out["hits"]) == [100]
        assert out["hits"][100][0].device_tier == "tpu-hbm"
