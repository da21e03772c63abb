"""Learned sparse attention with a lightning indexer, latent attention with
q-LoRA and one chip's share of a routed layer, served through the paged
cache, against the configuration's plain reference
(``kvbench/references/deepseek-v3.2-exp-ep16-l5.py``): toy widths, seeded
random weights, ``index_topk`` below the sequences' lengths so that the
selection is live. Logits are compared, not tokens.
"""

import dataclasses
import json
import re
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvbench.harness import names
from llmd_kv_cache_tpu.models import llama
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
from llmd_kv_cache_tpu.ops import sparse_index
from llmd_kv_cache_tpu.telemetry.engine_telemetry import EngineTelemetryConfig

ROOT = Path(__file__).resolve().parents[1]
TOPK = 32


@pytest.fixture(scope="module")
def ref():
    return names.load_module(
        names.KVBENCH / "references" / "deepseek-v3.2-exp-ep16-l5.py",
        "the configuration's reference")


def toy(dtype=jnp.float32, **over) -> LlamaConfig:
    """4 chips share each routed layer: 32 experts, 8 held, 4 a token."""
    return LlamaConfig(**{**dict(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=32, intermediate_size=256, page_size=16,
        dtype=dtype, norm_eps=1e-6, num_experts=32, num_experts_per_token=4,
        moe_dispatch="grouped", moe_layers=(1, 2), n_shared_experts=1,
        moe_intermediate_size=64, moe_router=("deepseek_v3", 4, 2, 1, 2.5),
        experts_held=(8, 8), kv_lora_rank=64, qk_rope_head_dim=32,
        q_lora_rank=48, index_n_heads=16, index_head_dim=64, index_topk=TOPK,
        latent_pad=32, rope_scaling=("yarn", 40.0, 32.0, 1.0, 64.0, 1.0),
        softmax_scale_mult=1.3), **over})


def engine(cfg, params, pallas=False, chunk=32, **kw) -> MiniEngine:
    return MiniEngine(EngineConfig(
        model=cfg, num_pages=64, max_pages_per_seq=16, max_batch=2,
        max_prefill_tokens=chunk, use_pallas_decode=pallas or None,
        use_pallas_prefill=pallas or None, **kw), params=params)


def serve(eng, rid, prompt, max_new):
    """``(request, last-prompt-position logits)``."""
    req = eng.enqueue(rid, prompt, max_new_tokens=max_new)
    logits = None
    while not req.done:
        eng.step()
        if logits is None and req.last_logits is not None:
            logits = np.asarray(req.last_logits, np.float32)
    return req, logits


def rel(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def shortfalls(ref_rows, out) -> list:
    """How far below the reference's best each chosen token lies."""
    return [float((r.max() - r[t]) / np.abs(r).max())
            for r, t in zip(ref_rows, out)]


PROMPT = np.random.default_rng(0).integers(1, 256, 100).tolist()
# float32 against float32 "highest": what is left is the CPU's default
# matmul precision and, rarely, a key that changes sides at the
# selection's edge (one of 32 here, one of 2048 at the published widths).
F32_TOL = 5e-3


@pytest.fixture(scope="module")
def served(ref):
    """One model, its reference logits over prompt + 6 decoded tokens."""
    cfg = toy()
    params = init_params(jax.random.PRNGKey(3), cfg)
    eng = engine(cfg, params)
    req, logits = serve(eng, "cold", PROMPT, 7)
    out = list(req.output)
    positions = [len(PROMPT) - 1 + i for i in range(7)]
    want = ref.logits_at(params, cfg, PROMPT + out[:6], positions)
    return SimpleNamespace(cfg=cfg, params=params, eng=eng, logits=logits,
                           out=out, want=want)


class TestThroughThePagedCache:
    def test_prefill_then_decode_match_the_reference(self, served):
        assert rel(served.logits, served.want[0]) < F32_TOL
        assert max(shortfalls(served.want, served.out)) < F32_TOL

    def test_a_prefix_hit_brings_the_index_keys_back(self, served):
        req, logits = serve(served.eng, "hit", PROMPT, 1)
        assert req.cached_len == len(PROMPT) // 16 * 16
        assert rel(logits, served.want[0]) < F32_TOL

    def test_a_hit_without_its_index_keys_is_wrong(self, served):
        """The comparison sees stream 2: with the index keys of the cached
        pages gone, the same hit selects other keys and misses."""
        eng = served.eng
        keep = eng.v_cache
        eng.v_cache = jnp.zeros_like(keep)
        try:
            req, logits = serve(eng, "hit-blind", PROMPT, 1)
        finally:
            eng.v_cache = keep
        assert req.cached_len > 0
        assert rel(logits, served.want[0]) > 10 * F32_TOL

    @pytest.mark.parametrize("fused", [False, True])
    def test_the_kernels_serve_the_same_function(self, served, fused):
        """The Pallas step programs (interpreted here): a masked latent
        prefill, a decode step that scores, selects, gathers and attends,
        the grouped matmul kernel; on the fused tree too."""
        params = (llama.fuse_params(served.params, served.cfg) if fused
                  else served.params)
        eng = engine(served.cfg, params, pallas=True)
        assert eng.attention_backends["decode"]["backend"] == "pallas"
        assert eng.attention_backends["prefill"]["backend"] == "pallas"
        req, logits = serve(eng, "cold", PROMPT, 7)
        assert rel(logits, served.want[0]) < F32_TOL
        assert list(req.output) == served.out

    def test_a_full_chunk_attends_per_head(self, served):
        """The prompt as one chunk padded to 128 queries: at these widths
        (latent 64 + rope 32 in pages of 128 lanes, heads of 32) the chunk
        program attends per head from 42 queries on, on keys and values
        expanded from the latents inside the kernel, under the same
        selection. Same logits and tokens as the reference and as the
        absorbed program (chunks of 32, above); its dispatch says how many
        keys a head expanded (one superblock: the row's 256), an absorbed
        chunk's says 0."""
        from tests.test_telemetry import _recorded

        assert llama.prefill_per_head(served.cfg, 128)
        assert not llama.prefill_per_head(served.cfg, 32)
        eng = engine(served.cfg, served.params, pallas=True, chunk=128,
                     telemetry=EngineTelemetryConfig())
        seen = _recorded(eng._phases)
        req, logits = serve(eng, "per-head", PROMPT, 7)
        assert rel(logits, served.want[0]) < F32_TOL
        assert list(req.output) == served.out
        assert [a["expanded_keys"] for n, a, _ in seen
                if n == "step.dispatch" and "prefill_pos" in a] == [256]
        short = engine(served.cfg, served.params, pallas=True,
                       telemetry=EngineTelemetryConfig())
        seen = _recorded(short._phases)
        _, absorbed = serve(short, "absorbed", PROMPT, 1)
        assert rel(logits, absorbed) < F32_TOL
        assert [a["expanded_keys"] for n, a, _ in seen
                if n == "step.dispatch" and "prefill_pos" in a] == [0] * 4
        # The XLA prefill has neither kernel.
        xla = engine(served.cfg, served.params, chunk=128,
                     telemetry=EngineTelemetryConfig())
        seen = _recorded(xla._phases)
        serve(xla, "xla", PROMPT, 1)
        assert [a["expanded_keys"] for n, a, _ in seen
                if n == "step.dispatch" and "prefill_pos" in a] == [0]

    def test_chunked_prefill_is_one_chunk(self, served):
        whole = engine(served.cfg, served.params, chunk=128)
        _, logits = serve(whole, "one-chunk", PROMPT, 1)
        assert rel(logits, served.logits) < F32_TOL

    @pytest.mark.parametrize("pallas", [False, True])
    def test_a_row_crosses_topk_while_it_decodes(self, ref, served, pallas):
        """29 keys after prefill, 37 after the last step: the first steps
        attend every key, the later ones select."""
        prompt = PROMPT[:TOPK - 3]
        eng = engine(served.cfg, served.params, pallas=pallas)
        req, logits = serve(eng, "crossing", prompt, 9)
        out = list(req.output)
        positions = [len(prompt) - 1 + i for i in range(9)]
        want = ref.logits_at(served.params, served.cfg, prompt + out[:8],
                             positions)
        assert rel(logits, want[0]) < F32_TOL
        assert max(shortfalls(want, out)) < F32_TOL

    def test_a_wrong_selection_fails(self, ref, served, monkeypatch):
        """The first ``topk`` keys instead of the best: the comparison that
        passes the program must not pass this."""
        def first_keys(scores, q_positions, total_lens, topk):
            pos = jnp.arange(scores.shape[-1])[None, None, :]
            return (pos <= q_positions[:, :, None]) & (pos < topk)

        monkeypatch.setattr(sparse_index, "keep_mask", first_keys)
        jax.clear_caches()
        try:
            _, logits = serve(engine(served.cfg, served.params), "wrong",
                              PROMPT, 1)
        finally:
            monkeypatch.undo()
            jax.clear_caches()
        assert rel(logits, served.want[0]) > 10 * F32_TOL


class TestAnswers:
    """The reference admits more than one answer where a router's deciding
    scores lie within ``MARGIN``: the nearest first, ``LIMIT`` at most."""

    ROUTER = ("deepseek_v3", 4, 2, 1, 2.5)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_choices_come_nearest_first(self, ref, seed):
        rng = np.random.default_rng(seed)
        scores = 1 / (1 + np.exp(-rng.standard_normal(32) * 1.5))
        bias = rng.standard_normal(32) * 0.02
        got = ref.choices(scores, bias, self.ROUTER, 4, 0.05, (8, 8))
        needs = [need for need, _ in got]
        assert needs[0] == -np.inf and needs == sorted(needs)
        assert all(0 <= need < 0.05 for need in needs[1:])
        held = [tuple(e for e in experts if 8 <= e < 16)
                for _, experts in got]
        assert len(set(held)) == len(held)  # one answer a set held here
        assert ref.choices(scores, bias, self.ROUTER, 4, 0.0, (8, 8)) == [
            got[0]]

    def test_a_position_gets_the_nearest_and_no_more(self, ref, served,
                                                     monkeypatch):
        """With a margin that admits far more than ``LIMIT`` answers: row
        0 is ``logits_at``'s, the rows stop at ``LIMIT``, and the second is
        the forward under the nearest other choice of the first routed
        layer that has one (the other positions' choices, run in the same
        forwards, reach it as one key among the attended)."""
        monkeypatch.setattr(ref, "MARGIN", 0.2)
        tokens = PROMPT + served.out[:6]
        positions = [len(PROMPT) - 1, len(PROMPT) + 2]
        rows = ref.alternatives_at(served.params, served.cfg, tokens,
                                   positions)
        _, ties, _ = ref._forward(served.params, served.cfg, tokens,
                                  positions)
        for i, p in enumerate(positions):
            assert 1 < len(rows[i]) <= ref.LIMIT
            np.testing.assert_array_equal(rows[i][0],
                                          served.want[p - positions[0]])
            others = sorted((ties[li][p][1][0], li) for li in ties
                            if len(ties[li][p]) > 1)
            _, li = others[0]
            alone = ref._forward(served.params, served.cfg, tokens, [p],
                                 {li: {p: ties[li][p][1][1]}})[0][0]
            assert rel(rows[i][1], alone) < 2e-3
            assert rel(rows[i][1], rows[i][0]) > 10 * rel(rows[i][1], alone)


class TestSelection:
    def test_the_programs_set_is_the_references(self, ref):
        """bfloat16 program against float32 reference, every query of a
        prompt, every layer: the sets overlap by at least 0.98. What is
        left are keys whose index scores lie within bfloat16's rounding of
        the query's ``topk``-th: program and reference rank them
        differently, both by right."""
        cfg = toy(jnp.bfloat16, index_topk=64)
        params = init_params(jax.random.PRNGKey(11), cfg)
        tokens = np.random.default_rng(5).integers(1, 256, 256).tolist()
        masks = []
        real = sparse_index.keep_mask

        def recording(scores, q_positions, total_lens, topk):
            keep = real(scores, q_positions, total_lens, topk)
            masks.append(np.asarray(keep[0]))
            return keep

        k, v = llama.init_kv_cache(cfg, 20)
        table = jnp.arange(1, 17, dtype=jnp.int32)[None, :]
        sparse_index.keep_mask = recording
        try:
            with jax.disable_jit():
                llama.forward.__wrapped__(
                    params, cfg, jnp.asarray([tokens], jnp.int32), k, v,
                    table, jnp.zeros((1,), jnp.int32),
                    jnp.asarray([256], jnp.int32), last_only=True)
        finally:
            sparse_index.keep_mask = real
        want = ref.kept_keys(params, cfg, tokens)
        assert len(masks) == cfg.num_layers
        # Layer 0 sees the same input in both; deeper layers' inputs
        # already differ by the rounding of what came before.
        shared = [(masks[li] & want[li]).sum(1) / want[li].sum(1)
                  for li in range(cfg.num_layers)]
        for li, share in enumerate(shared):
            assert want[li][100].sum() == 64 and masks[li][100].sum() == 64
            assert share[64:].mean() >= 0.98, (li, share[64:].mean())

    def test_kth_largest_is_exact(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((5, 300)).astype(np.float32)
        x[0, :50] = -np.inf
        x[1] = np.abs(x[1])
        x[2, ::3] = 0.0
        for k in (1, 7, 64, 300):
            thr = sparse_index.kth_largest(jnp.asarray(x), k)
            keep = np.asarray(sparse_index._ordered_bits(jnp.asarray(x))
                              >= thr)
            want = np.sort(x, axis=1)[:, -k][:, None]
            np.testing.assert_array_equal(keep, x >= want)

    def test_the_kernels_names_are_their_wrappers(self):
        """A device trace names a kernel's ops after its jitted wrapper."""
        assert (sparse_index.dsa_index_scores.__name__
                == sparse_index.KERNEL_INDEX == "dsa_index_scores")
        # The decode step's form of it, which reads the pool: the same
        # kernel to the benchmark's reader of the scoring's roofline.
        from kvbench.metrics import dsa_index_roofline
        for kernel in (sparse_index.dsa_index_scores,
                       sparse_index.dsa_index_scores_paged):
            assert re.match(dsa_index_roofline.KERNEL, kernel.__name__)
        assert (sparse_index.dsa_keep_bias.__name__
                == sparse_index.KERNEL_KEEP == "dsa_keep_bias")
        assert (sparse_index.topk_by_count.__name__
                == sparse_index.KERNEL_SELECT == "topk_by_count")
        assert (sparse_index.gather_by_product.__name__
                == sparse_index.KERNEL_GATHER == "gather_by_product")
        # The benchmark's reader of the selection's share tells ops by
        # name, and has to keep finding what replaced the sort, and the
        # gather of the chosen latents now that it is a kernel.
        from kvbench.metrics import dsa_select_share
        assert re.match(dsa_select_share.OPS, sparse_index.KERNEL_SELECT)
        assert re.match(dsa_select_share.OPS, sparse_index.KERNEL_GATHER)

    # (scores' shape, ctx_lens, new_lens, topk, what the scores hold). 32
    # queries a chunk in tiles of 16; keys in blocks of 256, 128 or 64.
    KEEP_CASES = {
        "rows-shorter-than-topk": ((1, 32, 256), [10], [32], 64, "normal"),
        "a-row-of-topk-keys": ((1, 32, 256), [32], [32], 64, "normal"),
        "rows-longer-than-topk": ((1, 32, 256), [150], [32], 64, "normal"),
        "a-chunk-crosses-topk": ((1, 32, 256), [50], [32], 64, "normal"),
        "a-tile-crosses-topk": ((1, 32, 256), [56], [32], 64, "normal"),
        "two-unlike-rows": ((2, 32, 256), [200, 8], [32, 20], 64, "normal"),
        "ties-at-the-topk-th": ((2, 32, 256), [200, 70], [32, 32], 64,
                                "ties"),
        "ties-everywhere": ((1, 32, 256), [120], [32], 7, "zeros"),
        "minus-infinity": ((2, 32, 256), [200, 90], [32, 32], 64, "-inf"),
        "all-negative": ((2, 32, 256), [200, 60], [32, 17], 64, "negative"),
        "live-keys-end-mid-block": ((1, 32, 384), [140], [27], 64, "normal"),
        "a-padded-chunk": ((1, 32, 256), [100], [3], 64, "normal"),
        "an-empty-row": ((2, 32, 256), [0, 100], [0, 32], 64, "normal"),
        "more-keys-than-a-block": ((1, 32, 2048 + 1024), [2500], [32], 64,
                                   "normal"),
        "topk-is-one": ((1, 32, 192), [100], [32], 1, "ties"),
    }

    @pytest.mark.parametrize("case", KEEP_CASES)
    def test_the_threshold_kernel_keeps_what_keep_mask_keeps(self, case):
        """``dsa_keep_bias`` (interpreted) against ``keep_mask`` +
        ``jnp.where``: the bias equal element for element."""
        shape, ctx, new, topk, holds = self.KEEP_CASES[case]
        rng = np.random.default_rng(len(case))
        x = rng.standard_normal(shape).astype(np.float32)
        if holds == "ties":
            x[:, :, ::3] = 0.0
            x[:, 5] = np.round(x[:, 5])
        elif holds == "zeros":
            x[:] = 0.0
        elif holds == "-inf":
            x[:, :, :150:2] = -np.inf
            x[0, 3, :] = -np.inf
            x[0, 4, 7:] = -np.inf
        elif holds == "negative":
            x = -np.abs(x) - 1e-3
            x[:, ::2] *= 1e-30
        ctx, new = np.asarray(ctx), np.asarray(new)
        q_positions = jnp.asarray(ctx[:, None] + np.arange(shape[1])[None])
        total_lens = jnp.asarray(ctx + new)
        want = jnp.where(
            sparse_index.keep_mask(jnp.asarray(x), q_positions, total_lens,
                                   topk), 0.0, sparse_index.DROPPED)
        got = sparse_index.dsa_keep_bias(
            jnp.asarray(x), q_positions, total_lens, topk=topk,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # The case is what its name says: a candidate is dropped where a
        # row is longer than topk (and not all ties), none where none is.
        candidates = np.asarray(sparse_index.keep_mask(
            jnp.asarray(x), q_positions, total_lens, shape[2]))
        assert (candidates & (np.asarray(want) != 0.0)).any() == (
            int((ctx + new).max()) > topk and holds != "zeros")

    # (rows x keys, total_lens, topk, what the scores hold). The kernel
    # counts blocks of 1024 keys in chunks of 128 and fills slots in tiles
    # of 128.
    SELECT_CASES = {
        "random-rows": ((3, 96), [96, 20, 33], 32, "normal"),
        "one-key-more-than-topk": ((2, 256), [65, 201], 64, "normal"),
        "lens-off-the-block": ((3, 2048 + 1024), [2500, 1025, 3071], 64,
                               "normal"),
        "rows-of-0-1-and-topk-keys": ((4, 384), [0, 1, 64, 300], 64,
                                      "normal"),
        "ties-at-the-threshold": ((3, 640), [601, 333, 640], 200, "ties"),
        "an-all-equal-row": ((2, 512), [512, 300], 70, "equal"),
        "zeros-of-both-signs": ((2, 1280), [1280, 700], 130, "zeros"),
        "minus-infinity-past-the-length": ((2, 512), [200, 129], 128,
                                           "-inf"),
        "slots-in-several-tiles": ((2, 4096), [4000, 2100], 300, "ties"),
        "the-cells-shape-one-live-row": (
            (8, 33792), [0, 0, 0, 29000, 0, 0, 0, 0], 2048, "ties"),
        "the-cells-shape-two-live-rows": (
            (8, 33792), [33792, 0, 1500, 0, 0, 8200, 0, 2048], 2048,
            "normal"),
    }

    @pytest.mark.parametrize("case", SELECT_CASES)
    def test_select_topk_picks_what_top_k_picks(self, case):
        """``select_topk`` (the counting kernel, interpreted) against
        ``jax.lax.top_k`` on the same scores masked past each row's length:
        ``count`` positions a row, the same set, ascending; a row of at
        most ``topk`` keys whole and in order."""
        shape, lens, topk, holds = self.SELECT_CASES[case]
        rng = np.random.default_rng(len(case))
        x = rng.standard_normal(shape).astype(np.float32)
        if holds == "ties":
            x = np.round(x * 4) / 4  # some twenty values in all
        elif holds == "equal":
            x[:] = 0.25
        elif holds == "zeros":
            # A weighted sum of ReLUs: few above zero, most zero, of
            # either sign.
            x = np.where(x > 1.5, x, np.where(x > 0, 0.0, -0.0)
                         ).astype(np.float32)
        elif holds == "-inf":
            x[:, 100:] = -np.inf  # finite scores fall short of topk too
        lens = np.asarray(lens)
        pos = np.arange(shape[1])[None, :]
        masked = jnp.where(pos < lens[:, None], jnp.asarray(x), -jnp.inf)
        want = np.asarray(jax.lax.top_k(masked, topk)[1])
        picked, count = sparse_index.select_topk(
            jnp.asarray(x), jnp.asarray(lens, jnp.int32), topk)
        picked = np.asarray(picked)
        assert picked.shape == (shape[0], topk) and picked.dtype == np.int32
        assert count.tolist() == np.minimum(lens, topk).tolist()
        for row, n in enumerate(lens):
            if n <= topk:
                assert picked[row].tolist() == list(range(topk))
            else:
                assert picked[row].tolist() == sorted(want[row].tolist())
                assert picked[row].max() < n
        # The case is what its name says: a key tied with the threshold is
        # left out where ties are planted, and -0.0 loses to +0.0.
        if holds in ("ties", "equal", "zeros"):
            row = int(np.argmax(lens))
            live = x[row, :lens[row]]
            thr = np.sort(live)[-topk]
            assert (live == thr).sum() > (live[picked[row]] == thr).sum() > 0
        if holds == "zeros":
            chosen = x[0, picked[0]]
            assert (chosen == 0).any() and not np.signbit(
                chosen[chosen == 0]).any()

    # (page size, width, layers, layer_idx, pages a row, topk, each row's
    # keys, the order of a row's pages in the pool). The kernel streams a
    # row's pages 1024 keys a round and fills slots in tiles of 128.
    GATHER_CASES = {
        "a-padded-row-beside-a-live-one": (16, 128, 1, 0, 24, 64, [0, 300],
                                           "shuffled"),
        "a-row-under-topk": (16, 128, 1, 0, 8, 64, [40], "shuffled"),
        "a-row-of-exactly-topk": (16, 128, 1, 0, 8, 64, [64], "shuffled"),
        "a-row-over-topk-ending-mid-page": (16, 128, 1, 0, 24, 64, [301],
                                            "shuffled"),
        "two-unlike-rows-and-two-padded": (16, 128, 2, 1, 40, 64,
                                           [0, 70, 0, 600], "shuffled"),
        "another-layer": (16, 128, 3, 2, 24, 64, [333, 64, 65], "shuffled"),
        "pages-out-of-order": (16, 128, 2, 0, 24, 64, [380, 50],
                               "descending"),
        "pages-in-order": (16, 128, 2, 1, 24, 64, [380, 50], "ascending"),
        "topk-off-the-page-and-the-tile": (16, 128, 1, 0, 24, 40,
                                           [39, 40, 41, 380], "shuffled"),
        "several-rounds-and-tiles": (64, 256, 2, 1, 50, 300,
                                     [3200, 0, 301, 1100], "shuffled"),
        "every-row-padded": (16, 128, 1, 0, 8, 64, [0, 0], "shuffled"),
    }

    @staticmethod
    def gather_inputs(case, page_size, width, layers, row_pages, topk, lens,
                      order):
        """A pool of pages that no two rows share, a page table in
        ``order`` and ascending positions as ``select_topk`` leaves them
        (a row of at most ``topk`` keys its first); page 0 is nobody's."""
        rng = np.random.default_rng(len(case))
        used = sum(-(-n // page_size) for n in lens)
        pool = rng.standard_normal(
            (layers, used + 5, 1, page_size, width)).astype(np.float32)
        ids = np.arange(1, used + 5)
        ids = {"shuffled": rng.permutation(ids), "ascending": ids,
               "descending": ids[::-1]}[order]
        table = np.zeros((len(lens), row_pages), np.int32)
        positions = np.tile(np.arange(topk, dtype=np.int32), (len(lens), 1))
        at = 0
        for row, n in enumerate(lens):
            need = -(-n // page_size)
            table[row, :need] = ids[at:at + need]
            at += need
            if n > topk:
                positions[row] = np.sort(rng.choice(n, topk, replace=False))
        return pool, table, positions, np.minimum(lens, topk).astype(np.int32)

    @staticmethod
    def gather_reference(pool, layer_idx, table, positions):
        """The ``jax.numpy`` gather that ``gather_selected`` was until
        PR 55: every slot of every row, a page looked up for each."""
        page_size = pool.shape[-2]
        page = jnp.take_along_axis(
            jnp.asarray(table), jnp.asarray(positions) // page_size, axis=1)
        return np.asarray(jnp.asarray(pool)[
            layer_idx, page, 0, jnp.asarray(positions) % page_size])

    @pytest.mark.parametrize("case", GATHER_CASES)
    def test_the_gather_kernel_fetches_what_the_gather_fetched(self, case):
        """``gather_by_product`` (interpreted) against the ``jax.numpy``
        gather: the first ``count`` slots of every live row bit for bit,
        zeros behind them in a row that was compacted, and nothing written
        for a padded row (the interpreter hands a kernel NaNs to write
        into)."""
        (page_size, width, layers, layer_idx, row_pages, topk, lens,
         order) = self.GATHER_CASES[case]
        pool, table, positions, count = self.gather_inputs(
            case, page_size, width, layers, row_pages, topk, lens, order)
        want = self.gather_reference(pool, layer_idx, table, positions)
        pages = -(-topk // page_size)
        args = (jnp.asarray(pool, jnp.bfloat16), layer_idx,
                jnp.asarray(table), jnp.asarray(positions),
                jnp.asarray(count))
        got = sparse_index.gather_by_product(*args, interpret=True)
        assert got.shape == (len(lens) * pages, 1, page_size, width)
        assert got.dtype == jnp.bfloat16
        got = np.asarray(got).reshape(len(lens), pages * page_size, width)
        served = np.asarray(sparse_index.gather_selected(*args)).reshape(
            got.shape)  # off the chip: the gather itself
        want = np.asarray(jnp.asarray(want, jnp.bfloat16))
        for row, n in enumerate(count):
            assert got[row, :n].tobytes() == want[row, :n].tobytes()
            assert served[row, :n].tobytes() == want[row, :n].tobytes()
            tail = got[row, n:].astype(np.float32)
            if n == 0:
                assert np.isnan(tail).all()
            elif lens[row] > topk:  # compacted: zeros to the pages' end
                assert (tail == 0).all()
            else:  # whole pages: the last one's own tail, and no further
                end = -(-n // page_size) * page_size
                assert np.isfinite(tail[:end - n]).all()
                assert np.isnan(tail[end - n:]).all()

    def test_attention_over_the_gathered_pool_meets_nothing_unwritten(self):
        """The decode kernel over ``gather_by_product``'s pool, with the
        pool's unused pages and everything the kernel did not write NaN:
        every live row finite and what it is over the gather's pool. (A
        masked key still enters ``p @ v`` as ``0 x`` what its slot holds.)
        A padded row attends nothing and comes out 0."""
        from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
            pallas_paged_decode_attention)
        page_size, width, topk, lens = 16, 128, 72, [0, 500, 30, 72, 0, 75]
        pool, table, positions, count = self.gather_inputs(
            "poisoned", page_size, width, 2, 40, topk, lens, "shuffled")
        unused = np.setdiff1d(np.arange(pool.shape[1]), table[table > 0])
        pool[:, unused] = np.nan
        pages = -(-topk // page_size)
        got = sparse_index.gather_by_product(
            jnp.asarray(pool, jnp.bfloat16), 1, jnp.asarray(table),
            jnp.asarray(positions), jnp.asarray(count), interpret=True)
        assert np.isnan(np.asarray(got, np.float32)).any()  # the padded rows
        want = self.gather_reference(np.nan_to_num(pool), 1, table, positions)
        want = jnp.asarray(np.pad(want, [
            (0, 0), (0, pages * page_size - topk), (0, 0)]),
            jnp.bfloat16).reshape(got.shape)
        q = jnp.asarray(np.random.default_rng(7).standard_normal(
            (len(lens), 4, width)), jnp.bfloat16)
        own = jnp.arange(len(lens) * pages, dtype=jnp.int32).reshape(
            len(lens), pages)

        def attend(chosen):
            return np.asarray(pallas_paged_decode_attention(
                q, chosen, chosen, own, jnp.asarray(count), shared_kv=True,
                interpret=True), np.float32)

        out, ref = attend(got), attend(want)
        assert np.isfinite(out).all()
        for row, n in enumerate(count):
            if n:
                np.testing.assert_array_equal(out[row], ref[row])
                assert np.abs(out[row]).max() > 0
            else:
                assert (out[row] == 0).all()

    @pytest.mark.parametrize("q_seq", [1, 32])
    def test_the_scoring_kernel_is_the_function(self, q_seq):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((2, q_seq, 4, 64)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((2, q_seq, 4)), jnp.float32)
        keys = jnp.asarray(rng.standard_normal((2, 256, 64)), jnp.bfloat16)
        lens = jnp.asarray([256, 70], jnp.int32)
        got = np.asarray(sparse_index.dsa_index_scores(
            q, w, keys, lens, interpret=True))
        want = np.asarray(sparse_index.index_scores_xla(q, w, keys))
        np.testing.assert_allclose(got[0], want[0], rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(got[1, :, :70], want[1, :, :70],
                                   rtol=2e-2, atol=2e-2)

    # (page size, heads, queries a row, layers, layer_idx, pages a row, each
    # row's ``lens``, the order of a row's pages in the pool). A round is
    # 1024 keys, or all of a row's pages where they hold fewer (a power of
    # two of them): 128 keys at 24 pages of 16.
    PAGED_CASES = {
        "rows-of-0-keys": (16, 4, 1, 1, 0, 24, [0, 0], "shuffled"),
        "a-row-ends-inside-a-page": (16, 4, 1, 1, 0, 24, [301], "shuffled"),
        "a-row-ends-on-a-rounds-edge": (16, 4, 1, 2, 1, 24, [256, 128],
                                        "shuffled"),
        "one-key": (16, 4, 1, 1, 0, 24, [1, 0], "shuffled"),
        "every-page-of-a-row": (16, 4, 1, 1, 0, 24, [384, 383], "shuffled"),
        "live-and-dead-rows-in-any-order": (
            16, 4, 1, 2, 0, 24, [0, 300, 0, 0, 77, 384, 0, 129], "shuffled"),
        "pages-out-of-order": (16, 4, 1, 2, 0, 24, [380, 50], "descending"),
        "pages-in-order": (16, 4, 1, 2, 1, 24, [380, 50], "ascending"),
        "another-layer": (16, 8, 1, 3, 2, 40, [333, 0, 65], "shuffled"),
        "rounds-of-1024-keys": (64, 4, 1, 1, 0, 48, [3000, 0, 2048, 1025],
                                "shuffled"),
        "the-cells-528-pages": (64, 4, 1, 1, 0, 528, [0, 33792, 0, 25000],
                                "shuffled"),
        "a-chunks-queries": (16, 4, 32, 1, 0, 24, [0, 300, 384],
                             "shuffled"),
    }

    @pytest.mark.parametrize("case", PAGED_CASES)
    def test_the_paged_scoring_kernel_is_the_function(self, case):
        """``dsa_index_scores_paged`` (interpreted), which reads the pool
        through the page table, against ``dsa_index_scores`` and
        ``index_scores_xla`` over ``gather_index_keys``: below a row's
        ``lens`` the kernel's scores bit for bit, zeros from there on. And
        it reads what a row holds and no more: with NaN in every page that
        no row names below its ``lens`` (the garbage page 0 among them: all
        a dead row's line names) the scores are the same, so finite."""
        (page_size, heads, q_seq, layers, layer_idx, row_pages, lens,
         order) = self.PAGED_CASES[case]
        width = 128
        pool, table, _, _ = self.gather_inputs(
            case, page_size, width, layers, row_pages, 1, lens, order)
        rng = np.random.default_rng(len(case))
        q = jnp.asarray(rng.standard_normal(
            (len(lens), q_seq, heads, width)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((len(lens), q_seq, heads)),
                        jnp.float32)
        n = jnp.asarray(lens, jnp.int32)
        sound = jnp.asarray(pool, jnp.bfloat16)
        keys = sparse_index.gather_index_keys(sound, layer_idx,
                                              jnp.asarray(table))
        kernel = np.asarray(sparse_index.dsa_index_scores(
            q, w, keys, n, interpret=True))
        xla = np.asarray(sparse_index.index_scores_xla(q, w, keys))
        got = np.asarray(sparse_index.dsa_index_scores_paged(
            q, w, sound, layer_idx, jnp.asarray(table), n, interpret=True))
        assert got.shape == (len(lens), q_seq, row_pages * page_size)
        assert got.dtype == np.float32
        for row, k in enumerate(lens):
            assert got[row, :, :k].tobytes() == kernel[row, :, :k].tobytes()
            np.testing.assert_allclose(got[row, :, :k], xla[row, :, :k],
                                       rtol=1e-5, atol=1e-5)
            assert (got[row, :, k:] == 0).all()
            assert k == 0 or np.abs(got[row, :, :k]).max() > 0
        pool[:, np.setdiff1d(np.arange(pool.shape[1]), table[table > 0])
             ] = np.nan
        pool[np.arange(layers) != layer_idx] = np.nan
        assert np.isnan(pool[:, 0]).all()
        poisoned = np.asarray(sparse_index.dsa_index_scores_paged(
            q, w, jnp.asarray(pool, jnp.bfloat16), layer_idx,
            jnp.asarray(table), n, interpret=True))
        assert np.isfinite(poisoned).all()
        assert poisoned.tobytes() == got.tobytes()


class TestExpertLayer:
    @pytest.fixture(scope="class")
    def layer(self):
        cfg = toy(experts_held=())
        params = init_params(jax.random.PRNGKey(7), cfg)
        x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 128),
                              jnp.float32)
        # A whole layer is drawn with a zero bias; the shares' is not.
        bias = 0.02 * jax.random.normal(jax.random.PRNGKey(9), (32,))
        return cfg, {**params["layers"][1], "router_bias": bias}, x

    @pytest.mark.parametrize("kernel", [None, {"interpret": True}])
    def test_the_grouped_dispatch_is_the_dense_form(self, layer, kernel):
        cfg, lyr, x = layer
        dense = llama._moe_deepseek(
            x, lyr, dataclasses.replace(cfg, moe_dispatch="dense"))
        counters = {}
        grouped = llama._moe_deepseek(x, lyr, cfg, kernel=kernel,
                                      counters=counters)
        np.testing.assert_allclose(grouped, dense, rtol=2e-5, atol=2e-5)
        assert int(counters["assignments_held"]) == 2 * 24 * 4

    def test_padded_tokens_are_not_dispatched(self, layer):
        cfg, lyr, x = layer
        valid = jnp.arange(24)[None, :] < jnp.asarray([[24], [5]])
        counters = {}
        llama._moe_deepseek(x, lyr, cfg, valid=valid, counters=counters)
        assert int(counters["assignments_held"]) == (24 + 5) * 4

    def test_the_shares_add_up_to_the_uncut_layer(self, layer, ref):
        """The guide's share test: the routed parts that the 4 shares
        give, with the shared expert counted once, are the uncut layer, in
        the program and against the reference's uncut layer."""
        cfg, lyr, x = layer
        whole = llama._moe_deepseek(x, lyr, cfg)
        flat = x.reshape(-1, 128)
        shared = (jax.nn.silu(flat @ lyr["w_gate_sh"])
                  * (flat @ lyr["w_up_sh"])) @ lyr["w_down_sh"]
        total = shared.reshape(x.shape)
        held_sum = 0
        for rank in range(4):
            part_cfg = dataclasses.replace(cfg, experts_held=(8 * rank, 8))
            part = {**lyr, **{k: lyr[k][8 * rank:8 * rank + 8]
                              for k in ("w_gate", "w_up", "w_down")}}
            counters = {}
            total = total + (llama._moe_deepseek(
                x, part, part_cfg, counters=counters)
                - shared.reshape(x.shape))
            held_sum += int(counters["assignments_held"])
        assert held_sum == 2 * 24 * 4  # every assignment fell to one share
        np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
        with jax.default_matmul_precision("highest"):
            want = ref._routed(jnp.asarray(flat), lyr, cfg, 1, [], {}, {},
                               {}, 0.0)
        np.testing.assert_allclose(total.reshape(-1, 128), want,
                                   rtol=2e-3, atol=2e-4)

    def test_a_share_routes_over_the_whole_width(self):
        cfg = toy()
        lyr = init_params(jax.random.PRNGKey(7), cfg)["layers"][1]
        assert lyr["router"].shape == (128, 32)
        assert lyr["w_gate"].shape == (8, 128, 64)
        assert float(jnp.abs(lyr["router_bias"]).max()) > 0


class TestConfiguration:
    def published(self, **over):
        conf = json.loads((ROOT / "kvbench" / "configs"
                           / "deepseek-v3.2-exp-ep16-l5.json").read_text())
        conf.pop("kvbench")
        return SimpleNamespace(**{**conf, **over})

    def test_the_loader_takes_the_published_keys(self):
        cfg = config_from_hf(self.published(), page_size=64)
        assert cfg.is_dsa and cfg.is_mla
        assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
            64, 128, 2048)
        assert cfg.q_lora_rank == 1536 and cfg.latent_pad == 64
        assert cfg.kv_cache_head_dim == 640
        assert cfg.num_experts == 256 and cfg.experts_held == (0, 16)
        assert cfg.moe_dispatch == "grouped" and cfg.moe_layers == (1, 2, 3, 4)
        assert cfg.rope_scaling[0] == "yarn"
        assert cfg.softmax_scale_mult == pytest.approx(
            (0.1 * np.log(40.0) + 1.0) ** 2)

    def test_a_share_that_does_not_add_up_is_refused(self):
        share = {"chips": 8, "rank": 0, "n_routed_experts": 256}
        with pytest.raises(ValueError, match="layer_share"):
            config_from_hf(self.published(layer_share=share), page_size=64)

    def test_init_params_makes_what_the_model_has(self):
        cfg = toy()
        layer = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
        assert layer["w_dq"].shape == (128, 48)
        assert layer["wq"].shape == (48, 4 * 64)
        assert layer["w_iq"].shape == (48, 16 * 64)
        assert layer["w_ik"].shape == (128, 64)
        assert layer["w_iw"].shape == (128, 16)
        assert {"q_latent_norm", "latent_norm", "index_norm",
                "index_norm_bias"} <= set(layer)

    def test_the_pool_holds_two_streams_under_one_page_id(self):
        k, v = llama.init_kv_cache(toy(), 10)
        assert k.shape == (3, 10, 1, 16, 128) and v.shape == (3, 10, 1, 16, 64)
        k, v = llama.init_kv_cache(toy(index_topk=0), 10)
        assert v.shape[-1] == 0

    def test_an_indexer_needs_its_q_latent(self):
        with pytest.raises(ValueError, match="q latent"):
            toy(q_lora_rank=0)

    def test_a_storage_tier_is_refused_at_construction(self):
        from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec

        cfg = toy()
        spec = SharedStorageOffloadSpec(
            root="/tmp/never-made", model_name="m", page_size=16,
            num_layers=3, kv_heads=1, head_dim=128, kv_streams=1)
        with pytest.raises(ValueError, match="two streams a page"):
            MiniEngine(EngineConfig(model=cfg, num_pages=16,
                                    max_pages_per_seq=4, max_batch=2),
                       offload_spec=spec)


class TestCounters:
    def test_the_phases_carry_the_counts(self, served):
        from tests.test_telemetry import _recorded

        eng = engine(served.cfg, served.params,
                     telemetry=EngineTelemetryConfig())
        seen = _recorded(eng._phases)
        serve(eng, "counted", PROMPT[:40], 4)
        dispatch = [a for n, a, _ in seen if n == "step.dispatch"
                    and "selected_keys" in a]
        # Prefill emits token 1; three decode steps at 41, 42, 43 keys.
        assert [a["selected_keys"] for a in dispatch] == [TOPK] * 3
        assert [a["index_keys"] for a in dispatch] == [41, 42, 43]
        fetch = [a for n, a, _ in seen if n == "step.fetch"
                 and "assignments_held" in a]
        assert [a["counted_program"] for a in fetch] == (
            ["prefill"] + ["decode"] * 3)
        assert fetch[0]["counted_tokens"] == 8  # the last chunk's tokens
        for a in fetch:
            made = a["counted_tokens"] * 4 * 2
            assert 0 <= a["assignments_held"] <= made
            assert a["experts_touched"] <= min(16, a["assignments_held"])
        finish = [a for n, a, _ in seen if n == "step.finish"]
        assert all(a["programs"] == a["transfers"] for a in finish)
        # The XLA prefill finds its thresholds with ``kth_largest``.
        assert not any("threshold_keys" in a for _, a, _ in seen)


class TestCounts:
    @pytest.fixture(scope="class")
    def real(self):
        conf = json.loads((ROOT / "kvbench" / "configs"
                           / "deepseek-v3.2-exp-ep16-l5.json").read_text())
        counts = names.counts(conf)
        conf.pop("kvbench")
        return counts, config_from_hf(SimpleNamespace(**conf), page_size=64)

    def test_a_tokens_matmuls_are_the_cuts_parameters(self, real):
        """2 FLOPs a parameter a token: attention 187.1 M and the indexer
        14.0 M in all 5 layers, the dense layer's 396.4 M, and in 4 layers
        the gate 1.8 M, the shared expert 44.0 M and half an expert held."""
        counts, cfg = real
        per_layer = counts.flops_per_token(cfg) / cfg.num_layers
        assert per_layer == pytest.approx(6.7e8, rel=0.01)

    def test_a_pair_pays_the_indexer_over_all_and_attention_over_kept(
            self, real):
        counts, cfg = real
        base = counts.prefill_flops(cfg, 0, 1) - counts.prefill_flops(
            cfg, 0, 0)
        one = counts.prefill_flops(cfg, 9999, 1) - base
        # One query at 10,000 keys: 64 x 128 over all, 128 heads x (2 x
        # 128 + 64) over the 2048 kept; its own key was in ``base``.
        want = 5 * 2.0 * (64 * 128 * 9999 + 128 * 320 * 2047)
        assert one == pytest.approx(want, rel=1e-6)

    def test_decode_bytes_are_a_floor(self, real):
        counts, cfg = real
        for n in (100, 2048, 8192, 33792):
            true = counts.latent_bytes(cfg, min(n, 2048))
            assert counts.decode_attention_bytes(cfg, n) <= true
        assert counts.latent_bytes(cfg, 1) == 5 * 640 * 2
        assert counts.index_bytes(cfg, 1) == 5 * 128 * 2
