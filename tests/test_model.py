"""Paged-Llama model and ops tests (CPU backend, 8 virtual devices)."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import step_programs as sp

from llmd_kv_cache_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_kv_cache,
    init_params,
)
from llmd_kv_cache_tpu.ops.kv_pages import gather_kv_pages, scatter_kv_pages
from llmd_kv_cache_tpu.ops.paged_attention import paged_attention


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


class TestKVPages:
    def test_scatter_gather_roundtrip(self):
        cache = jnp.zeros((8, 2, 4, 4), jnp.float32)
        new = jnp.arange(2 * 8 * 2 * 4, dtype=jnp.float32).reshape(2, 8, 2, 4)
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        positions = jnp.arange(8)[None, :].repeat(2, axis=0)
        valid = jnp.ones((2, 8), bool)
        cache = scatter_kv_pages(cache, new, table, positions, valid)
        out = gather_kv_pages(cache, table)
        np.testing.assert_allclose(np.asarray(out), np.asarray(new))

    def test_invalid_slots_go_to_garbage(self):
        cache = jnp.zeros((4, 1, 4, 2), jnp.float32)
        new = jnp.ones((1, 4, 1, 2), jnp.float32)
        table = jnp.asarray([[2]], jnp.int32)
        positions = jnp.arange(4)[None, :]
        valid = jnp.asarray([[True, True, False, False]])
        cache = scatter_kv_pages(cache, new, table, positions, valid)
        page2 = np.asarray(cache[2])  # [kv_heads, page_size, head_dim]
        assert page2[:, :2].sum() == 4  # two valid slots written
        assert page2[:, 2:].sum() == 0  # invalid slots untouched
        assert np.asarray(cache[0]).sum() != 0  # garbage page absorbed them


class TestPagedAttention:
    def test_matches_dense_attention(self):
        """Paged attention == plain causal attention on contiguous pages."""
        rng = np.random.default_rng(0)
        b, s, h, d, page = 2, 8, 2, 4, 4
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)

        # scatter k/v into pages 1..4 (per sequence)
        k_cache = jnp.zeros((16, h, page, d), jnp.float32)
        v_cache = jnp.zeros((16, h, page, d), jnp.float32)
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        positions = jnp.arange(s)[None, :].repeat(b, axis=0)
        valid = jnp.ones((b, s), bool)
        k_cache = scatter_kv_pages(k_cache, k, table, positions, valid)
        v_cache = scatter_kv_pages(v_cache, v, table, positions, valid)

        out = paged_attention(
            q, k_cache, v_cache, table, positions, jnp.full((b,), s, jnp.int32)
        )

        # dense reference
        scale = d ** -0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)

        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gqa_grouping(self):
        b, s, qh, kvh, d, page = 1, 4, 4, 2, 4, 4
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(b, s, qh, d)), jnp.float32)
        k_cache = jnp.asarray(rng.normal(size=(4, page, kvh, d)), jnp.float32)
        v_cache = jnp.asarray(rng.normal(size=(4, page, kvh, d)), jnp.float32)
        table = jnp.asarray([[1]], jnp.int32)
        positions = jnp.arange(s)[None, :]
        out = paged_attention(
            q, k_cache, v_cache, table, positions, jnp.asarray([s], jnp.int32)
        )
        assert out.shape == (b, s, qh, d)


class TestForward:
    def test_prefill_then_decode_matches_full_prefill(self, cfg, params):
        """KV correctness: logits for token N computed incrementally equal
        logits from prefilling all N+1 tokens at once."""
        prompt = np.asarray([[5, 7, 9, 11, 13, 17, 19, 23]], np.int32)
        table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

        # full prefill of 8 tokens
        k1, v1 = init_kv_cache(cfg, 8)
        logits_full, k1, v1 = forward(
            params, cfg, jnp.asarray(prompt), k1, v1, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([8], jnp.int32),
        )

        # prefill 7, then decode token 8
        k2, v2 = init_kv_cache(cfg, 8)
        _, k2, v2 = forward(
            params, cfg, jnp.asarray(prompt[:, :7]), k2, v2, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([7], jnp.int32),
        )
        logits_step, k2, v2 = forward(
            params, cfg, jnp.asarray(prompt[:, 7:8]), k2, v2, table,
            jnp.asarray([7], jnp.int32), jnp.asarray([1], jnp.int32),
        )

        np.testing.assert_allclose(
            np.asarray(logits_full[0, 7]), np.asarray(logits_step[0, 0]),
            rtol=3e-2, atol=3e-2,  # bf16 accumulation tolerance
        )

    def test_padding_does_not_affect_logits(self, cfg, params):
        prompt = np.asarray([[5, 7, 9, 11]], np.int32)
        padded = np.asarray([[5, 7, 9, 11, 0, 0, 0, 0]], np.int32)
        table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

        k1, v1 = init_kv_cache(cfg, 8)
        logits_a, *_ = forward(
            params, cfg, jnp.asarray(prompt), k1, v1, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([4], jnp.int32),
        )
        k2, v2 = init_kv_cache(cfg, 8)
        logits_b, *_ = forward(
            params, cfg, jnp.asarray(padded), k2, v2, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([4], jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(logits_a[0, 3]), np.asarray(logits_b[0, 3]), rtol=1e-5
        )


class TestTraceNames:
    """What a device trace calls the step programs, the kernels and the
    regions inside them: constants in the program (the benchmark's readers
    are held to them in ``kvbench/tests/test_trace_names.py``)."""

    def test_program_and_kernel_names_are_the_constants(self):
        from llmd_kv_cache_tpu.models import llama
        from llmd_kv_cache_tpu.ops import pallas_paged_attention as ppa

        assert llama.forward_prefill_pallas.__name__ == llama.PROGRAM_PREFILL
        assert llama.forward_decode_pallas.__name__ == llama.PROGRAM_DECODE
        # What the engine dispatches, and so what a trace of it shows.
        assert llama.step_prefill_pallas.__name__ == llama.PROGRAM_PREFILL
        assert llama.step_decode_pallas.__name__ == llama.PROGRAM_DECODE
        assert ppa.pallas_paged_decode_attention.__name__ == ppa.KERNEL_DECODE
        assert ppa.pallas_paged_prefill_attention.__name__ == ppa.KERNEL_PREFILL
        assert ppa.pallas_paged_ragged_attention.__name__ == ppa.KERNEL_RAGGED
        from llmd_kv_cache_tpu.ops import pallas_latent_prefill as plp

        assert (plp.pallas_per_head_prefill_attention.__name__
                == plp.KERNEL_PER_HEAD_PREFILL
                == "pallas_per_head_prefill_attention")
        # Today's strings: the readers that exist read what they read.
        assert (llama.PROGRAM_PREFILL, llama.PROGRAM_DECODE) == (
            "forward_prefill_pallas", "forward_decode_pallas")
        assert (ppa.KERNEL_DECODE, ppa.KERNEL_PREFILL) == (
            "pallas_paged_decode_attention", "pallas_paged_prefill_attention")

    def _lowered(self, cfg, scoped):
        """Compiled HLO of one decode-shaped ``forward`` step, and the
        set of scopes its op names carry."""
        import contextlib
        import re
        from unittest import mock

        from llmd_kv_cache_tpu.models import llama

        params = init_params(jax.random.PRNGKey(0), cfg)
        k, v = init_kv_cache(cfg, 8)
        args = (params, cfg, jnp.zeros((2, 1), jnp.int32), k, v,
                jnp.zeros((2, 4), jnp.int32), jnp.array([3, 5], jnp.int32),
                jnp.ones((2,), jnp.int32))
        # A fresh function under a fresh jit: the patched trace must not
        # be served from (or left in) forward's own cache.
        def step(*args):
            return llama.forward.__wrapped__(*args)

        fn = jax.jit(step, static_argnums=(1,), donate_argnums=(3, 4))
        patch = (contextlib.nullcontext() if scoped else mock.patch.object(
            jax, "named_scope", lambda name: contextlib.nullcontext()))
        with patch:
            text = fn.lower(*args).compile().as_text()
        found = {s for s in llama.SCOPES
                 if re.search(rf'op_name="[^"]*/{s}/', text)}
        # Without what only describes the source: each op's metadata and
        # the module's tables of files, functions and stack frames.
        bare = re.sub(r", metadata=\{[^}]*\}", "", text)
        tables = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")
        bare = "\n\n".join(part for part in bare.split("\n\n")
                           if not part.lstrip().startswith(tables))
        return bare, found

    def test_scopes_are_metadata_only(self):
        from llmd_kv_cache_tpu.models import llama

        cfg = LlamaConfig.tiny()
        with_scopes, found = self._lowered(cfg, scoped=True)
        without, none = self._lowered(cfg, scoped=False)
        assert none == set()
        assert found == set(llama.SCOPES) - {llama.SCOPE_SAMPLE}
        # The same instructions under the same names: a scope is a path
        # in op_name and nothing else, so fusion names, the readers keyed
        # on them and the compile-cache key (which leaves metadata out)
        # cannot move.
        assert with_scopes == without


class TestLatentPrefillForms:
    """A latent model's chunk program holds one of two attention kernels,
    chosen from its shapes (``llama.prefill_per_head``): per head on keys
    and values expanded from the latents inside the kernel from enough
    queries on, else absorbed. Same function either way."""

    @pytest.fixture(scope="class")
    def latent(self):
        # Rank 16 + rope 8 in pages of 24 lanes, heads of 16: the forms'
        # FLOPs break even at 64 queries, the rule's margin makes it 84.
        import dataclasses

        cfg = dataclasses.replace(LlamaConfig.deepseek_tiny(),
                                  dtype=jnp.float32)
        return SimpleNamespace(
            cfg=cfg, params=init_params(jax.random.PRNGKey(5), cfg),
            # The same prefill under another jit key (decode-only field).
            twin=dataclasses.replace(cfg, mla_decode_stream="reuse"))

    @staticmethod
    def chunk(cfg, seq, ctx, new, seed=0):
        k_cache, v_cache = init_kv_cache(cfg, num_pages=80)
        tokens = np.zeros((1, seq), np.int32)
        tokens[0, :new] = np.random.default_rng(seed).integers(1, 250, new)
        return (jnp.asarray(tokens), k_cache, v_cache,
                jnp.arange(1, 65, dtype=jnp.int32)[None, :],
                jnp.asarray([ctx], jnp.int32), jnp.asarray([new], jnp.int32))

    def test_the_rule_is_the_shapes(self, latent, cfg):
        from llmd_kv_cache_tpu.models import llama

        assert llama.prefill_per_head(latent.cfg, 128)
        assert llama.prefill_per_head(latent.cfg, 84)
        assert not llama.prefill_per_head(latent.cfg, 83)
        assert not llama.prefill_per_head(latent.cfg, 128, mesh=object())
        assert not llama.prefill_per_head(cfg, 4096)      # no latent

    @pytest.mark.parametrize("seq,per_head", [(128, True), (64, False)])
    def test_a_program_holds_one_of_the_two_kernels(self, latent, seq,
                                                    per_head):
        from llmd_kv_cache_tpu.models import llama
        from llmd_kv_cache_tpu.ops.pallas_latent_prefill import (
            KERNEL_PER_HEAD_PREFILL)
        from llmd_kv_cache_tpu.ops.pallas_paged_attention import (
            KERNEL_PREFILL)

        text = str(jax.make_jaxpr(
            lambda params, *chunk: llama.forward_prefill_pallas(
                params, latent.cfg, *chunk, interpret=True,
                last_only=True))(latent.params,
                                 *self.chunk(latent.cfg, seq, 0, seq - 3)))
        assert (KERNEL_PER_HEAD_PREFILL in text) == per_head
        assert (KERNEL_PREFILL in text) == (not per_head)

    def test_both_forms_give_the_chunks_logits(self, latent, monkeypatch):
        """A chunk of 128 behind 40 cached tokens: per head, absorbed (the
        rule switched off under the twin's jit key) and the XLA forward."""
        from llmd_kv_cache_tpu.models import llama

        first = self.chunk(latent.cfg, 64, 0, 40, seed=1)
        _, k_cache, v_cache = forward(latent.params, latent.cfg, *first)
        tokens, _, _, table, _, new = self.chunk(latent.cfg, 128, 40, 117)

        def args():                    # every forward donates its pools
            return (tokens, jnp.copy(k_cache), jnp.copy(v_cache), table,
                    jnp.asarray([40], jnp.int32), new)

        want, _, _ = forward(latent.params, latent.cfg, *args())
        per_head, _, _ = llama.forward_prefill_pallas(
            latent.params, latent.cfg, *args(), interpret=True)
        monkeypatch.setattr(llama, "prefill_per_head",
                            lambda *_a, **_k: False)
        absorbed, _, _ = llama.forward_prefill_pallas(
            latent.params, latent.twin, *args(), interpret=True)
        for got in (per_head, absorbed):
            np.testing.assert_allclose(np.asarray(got[0, :117]),
                                       np.asarray(want[0, :117]),
                                       rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(per_head[0, :117]),
                                   np.asarray(absorbed[0, :117]),
                                   rtol=2e-4, atol=2e-4)


class TestFramesUnderEveryProgram:
    """A tripwire, not a law. The functions below are on the stack of every
    op of every model's step programs while they are traced and lowered,
    and the sizes of their frames (locals + stack slots) decide which call
    sites of that work fall on the edge of one of CPython's 16 KiB frame
    chunks, where every call maps and unmaps memory: a slot more or less
    has moved every dense cell's set-up by seconds, either way (PERF.md §6,
    PR 47; ``hack/stack_chunk_cliff.py``). A PR that changes one of them on
    purpose measures ``setup_s`` in ``qwen3-1.7b.short-control`` on the
    chip, parent against change, and then writes the new size here."""

    SIZES = {
        "llmd_kv_cache_tpu/models/llama.py": {
            "_forward_impl_grouped": 83, "_forward_impl": 32,
            "forward_prefill_pallas": 39,
            "forward_prefill_pallas.attention_fn": 34,
            "forward_decode_pallas": 37, "step_program.program": 34},
        "llmd_kv_cache_tpu/models/engine.py": {
            "MiniEngine.step": 26, "MiniEngine._prefill_chunk": 41,
            "MiniEngine._launch_decode": 36},
    }

    def test_their_frames_keep_their_size(self):
        import sys

        if sys.version_info[:2] != (3, 12):
            pytest.skip("slot counts are the 3.12 compiler's")
        root = Path(__file__).resolve().parents[1]
        sys.path.insert(0, str(root / "hack"))
        try:
            import frame_sizes
        finally:
            sys.path.pop(0)
        for rel, want in self.SIZES.items():
            got = frame_sizes.frames(root / rel)
            assert {n: got.get(n) for n in want} == want, rel


class TestNoLayerOfAPoolMoves:
    """A step's K/V rows go into the donated stack at ``[layer, page, :,
    slot, :]`` and every backend reads the stack at a layer index. What
    took a layer out (a slice), wrote into the copy (a scatter on a layer)
    and put it back (a dynamic-update-slice of a layer) moved the pool
    around every layer of every step: 70% of the device's time on a v5e.

    Read off the module as lowered for the TPU (the kernels as Mosaic
    custom calls, not the interpreter's emulation of their DMAs), before
    any backend: it holds here, on the CPU."""

    @staticmethod
    def _ops(module):
        def walk(op):
            yield op
            for region in op.regions:
                for block in region:
                    for child in block:
                        yield from walk(child.operation)
        return walk(module.operation)

    @pytest.mark.parametrize("name", list(sp.PROGRAMS))
    def test_program_touches_rows_not_layers(self, name):
        prog = sp.PROGRAMS[name]
        params = init_params(jax.random.PRNGKey(0), prog.cfg)
        pools = sp.init_pools(prog.cfg)
        module = prog.fn.trace(
            *prog.args(params, prog.cfg, pools, 0), **prog.static,
        ).lower(lowering_platforms=("tpu",)).compiler_ir()

        stacks = {tuple(p.shape) for p in pools if p.shape[-1]}
        # One layer, as a slice of the stack leaves it or squeezed.
        layers = {s[1:] for s in stacks} | {(1,) + s[1:] for s in stacks}

        def shape(value):
            return tuple(getattr(value.type, "shape", ()))

        seen, moved = set(), []
        for op in self._ops(module):
            seen.add(op.name)
            if op.name in ("stablehlo.slice", "stablehlo.dynamic_slice",
                           "stablehlo.gather"):
                hit = shape(op.results[0]) in layers
            elif op.name == "stablehlo.dynamic_update_slice":
                hit = shape(op.operands[1]) in layers
            elif op.name == "stablehlo.scatter":
                hit = shape(op.operands[0]) in layers
            else:
                continue
            if hit:
                moved.append(f"{op.name} {shape(op.results[0])}")
        assert not moved
        # The walk saw the program: its writes, and its kernel if it has one.
        assert "stablehlo.scatter" in seen
        assert ("stablehlo.custom_call" in seen) == prog.pallas


    @pytest.mark.parametrize("name", list(sp.DRAFTING))
    def test_speculative_program_touches_rows_not_layers(self, name):
        """The programs of a model that drafts: the main model's two
        positions and the module's rows go into the stack in place too, the
        module's into layer 0."""
        from llmd_kv_cache_tpu.models import llama

        pallas, chunk = sp.DRAFTING[name]
        cfg = sp.DRAFT_CFG
        params = init_params(jax.random.PRNGKey(0), cfg)
        pools = sp.init_pools(cfg)
        packed, shapes = sp.drafting_inputs(chunk)
        extra = {} if chunk else {"prev": jnp.zeros((8,), jnp.int32)}
        module = llama.DRAFTING_PROGRAMS[pallas, chunk].trace(
            params, cfg, packed, pools, shapes=shapes, **extra,
        ).lower(lowering_platforms=("tpu",)).compiler_ir()
        stack = tuple(pools[0].shape)
        layers = {stack[1:], (1,) + stack[1:]}
        seen = set()
        for op in self._ops(module):
            seen.add(op.name)
            if op.name in ("stablehlo.slice", "stablehlo.dynamic_slice",
                           "stablehlo.gather"):
                moved = tuple(op.results[0].type.shape)
            elif op.name == "stablehlo.dynamic_update_slice":
                moved = tuple(op.operands[1].type.shape)
            elif op.name == "stablehlo.scatter":
                moved = tuple(op.operands[0].type.shape)
            else:
                continue
            assert moved not in layers, (op.name, moved)
        assert "stablehlo.scatter" in seen
        assert ("stablehlo.custom_call" in seen) == pallas


class TestStepFormsStayWhatTheyWere:
    """A model without a prediction module is stepped by the programs it
    was stepped by before there were modules (PR 53): each step form's
    jaxpr, kernels' bodies included, hashes to what it hashed to at that
    PR's parent commit (``step_programs.step_form_jaxpr``: source positions
    and addresses taken out). A PR that changes a program on purpose
    writes the new hash here and says so; `python3 -c "import
    step_programs as sp, hashlib; ..."` over ``step_form_jaxpr``
    regenerates them."""

    PARENT = {
        ("forward", False): "41566c4569e93a4f",
        ("forward_mla", False): "ab42d57e6b5a77cd",
        ("forward_decode", False): "d8621efbf1d3aee8",
        ("forward_decode", True): "047d47611c6b4807",
        ("forward_hybrid", False): "6df7bf4b47aca6e5",
        ("forward_hybrid_decode", False): "859fbe2470c85f19",
        ("forward_hybrid_decode", True): "72c563787de2291b",
        ("forward_decode_pallas", False): "4ff07f204f260696",
        ("forward_decode_pallas", True): "940756cc4bc86688",
        ("forward_decode_pallas_mla", False): "37704127a2f4d79e",
        ("forward_decode_pallas_mla", True): "0b799f6505384c83",
        ("forward_prefill_pallas", False): "8f0e29ebbef8a369",
        ("forward_ragged", False): "0b3bb8d2206a457e",
    }

    @pytest.mark.parametrize("name, prev", list(PARENT))
    def test_a_program_is_the_parents(self, name, prev):
        import hashlib

        text = sp.step_form_jaxpr(name, prev)
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
            self.PARENT[name, prev])


def _benchmark_configs():
    """name -> file of every configuration ``BENCHMARK.json`` runs."""
    root = Path(__file__).resolve().parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {c["name"]: root / c["file"] for c in bench["configs"]}


class TestDecodeKernelLowersForTheChip:
    """``forward_decode_pallas`` lowered for the TPU, here on the CPU, at
    each benchmark configuration's rehearsal shape (one kv head: the
    per-head kernel) and at that depth with the published heads and the
    served page table (the merged kernel, 64 pages a superblock, eight
    granules a round): what Pallas cannot hand to Mosaic (a guard on a
    copy, a slice of the scratch at a traced page) fails here and not on
    the chip."""

    @pytest.mark.parametrize("served", [False, True],
                             ids=["rehearsal", "served_heads"])
    @pytest.mark.parametrize("name", list(_benchmark_configs()))
    def test_decode_program_lowers(self, name, served):
        from llmd_kv_cache_tpu.models.hf_loader import config_from_hf
        from llmd_kv_cache_tpu.models.llama import forward_decode_pallas

        conf = json.loads(_benchmark_configs()[name].read_text())
        kv = conf.pop("kvbench")
        toy = kv["rehearse"]
        model = {**conf, **toy["model"]}
        engine = {**kv["engine"], **toy["engine"]}
        if served:
            for key in ("num_attention_heads", "num_key_value_heads"):
                model[key] = conf[key]
            for key in ("max_pages_per_seq", "max_batch"):
                engine[key] = kv["engine"][key]
        cfg = config_from_hf(SimpleNamespace(**model),
                             page_size=engine["page_size"],
                             dtype=jnp.bfloat16)
        params = init_params(jax.random.PRNGKey(0), cfg)
        k_cache, v_cache = init_kv_cache(cfg, engine["num_pages"])
        rows, width = engine["max_batch"], engine["max_pages_per_seq"]
        state = {}
        if cfg.parallel_layers:
            # Its layers keep pages AND a state: the pools ride in the state
            # and the multipliers reach the body as a view, both of which
            # the step form sets up (``llama.with_pages_in_state``).
            from llmd_kv_cache_tpu.models import llama

            packed, shapes = llama.pack_inputs((
                np.zeros((rows, 1)), np.zeros((rows, width)),
                np.ones((rows,)), np.ones((rows,)), np.zeros((rows,)),
                np.zeros((3,))))
            text = llama.step_decode_pallas_paged_state.trace(
                params, cfg, packed,
                (k_cache, v_cache, *llama.init_state_pool(cfg)),
                shapes=shapes, interpret=False, mesh=None, batch_rows=1,
            ).lower(lowering_platforms=("tpu",)).as_text()
            assert text.count("tpu_custom_call") >= 2  # both mixers' kernels
            return
        if cfg.linear_layers:  # its state pool and the rows' slots in it
            from llmd_kv_cache_tpu.models.llama import init_state_pool

            state = {"state": (*init_state_pool(cfg),
                               jnp.zeros((rows,), jnp.int32), None)}
        text = forward_decode_pallas.trace(
            params, cfg, jnp.zeros((rows, 1), jnp.int32), k_cache, v_cache,
            jnp.zeros((rows, width), jnp.int32),
            jnp.ones((rows,), jnp.int32), jnp.ones((rows,), jnp.int32),
            **state,
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text


class TestStepForms:
    """A forward's step form (``llama.step_program``) is what the engine's
    ``step()`` dispatches: the same body, its per-step inputs in one packed
    array, the greedy sampling as its tail. One case per program of the
    table, padding rows included."""

    @staticmethod
    def _case(name):
        prog = sp.PROGRAMS[name]
        params = init_params(jax.random.PRNGKey(0), prog.cfg)
        static = dict(prog.static, interpret=True) if prog.pallas else dict(
            prog.static)
        if prog.chunk:
            static["last_only"] = True
        return prog, params, static

    @pytest.mark.parametrize("name", list(sp.PROGRAMS))
    def test_packed_inputs_unpack_to_the_arrays_they_replaced(self, name):
        from llmd_kv_cache_tpu.models import llama

        prog, params, _ = self._case(name)
        pools = sp.init_pools(prog.cfg)
        arrays = sp.step_inputs(prog.args(params, prog.cfg, pools, 1), pools)
        packed, shapes = llama.pack_inputs(arrays)
        assert packed.dtype == np.int32 and packed.ndim == 1
        assert packed.size == sum(np.asarray(a).size for a in arrays)
        on_host = llama.unpack_inputs(packed, shapes)
        in_program = jax.jit(llama.unpack_inputs, static_argnums=1)(
            packed, shapes)
        assert len(on_host) == len(in_program) == len(arrays) >= 4
        for want, a, b in zip(arrays, on_host, in_program):
            assert a.shape == b.shape == np.shape(want)
            assert b.dtype == jnp.int32
            np.testing.assert_array_equal(a, want)
            np.testing.assert_array_equal(np.asarray(b), want)

    @pytest.mark.parametrize("name", list(sp.PROGRAMS))
    def test_tokens_are_the_argmax_of_the_logits_form(self, name):
        from llmd_kv_cache_tpu.models import llama

        prog, params, static = self._case(name)
        want_pools = sp.init_pools(prog.cfg)
        got_pools = sp.init_pools(prog.cfg)
        for step in range(2):
            args = prog.args(params, prog.cfg, want_pools, step)
            out, *want_pools = prog.fn(*args, **static)
            packed, shapes = llama.pack_inputs(
                sp.step_inputs(args, got_pools))
            tokens, row, got_pools = prog.step(
                params, prog.cfg, packed, got_pools, shapes=shapes,
                keep_row=prog.chunk, **static)
            logits = np.asarray(out, np.float32)
            logits = logits.reshape(-1, logits.shape[-1])
            want = logits.argmax(-1)
            assert tokens.dtype == jnp.int32 and tokens.shape == want.shape
            np.testing.assert_array_equal(np.asarray(tokens), want)
            if prog.chunk:
                assert row.dtype == jnp.float32
                np.testing.assert_array_equal(np.asarray(row), logits[0])
            else:
                assert row is None
            for got, want_pool in zip(got_pools, want_pools):
                np.testing.assert_array_equal(
                    np.asarray(got, np.float32),
                    np.asarray(want_pool, np.float32))

    def test_ragged_keeps_the_last_row_that_holds_tokens(self):
        from llmd_kv_cache_tpu.models import llama

        prog, params, static = self._case("forward_ragged")
        pools = sp.init_pools(prog.cfg)
        args = list(prog.args(params, prog.cfg, pools, 0))
        # Two rows of tokens and two of padding: the prefill chunk is row 1.
        args[-3] = np.zeros((4, sp.TABLE.shape[1]), np.int32)
        args[-3][:2] = sp.TABLE
        args[-2] = np.asarray([0, 3, 5, 5, 5], np.int32)
        args[-1] = np.asarray([0, 0, 0, 0], np.int32)
        logits, *_ = prog.fn(*args, **static)
        packed, shapes = llama.pack_inputs(sp.step_inputs(args, pools))
        tokens, row, _ = prog.step(
            params, prog.cfg, packed, sp.init_pools(prog.cfg),
            shapes=shapes, keep_row=True, **static)
        np.testing.assert_array_equal(
            np.asarray(tokens), np.asarray(logits).argmax(-1))
        np.testing.assert_array_equal(np.asarray(row), np.asarray(logits)[1])

    @pytest.mark.parametrize("name", list(sp.PROGRAMS))
    def test_no_logits_leave_the_program(self, name):
        """An output is materialised: ``[rows, 1, vocab]`` in float32 is 19
        MB a step at a vocabulary of 152k. The step form hands back tokens
        and pools, and one ``[vocab]`` row where the caller keeps it."""
        from llmd_kv_cache_tpu.models import llama

        prog, params, static = self._case(name)
        static.pop("interpret", None)
        pools = sp.init_pools(prog.cfg)
        args = prog.args(params, prog.cfg, pools, 0)
        packed, shapes = llama.pack_inputs(sp.step_inputs(args, pools))
        vocab = prog.cfg.vocab_size

        def outputs(fn, *a, **kw):
            lowered = fn.trace(*a, **kw).lower(lowering_platforms=("tpu",))
            return [tuple(o.shape) for o in jax.tree.leaves(lowered.out_info)]

        assert prog.step.__name__ == prog.fn.__name__
        for keep in (False, True):
            shapes_out = outputs(
                prog.step, params, prog.cfg, packed, pools, shapes=shapes,
                keep_row=keep, **static)
            with_vocab = [s for s in shapes_out if vocab in s]
            assert with_vocab == ([(vocab,)] if keep else [])
            assert shapes_out[len(with_vocab) + 1:] == [
                tuple(p.shape) for p in pools]
        # The form tests and references call does hand its logits back.
        assert any(vocab in s for s in outputs(prog.fn, *args, **static))
