"""A model that brings a prediction module (``LlamaConfig.
num_nextn_predict_layers``): the module drafts one token, a decode step runs
the main model over the row's last token and the draft, and a row emits one
token or two. Held here, at toy widths in float32 on the CPU:

- the system against the plain reference (``kvbench/references/
  openpangu-ultra-ep32-l5.py``): prefill logits, tokens decoded through the
  cache, a prefix hit, and every draft the module leaves;
- the accept path: drafts replayed from the row's own continuation (every
  one right), a token that is always wrong, and the module's own: the tokens
  a request receives are those of the one-token decode, whatever the drafts;
- the cache invariant: only accepted tokens reach committed blocks and
  ``BlockStored`` events, a finished request leaves no page behind, and a
  prefix hit (also one that parts from the prefix at a block boundary) reads
  what a cold run computes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.events.model import BlockStoredEvent
from llmd_kv_cache_tpu.models import llama
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.hf_loader import config_from_hf

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "kvbench" / "configs" / "openpangu-ultra-ep32-l5.json"
PAGE = 4


def toy(nextn=1, **over):
    """One dense and one expert layer behind latent attention with q-LoRA,
    sandwich norms, a chip's share of the experts, and the module."""
    return llama.LlamaConfig(**{**dict(
        vocab_size=48, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=16, intermediate_size=128, page_size=PAGE,
        kv_lora_rank=16, qk_rope_head_dim=8, q_lora_rank=24, latent_pad=8,
        rope_theta=25.6e6, post_norms=True, num_experts=8,
        num_experts_per_token=2, experts_held=(0, 4), moe_layers=(1,),
        n_shared_experts=1, moe_intermediate_size=32,
        moe_router=("deepseek_v3", 1, 1, 1, 2.5), moe_dispatch="grouped",
        embed_init_scale=0.3, dtype=jnp.float32,
        num_nextn_predict_layers=nextn), **over})


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "openpangu_reference",
        ROOT / "kvbench" / "references" / "openpangu-ultra-ep32-l5.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    cfg = toy()
    return cfg, llama.init_params(jax.random.PRNGKey(3), cfg)


def engine(cfg, params, events=None, **over):
    conf = dict(model=cfg, num_pages=96, max_pages_per_seq=24, max_batch=3,
                max_prefill_tokens=2 * PAGE)
    conf.update(over)
    return MiniEngine(EngineConfig(**conf), params=params,
                      event_sink=None if events is None else events.extend)


def plain(model):
    """The same weights without the module: the one-token decode."""
    cfg, params = model
    return (dataclasses.replace(cfg, num_nextn_predict_layers=0),
            {k: v for k, v in params.items() if k != "mtp"})


def prompt_of(n, seed=0, vocab=48):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


@pytest.fixture(scope="module")
def one_token(model):
    """``(prompt, new) -> tokens`` of the one-token-a-step decode."""
    eng = engine(*plain(model))
    seen = {}

    def decode(prompt, new):
        key = (tuple(prompt), new)
        if key not in seen:
            seen[key] = eng.generate(f"r{len(seen)}", prompt, new)
        return list(seen[key])
    return decode


def serve(eng, prompt, new, drafts="own", want=None, rid="a"):
    """One request to its end. ``drafts``: the module's ``"own"``;
    ``"right"``, every draft replaced by the next token of ``want`` (the
    row's own continuation); ``"wrong"``, by a token that it is not. A
    replaced draft is the host's, so every program is read before the next
    is built. Returns ``(request, decode steps, drafts the steps ran)``."""
    if drafts != "own":
        eng._defers = False
    req = eng.enqueue(rid, prompt, max_new_tokens=new)
    steps, ran = 0, []
    while not req.done:
        if req.prefill_pos is None and drafts != "own":
            nxt = want[len(req.output)] if len(req.output) < len(want) else 0
            req.draft = nxt if drafts == "right" else (nxt + 1) % 47 + 1
        decoding = req.prefill_pos is None and bool(req.output)
        if decoding:
            ran.append((len(req.output), req.draft))
        eng.step()
        steps += decoding
    return req, steps, ran


def committed_only_what_was_accepted(eng, events, prompts):
    """Every committed block's hash is that of its prompt's tokens, every
    ``BlockStored`` names prompt tokens only, and nothing of a finished
    request stays allocated."""
    manager = eng.block_manager
    known = {}
    for prompt in prompts:
        hashes = eng.processor.tokens_to_kv_block_keys(
            0, prompt, eng.cfg.model_name)
        for i, h in enumerate(hashes):
            known[h] = tuple(prompt[i * PAGE:(i + 1) * PAGE])
    for h, info in manager.blocks.items():
        assert known[h] == tuple(info.tokens)
        assert info.ref_count == 0
    for batch in events:
        for ev in getattr(batch, "events", [batch]):
            if isinstance(ev, BlockStoredEvent) and ev.tokens:
                for i, h in enumerate(ev.block_hashes):
                    assert known[h] == tuple(
                        ev.tokens[i * PAGE:(i + 1) * PAGE])
    stats = manager.pool_stats()
    assert stats["orphan_pages"] == 0
    assert (stats["free_pages"] + stats["cached_pages"]
            == manager.num_pages - 1)


class TestAgainstTheReference:
    def test_prefill_decode_hit_and_every_draft(self, model, ref):
        cfg, params = model
        prompt = prompt_of(4 * PAGE + 3, 1)
        eng = engine(cfg, params)
        req, _, ran = serve(eng, prompt, 7)
        tokens = prompt + req.output
        at = list(range(len(prompt) - 1, len(tokens) - 1))
        want = ref.logits_at(params, cfg, tokens, at)
        np.testing.assert_allclose(np.asarray(req.last_logits), want[0],
                                   rtol=2e-3, atol=2e-4)
        for i, token in enumerate(req.output[:len(at)]):
            assert want[i].max() - want[i][token] < 1e-3
        # The draft a step ran with k tokens out stood for the token after
        # output[k - 1]: the module's logits at the position before it.
        drafts = ref.draft_logits_at(params, cfg, tokens,
                                     list(range(len(tokens) - 1)))
        assert ran
        for out, draft in ran:
            row = drafts[len(prompt) + out - 2]
            assert row.max() - row[draft] < 1e-3

        again = eng.enqueue("hit", prompt, max_new_tokens=1)
        while not again.done:
            eng.step()
        assert again.cached_len == 4 * PAGE
        np.testing.assert_allclose(np.asarray(again.last_logits), want[0],
                                   rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("backend", ["xla", "prefill"])
    def test_the_modules_logits_at_every_position(self, model, ref, backend):
        """One chunk over a whole prompt, the module's logits at every
        position (not the last alone), on the XLA path and through the
        chunk's kernels (interpreted)."""
        cfg, params = model
        n = 3 * PAGE + 2
        tokens = prompt_of(n + 1, 2)
        pools = llama.init_kv_cache(cfg, 8)
        table = jnp.arange(1, 6, dtype=jnp.int32)[None, :]
        ctx, new = jnp.zeros((1,), jnp.int32), jnp.asarray([n], jnp.int32)
        chunk = jnp.zeros((1, 4 * PAGE), jnp.int32).at[0, :n].set(
            jnp.asarray(tokens[:n]))
        after = jnp.zeros((1, 4 * PAGE), jnp.int32).at[0, :n].set(
            jnp.asarray(tokens[1:]))
        _, hidden, k, v = llama._main_forward(
            params, cfg, chunk, *pools, table, ctx, new, backend, True, None)
        got, k, _ = llama.draft_logits(
            params, cfg, hidden, after, k, v, table, ctx, new, backend,
            interpret=True, last_only=False)
        want = ref.draft_logits_at(params, cfg, tokens, list(range(n)))
        np.testing.assert_allclose(np.asarray(got[0, :n]), want, rtol=2e-3,
                                   atol=2e-4)
        # The module's latents are layer 0 of the pool, from slot 1 on.
        assert not np.asarray(k[0, 1, 0, 0]).any()
        assert np.asarray(k[0, 1, 0, 1]).any()


class TestTheAcceptPath:
    @pytest.mark.parametrize("drafts", ["own", "right", "wrong"])
    @pytest.mark.parametrize("new", [7, 8])
    def test_tokens_are_the_one_token_decodes(self, model, one_token, drafts,
                                              new):
        """At page and block boundaries (pages of 4), with ``max_new_tokens``
        odd and even: a second token past the cap is dropped."""
        cfg, params = model
        prompt = prompt_of(2 * PAGE + 3, 4)
        want = one_token(prompt, new)
        events = []
        eng = engine(cfg, params, events)
        req, steps, _ = serve(eng, prompt, new, drafts, want)
        assert req.output == want
        if drafts == "right":
            assert steps == new // 2  # two a step behind the first token
        if drafts == "wrong":
            assert steps == new - 1
        eng.step()
        committed_only_what_was_accepted(eng, events, [prompt])

    @pytest.mark.parametrize("drafts", ["own", "right"])
    def test_through_the_kernels(self, model, one_token, drafts):
        cfg, params = model
        prompt = prompt_of(2 * PAGE + 1, 5)
        want = one_token(prompt, 6)
        events = []
        eng = engine(cfg, params, events, use_pallas_decode=True)
        assert eng.attention_backends["decode"]["backend"] == "pallas"
        req, steps, _ = serve(eng, prompt, 6, drafts, want)
        assert req.output == want
        if drafts == "right":
            assert steps == 3
        committed_only_what_was_accepted(eng, events, [prompt])

    def test_launched_ahead_and_an_abort_between_launch_and_read(
            self, model, one_token, monkeypatch):
        from llmd_kv_cache_tpu.models import engine as engine_module
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetryConfig)

        monkeypatch.setattr(engine_module, "_launch_counts", {})
        cfg, params = model
        prompts = {rid: prompt_of(n, seed) for rid, n, seed in (
            ("a", 9, 6), ("b", 14, 7), ("c", 5, 8))}
        events = []
        eng = engine(cfg, params, events, telemetry=EngineTelemetryConfig())
        reqs = {rid: eng.enqueue(rid, p, max_new_tokens=12)
                for rid, p in prompts.items()}
        aborted = False
        for _ in range(200):
            if not eng.requests:
                break
            eng.step()
            if (not aborted and eng._unread is not None
                    and eng._unread.host is None
                    and eng._unread.row_of(reqs["c"]) >= 0):
                aborted = eng.abort_request("c")
        assert aborted and not eng.requests
        for rid in "ab":
            assert reqs[rid].output == one_token(prompts[rid], 12)
        full = one_token(prompts["c"], 12)
        assert reqs["c"].output == full[:len(reqs["c"].output)]
        look = eng.telemetry.debug_vars()
        assert look["lookahead"]["launched_ahead"] > 0
        assert look["speculation"]["spec_drafted"] > 0
        committed_only_what_was_accepted(eng, events, prompts.values())

    def test_a_step_counts_what_it_accepted(self, model, one_token):
        """The counters: one draft a row a step, accepted where two
        tokens came."""
        from llmd_kv_cache_tpu.telemetry.engine_telemetry import (
            EngineTelemetryConfig)

        cfg, params = model
        prompt = prompt_of(6, 9)
        want = one_token(prompt, 9)
        eng = engine(cfg, params, telemetry=EngineTelemetryConfig())
        req, steps, _ = serve(eng, prompt, 9, "right", want)
        assert req.output == want and steps == 4
        assert eng.telemetry.spec_drafted == 4
        assert eng.telemetry.spec_accepted == 4


class TestAPrefixHit:
    def test_parting_at_a_block_boundary(self, model):
        """A second request that shares the first blocks and differs in the
        token right behind them reads, from the shared pages, main logits
        and drafts equal to its own cold run's: the module's entry in a
        block's last slot depends on no token beyond the block."""
        cfg, params = model
        first = prompt_of(3 * PAGE + 2, 10)
        second = first[:2 * PAGE] + prompt_of(PAGE + 3, 11)
        assert second[2 * PAGE] != first[2 * PAGE]

        cold = engine(cfg, params)
        want, _, want_ran = serve(cold, second, 6)
        assert want.cached_len == 0

        events = []
        eng = engine(cfg, params, events)
        serve(eng, first, 5, rid="first")
        got, _, got_ran = serve(eng, second, 6, rid="second")
        assert got.cached_len == 2 * PAGE
        np.testing.assert_allclose(np.asarray(got.last_logits),
                                   np.asarray(want.last_logits), rtol=1e-4,
                                   atol=1e-5)
        assert got.output == want.output
        assert got_ran == want_ran
        eng.step()
        committed_only_what_was_accepted(eng, events, [first, second])

    def test_a_hit_computes_its_last_position_again(self, model):
        cfg, params = model
        prompt = prompt_of(3 * PAGE + 2, 12)
        eng = engine(cfg, params)
        serve(eng, prompt, 2, rid="cold")
        req = eng.enqueue("hit", prompt, max_new_tokens=2)
        assert req.cached_len == 3 * PAGE
        assert req.prefill_pos == 3 * PAGE - 1


class TestTheShareAndTheConfiguration:
    def test_the_shares_add_up_to_the_uncut_layer(self, ref):
        """The guide's share test: the routed parts that 32 shares of one
        expert each give, with the shared expert counted once, are the
        uncut layer, in the program and against the reference's."""
        cfg = toy(0, num_experts=32, num_experts_per_token=4,
                  experts_held=())
        lyr = llama.init_params(jax.random.PRNGKey(5), cfg)["layers"][1]
        x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, 64))
        whole = llama._moe_deepseek(x, lyr, cfg)
        flat = x.reshape(-1, 64)
        shared = ((jax.nn.silu(flat @ lyr["w_gate_sh"])
                   * (flat @ lyr["w_up_sh"])) @ lyr["w_down_sh"]
                  ).reshape(x.shape)
        total, held_sum = shared, 0
        for rank in range(32):
            part_cfg = dataclasses.replace(cfg, experts_held=(rank, 1))
            part = {**lyr, **{k: lyr[k][rank:rank + 1]
                              for k in ("w_gate", "w_up", "w_down")}}
            counters = {}
            total = total + llama._moe_deepseek(
                x, part, part_cfg, counters=counters) - shared
            held_sum += int(counters["assignments_held"])
        assert held_sum == 24 * 4  # every assignment fell to one share
        np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
        with jax.default_matmul_precision("highest"):
            want = ref._routed(jnp.asarray(flat), lyr, cfg, 1, [], {}, {},
                               {}, 0.0)
        np.testing.assert_allclose(total.reshape(-1, 64), want, rtol=2e-3,
                                   atol=2e-4)

    def test_the_loader_reads_the_module(self):
        conf = json.loads(CONFIG.read_text())
        group = conf.pop("kvbench")
        cfg = config_from_hf(SimpleNamespace(**conf), page_size=64)
        assert cfg.num_nextn_predict_layers == 1 and cfg.post_norms
        assert cfg.page_layers == (-1, 0, 1, 2, 3, 4)
        assert cfg.num_experts == 256 and cfg.experts_held == (0, 8)
        assert cfg.moe_router == ("deepseek_v3", 1, 1, 1, 2.5)
        assert not cfg.rope_scaling and cfg.softmax_scale_mult == 1.0
        assert cfg.kv_cache_head_dim == 640 and cfg.q_lora_rank == 1536
        assert "num_nextn_predict_layers" not in group["reduced"]
        toy_cfg = config_from_hf(SimpleNamespace(
            **{**conf, **group["rehearse"]["model"]}), page_size=16)
        assert toy_cfg.num_nextn_predict_layers == 1

    def test_a_module_adds_a_layer_to_every_page(self, model):
        cfg, params = model
        k, _ = llama.init_kv_cache(cfg, 5)
        assert k.shape[0] == cfg.num_layers + 1
        assert set(params["mtp"]) == {"enorm", "hnorm", "w_eh", "layer",
                                      "final_norm"}
        assert params["mtp"]["w_eh"].shape == (128, 64)
        assert "router" in params["mtp"]["layer"]
        fused = llama.fuse_params(params, cfg)
        assert "w_mla_in" in fused["mtp"]["layer"]
        back = llama.unfuse_params(fused, cfg)
        np.testing.assert_array_equal(back["mtp"]["layer"]["w_dkv"],
                                      params["mtp"]["layer"]["w_dkv"])

    @pytest.mark.parametrize("what, match", [
        (dict(num_nextn_predict_layers=2), "more than one"),
        (dict(kv_lora_rank=0, qk_rope_head_dim=0, q_lora_rank=0,
              latent_pad=0), "latent attention"),
    ])
    def test_what_is_not_built_is_refused_by_name(self, what, match):
        with pytest.raises(NotImplementedError, match=match):
            toy(**what)

    @pytest.mark.parametrize("what, match", [
        (dict(ragged_attention=True), "ragged_attention"),
        (dict(offload_spec=object()), "offload spec"),
    ])
    def test_the_engine_refuses_its_unbuilt_pairs(self, model, what, match):
        cfg, params = model
        spec = what.pop("offload_spec", None)
        with pytest.raises(ValueError, match=match):
            MiniEngine(EngineConfig(model=cfg, num_pages=16,
                                    max_pages_per_seq=8, **what),
                       params=params, offload_spec=spec)
