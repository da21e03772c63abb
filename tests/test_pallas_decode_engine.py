"""Engine decode equivalence: Pallas flash-decode vs XLA reference path."""

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig


def test_pallas_decode_matches_xla_path():
    prompt = list(range(40, 52))
    outs = {}
    for use_pallas in (False, True):
        engine = MiniEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_pages=64, max_pages_per_seq=16,
                model_name="tiny", pod_identifier="p",
                use_pallas_decode=use_pallas,
            ),
            seed=0,
        )
        outs[use_pallas] = engine.generate("r", prompt, max_new_tokens=4)
    assert outs[False] == outs[True]


def test_pallas_decode_matches_xla_with_sliding_window():
    """Backend equivalence holds for SWA models too (window masking + page
    skipping in the kernel)."""
    tiny = LlamaConfig.tiny()
    swa = LlamaConfig(
        vocab_size=tiny.vocab_size, hidden_size=tiny.hidden_size,
        num_layers=tiny.num_layers, num_heads=tiny.num_heads,
        num_kv_heads=tiny.num_kv_heads, head_dim=tiny.head_dim,
        intermediate_size=tiny.intermediate_size, page_size=tiny.page_size,
        sliding_window=8, swa_layers=tuple(range(tiny.num_layers)),
    )
    prompt = list(range(60, 84))  # 24-token context >> window 8
    outs = {}
    for use_pallas in (False, True):
        engine = MiniEngine(
            EngineConfig(model=swa, num_pages=64, max_pages_per_seq=16,
                         model_name="swa", pod_identifier="p",
                         use_pallas_decode=use_pallas),
            seed=0,
        )
        outs[use_pallas] = engine.generate("r", prompt, max_new_tokens=5)
    assert outs[False] == outs[True]


def test_pallas_prefill_engine_matches_xla_path():
    """With use_pallas_prefill=True the engine prefills through the Pallas
    flash-prefill kernel; outputs must match the XLA path, including
    chunked prefill and prefix-cache resumes. (On TPU the flash kernel is
    the auto default — measured 1.9 ms/layer vs XLA's 3.5 at production
    chunks; on CPU auto stays XLA because interpret-mode Pallas is orders
    slower, so this test opts in explicitly.)"""
    prompt = list(range(30, 62))  # 8 pages of 4
    outs = {}
    for use_pallas in (False, True):
        engine = MiniEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_pages=64, max_pages_per_seq=16,
                model_name="tiny", pod_identifier="p",
                use_pallas_decode=use_pallas,
                use_pallas_prefill=use_pallas,
                max_prefill_tokens=16,  # force chunked prefill
            ),
            seed=0,
        )
        first = engine.generate("r", prompt, max_new_tokens=4)
        # resume with a shared prefix: nonzero ctx_lens into the kernel
        resumed = engine.generate("r2", prompt + [7, 8, 9, 10],
                                  max_new_tokens=4)
        outs[use_pallas] = (first, resumed)
    assert outs[False] == outs[True]


def test_pallas_decode_matches_xla_with_attention_sinks():
    """Sink models (StreamingLLM, sink_full_attention) decode through the
    flash kernel: the first-S mask applies in-kernel and matches the XLA
    path — the engine no longer gates Pallas off for this family."""
    prompt = list(range(60, 84))  # 24-token context >> window 8, sinks 4
    outs = {}
    for use_pallas in (False, True):
        engine = MiniEngine(
            EngineConfig(model=LlamaConfig.sink_tiny(), num_pages=64,
                         max_pages_per_seq=16, model_name="sink",
                         pod_identifier="p", use_pallas_decode=use_pallas),
            seed=0,
        )
        outs[use_pallas] = engine.generate("r", prompt, max_new_tokens=6)
    assert outs[False] == outs[True]


def test_pallas_decode_matches_xla_for_mla():
    """Absorbed MLA decodes through the flash kernel as the kv_heads=1
    multi-query case (latent pool passed as both K and V) — the engine no
    longer gates Pallas off for the MLA family."""
    prompt = list(range(40, 64))
    outs = {}
    for use_pallas in (False, True):
        engine = MiniEngine(
            EngineConfig(model=LlamaConfig.deepseek_tiny(), num_pages=64,
                         max_pages_per_seq=16, model_name="ds",
                         pod_identifier="p", use_pallas_decode=use_pallas),
            seed=0,
        )
        outs[use_pallas] = engine.generate("r", prompt, max_new_tokens=6)
    assert outs[False] == outs[True]


def test_mla_latent_pad_is_semantics_invariant():
    """latent_pad (Mosaic lane alignment for the on-chip kernel) must not
    change served tokens: zero key dims score zero and value reads slice
    [:rank], so padded and unpadded engines emit identical streams."""
    base = LlamaConfig.deepseek_tiny()
    padded = LlamaConfig(
        vocab_size=base.vocab_size, hidden_size=base.hidden_size,
        num_layers=base.num_layers, num_heads=base.num_heads,
        num_kv_heads=base.num_kv_heads, head_dim=base.head_dim,
        intermediate_size=base.intermediate_size, page_size=base.page_size,
        kv_lora_rank=base.kv_lora_rank,
        qk_rope_head_dim=base.qk_rope_head_dim,
        latent_pad=104,  # 16+8+104 = 128: the aligned on-chip layout
    )
    prompt = list(range(40, 60))
    outs = {}
    for name, cfg in (("base", base), ("padded", padded)):
        for use_pallas in (False, True):
            engine = MiniEngine(
                EngineConfig(model=cfg, num_pages=64, max_pages_per_seq=16,
                             model_name="ds", pod_identifier="p",
                             use_pallas_decode=use_pallas),
                seed=0,
            )
            outs[name, use_pallas] = engine.generate(
                "r", prompt, max_new_tokens=6)
    assert len({tuple(v) for v in outs.values()}) == 1, outs


def test_pallas_decode_batch_rows_matches_single_row():
    """decode_batch_rows co-schedules batch items per kernel program; the
    served tokens must not change (multi-request batch so the decode
    batch really has multiple rows, with distinct prompts)."""
    prompts = {f"r{i}": list(range(10 + 7 * i, 30 + 7 * i))
               for i in range(4)}
    outs = {}
    for rows in (1, 2, 4):
        engine = MiniEngine(
            EngineConfig(
                model=LlamaConfig.tiny(), num_pages=128,
                max_pages_per_seq=16, model_name="tiny", pod_identifier="p",
                use_pallas_decode=True, decode_batch_rows=rows,
            ),
            seed=0,
        )
        reqs = {rid: engine.enqueue(rid, p, max_new_tokens=6)
                for rid, p in prompts.items()}
        while not all(r.done for r in reqs.values()):
            engine.step()
        outs[rows] = {rid: list(r.output) for rid, r in reqs.items()}
    assert outs[1] == outs[2] == outs[4]
