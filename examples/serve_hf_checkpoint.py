#!/usr/bin/env python
"""Serve a local HuggingFace checkpoint through the paged engine.

The user-facing entry for real weights: point it at a checkpoint
directory (Llama/Mistral/Mixtral/Qwen2/Qwen3/Qwen3-MoE/DeepSeek — every
family logits-parity-pinned to transformers in tests/test_hf_loader.py),
it converts to the TPU-native parameter tree, admits the prompt through
the content-addressed prefix cache, and streams greedy tokens from the
continuous-batching scheduler.

Usage:
  PYTHONPATH=. python examples/serve_hf_checkpoint.py /path/to/ckpt \\
      --prompt "The capital of France is" --max-new-tokens 32

With no checkpoint argument, the demo builds a tiny random-init Qwen3 in
a temp dir first (no downloads; zero-egress-safe) and serves that — the
full disk path (save_pretrained → safetensors → conversion) still runs.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import tempfile


def _demo_checkpoint(tmp: str) -> str:
    """Build a tiny random-init Qwen3 checkpoint on disk (no network)."""
    import torch
    from transformers import AutoTokenizer  # noqa: F401 (env check)
    from transformers import Qwen3Config, Qwen3ForCausalLM

    torch.manual_seed(0)
    cfg = Qwen3Config(
        vocab_size=4096, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, tie_word_embeddings=True)
    Qwen3ForCausalLM(cfg).save_pretrained(tmp)
    return tmp


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("checkpoint", nargs="?", default=None,
                    help="HF checkpoint directory (local; no downloads)")
    ap.add_argument("--prompt", default="The capital of France is")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=2048)
    args = ap.parse_args()

    from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
    from llmd_kv_cache_tpu.models.hf_loader import load_hf_checkpoint
    from llmd_kv_cache_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    demo_ids = None
    cleanup = contextlib.ExitStack()
    if args.checkpoint is None:
        tmpdir = cleanup.enter_context(
            tempfile.TemporaryDirectory(prefix="hf-demo-"))
        print("no checkpoint given: building a tiny random-init Qwen3 demo",
              file=sys.stderr)
        args.checkpoint = _demo_checkpoint(tmpdir)
        demo_ids = list(range(30, 46))  # random-init: tokenizer-free demo

    print(f"converting {args.checkpoint} …", file=sys.stderr)
    with cleanup:
        cfg, params = load_hf_checkpoint(args.checkpoint,
                                         page_size=args.page_size)
    import jax

    # Tied checkpoints alias lm_head to the embedding — count it once.
    n_params = sum(p.size for p in jax.tree.leaves(params))
    if params["lm_head"].shape == params["embed"].T.shape and bool(
            (params["lm_head"] == params["embed"].T).all()):
        n_params -= params["lm_head"].size
    print(f"model: {cfg.num_layers}L/{cfg.hidden_size}h "
          f"{n_params / 1e6:.1f}M params, families: "
          f"mla={cfg.is_mla} moe={cfg.num_experts > 0} "
          f"qk_norm={cfg.qk_norm} window={cfg.sliding_window}",
          file=sys.stderr)

    if demo_ids is not None:
        prompt_ids = demo_ids
        decode = lambda ids: str(ids)  # noqa: E731
    else:
        from transformers import AutoTokenizer

        tok = AutoTokenizer.from_pretrained(args.checkpoint)
        prompt_ids = tok(args.prompt)["input_ids"]
        decode = tok.decode

    max_pages = (len(prompt_ids) + args.max_new_tokens
                 ) // cfg.page_size + 3
    eng = MiniEngine(
        EngineConfig(model=cfg, num_pages=args.num_pages,
                     max_pages_per_seq=max_pages, model_name="hf-serve",
                     pod_identifier="pod-0"),
        params=params)
    req = eng.enqueue("r0", prompt_ids, max_new_tokens=args.max_new_tokens)
    while not req.done:
        eng.step()
    print(decode(list(req.output)))


if __name__ == "__main__":
    main()
