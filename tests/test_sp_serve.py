"""Sequence-parallel serving prefill: an ``sp`` mesh axis splits a prefill
chunk's per-token compute across devices.

Long-context serving analog of the training-side ring attention: the
engine places each chunk's tokens sharded on the sequence dim and XLA
propagates — projections/MLP/attention-q run on seq shards, with the
collectives (cache-scatter all-gathers, logits reduce) derived from the
shardings. Verified two ways: token identity vs the single-device engine,
and the compiled HLO predominantly carrying seq-sharded intermediates
(i.e. the FLOPs really split — not an all-gather-then-replicate program).
The engine dispatches the forward's step form: the chunk arrives packed in
one replicated array, and the program holds the unpacked tokens to the
sequence sharding (``token_sharding``), which the compute follows.

Runs on the virtual 8-device CPU mesh (conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the 8-device virtual CPU mesh (tests/conftest.py)",
)

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
from llmd_kv_cache_tpu.parallel.mesh import make_mesh


def _engine(cfg, params, mesh=None, **kw):
    return MiniEngine(
        EngineConfig(model=cfg, num_pages=64, max_pages_per_seq=16,
                     model_name="sp-test", pod_identifier="p", **kw),
        params=params, mesh=mesh,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def test_sp_prefill_matches_single_device(setup):
    cfg, params = setup
    prompt = np.random.default_rng(0).integers(1, 250, 48).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=6)
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=6)
    assert out == ref


def test_sp_with_tp_axis(setup):
    """sp composes with tp: Megatron-sharded params + seq-sharded chunk
    tokens on one mesh."""
    cfg, params = setup
    prompt = np.random.default_rng(1).integers(1, 250, 32).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=6)
    mesh = make_mesh({"tp": 2, "sp": 2}, jax.devices()[:4])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=6)
    assert out == ref


def test_sp_chunked_prefill_and_resume(setup):
    """Chunked prefill (multiple sp-sharded chunks) + prefix-cache resume
    with nonzero ctx_lens."""
    cfg, params = setup
    prompt = np.random.default_rng(2).integers(1, 250, 40).tolist()
    mesh = make_mesh({"sp": 2}, jax.devices()[:2])
    ref_eng = _engine(cfg, params, max_prefill_tokens=16)
    sp_eng = _engine(cfg, params, mesh=mesh, max_prefill_tokens=16)
    assert sp_eng.generate("r", prompt, max_new_tokens=4) == \
        ref_eng.generate("r", prompt, max_new_tokens=4)
    ext = prompt + [7, 8, 9]
    assert sp_eng.generate("r2", ext, max_new_tokens=4) == \
        ref_eng.generate("r2", ext, max_new_tokens=4)


def test_sp_hybrid_engine():
    """The hybrid (two-pool) prefill path places sp-sharded tokens too."""
    cfg = LlamaConfig.gemma_tiny()
    params = init_params(jax.random.PRNGKey(9), cfg)
    prompt = np.random.default_rng(3).integers(1, 250, 32).tolist()
    ref = _engine(cfg, params).generate("r", prompt, max_new_tokens=4)
    mesh = make_mesh({"sp": 2}, jax.devices()[:2])
    out = _engine(cfg, params, mesh=mesh).generate("r", prompt,
                                                   max_new_tokens=4)
    assert out == ref


def test_sp_compute_actually_shards(setup):
    """The compiled prefill program must carry predominantly seq-sharded
    intermediates — proof the FLOPs split instead of an early all-gather
    replicating the whole chunk."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from llmd_kv_cache_tpu.models.llama import forward, init_kv_cache

    cfg, params = setup
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    tokens = jnp.asarray(np.arange(60, 124)[None, :], jnp.int32)  # [1, 64]
    tok_sp = jax.device_put(tokens, NamedSharding(mesh, P(None, "sp")))
    k, v = init_kv_cache(cfg, 64)
    table = jnp.asarray(1 + np.arange(16)[None, :], jnp.int32)
    lowered = jax.jit(
        forward.__wrapped__, static_argnames=("cfg", "last_only")
    ).lower(params, cfg, tok_sp, k, v, table,
            jnp.asarray([0], jnp.int32), jnp.asarray([64], jnp.int32),
            last_only=True)
    txt = lowered.compile().as_text()
    sharded = txt.count("[1,16,")   # 64/4 = 16-row seq shards
    full = txt.count("[1,64,")
    assert sharded > 2 * full, (sharded, full)
    assert re.search("all-gather", txt), "expected scatter all-gathers"


def test_sp_step_form_shards_from_one_packed_array(setup):
    """What the engine dispatches: the packed inputs arrive replicated in
    one transfer, ``token_sharding`` holds the unpacked tokens to the seq
    sharding, and the program's intermediates are predominantly seq shards
    as with tokens placed sharded by hand."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llmd_kv_cache_tpu.models.llama import (init_kv_cache, pack_inputs,
                                                step_forward)

    cfg, params = setup
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    packed, shapes = pack_inputs((
        np.arange(60, 124)[None, :], 1 + np.arange(16)[None, :], [0], [64]))
    k, v = init_kv_cache(cfg, 64)

    txt = step_forward.lower(
        params, cfg, jnp.asarray(packed), (k, v), shapes=shapes,
        last_only=True, keep_row=True,
        token_sharding=NamedSharding(mesh, P(None, "sp"))).compile().as_text()
    sharded, full = txt.count("[1,16,"), txt.count("[1,64,")
    assert sharded > 2 * full, (sharded, full)
    assert "all-gather" in txt
