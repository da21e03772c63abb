"""The step programs of ``models/llama.py`` at a tiny size, one table of
cases for the tests that hold every program to the same rule: the
structure test in ``test_model.py`` (no program moves a layer of a pool),
the equality test in ``test_kv_pages.py`` (writing rows into the stack in
place gives what taking the layer out and putting it back gave) and the
step-form tests in ``test_model.py`` (a forward's step form, which the
engine dispatches, gives the tokens its logits give).

A pool layer here is ``[11, 2, 4, 16]`` (the hybrid SWA group's
``[7, 2, 4, 16]``): no activation or weight of these
configurations has that shape, so an op of that shape is an op on a layer.
"""

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from llmd_kv_cache_tpu.models import llama

PAGES, SWA_PAGES = 11, 7
TABLE = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
SWA_TABLE = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
TOKENS = np.asarray([[[5, 9, 2, 7], [3, 8, 1, 6]],
                      [[4, 4, 9, 1], [7, 2, 2, 5]]], np.int32)


def _i32(*xs):
    return np.asarray(xs, np.int32)


def _chunk(step):
    """Two padded chunks per row: 3 and 2 new tokens, then 4 and 1 more."""
    ctx, new = ((_i32(0, 0), _i32(3, 2)), (_i32(3, 2), _i32(4, 1)))[step]
    return TOKENS[step], ctx, new


def _decode(step):
    """Two decode rows, then one live row and one row of padding."""
    ctx, new = ((_i32(3, 5), _i32(1, 1)), (_i32(4, 6), _i32(1, 0)))[step]
    return TOKENS[step][:, :1], ctx, new


def _padded(rows):
    def args(params, cfg, pools, step):
        tokens, ctx, new = rows(step)
        tables = (TABLE, SWA_TABLE)[:len(pools) // 2]
        return (params, cfg, tokens, *pools, *tables, ctx, new)
    return args


def _ragged(params, cfg, pools, step):
    row_starts, ctx = ((_i32(0, 3, 5), _i32(0, 0)),
                       (_i32(0, 4, 5), _i32(3, 2)))[step]
    return (params, cfg, TOKENS[step].reshape(1, 8), *pools, TABLE,
            row_starts, ctx)


class Program(NamedTuple):
    fn: Callable  # the jitted program; returns (out, *pools)
    cfg: llama.LlamaConfig
    args: Callable  # (params, cfg, pools, step) -> positional arguments
    static: dict  # its static keyword arguments
    pallas: bool  # attention is a kernel: ``interpret=True`` to run here
    # Its step form (``llama.step_program``): (tokens, row, pools) from
    # (params, cfg, packed, pools, shapes=, **static).
    step: Callable
    # What the engine adds to ``static`` when it dispatches the step form:
    # a chunk's logits are its last position's.
    chunk: bool = False


_GQA = llama.LlamaConfig.tiny()
_HYBRID = llama.LlamaConfig.gemma_tiny()
_MLA = llama.LlamaConfig.deepseek_tiny()
PROGRAMS = {
    "forward": Program(llama.forward, _GQA, _padded(_chunk), {}, False,
                       llama.step_forward, True),
    "forward_mla": Program(llama.forward, _MLA, _padded(_chunk), {}, False,
                           llama.step_forward, True),
    "forward_decode": Program(llama.forward, _GQA, _padded(_decode), {},
                              False, llama.step_forward),
    "forward_hybrid": Program(
        llama.forward_hybrid, _HYBRID, _padded(_chunk), {}, False,
        llama.step_forward_hybrid, True),
    "forward_hybrid_decode": Program(
        llama.forward_hybrid, _HYBRID, _padded(_decode), {}, False,
        llama.step_forward_hybrid),
    "forward_decode_pallas": Program(
        llama.forward_decode_pallas, _GQA, _padded(_decode), {}, True,
        llama.step_decode_pallas),
    "forward_decode_pallas_mla": Program(
        llama.forward_decode_pallas, _MLA, _padded(_decode), {}, True,
        llama.step_decode_pallas),
    "forward_prefill_pallas": Program(
        llama.forward_prefill_pallas, _GQA, _padded(_chunk), {}, True,
        llama.step_prefill_pallas, True),
    "forward_ragged": Program(llama.forward_ragged, _GQA, _ragged, {}, True,
                              llama.step_ragged),
}


# A model that drafts (``LlamaConfig.num_nextn_predict_layers``) is stepped
# by programs of its own, ``llama.DRAFTING_PROGRAMS``: (kernels, chunk) ->
# the speculative form of a forward. Here with the module's latents as one
# more layer of the pool ([11, 1, 4, 24 + 8 lanes of pad]).
DRAFT_CFG = llama.LlamaConfig(
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=4, head_dim=16, intermediate_size=128, page_size=4,
    kv_lora_rank=16, qk_rope_head_dim=8, latent_pad=8, q_lora_rank=24,
    post_norms=True, num_nextn_predict_layers=1)
DRAFTING = {("speculative_" + llama.DRAFTING_PROGRAMS[key].__name__
             + ("_chunk" if key[1] else "_decode")): key
            for key in llama.DRAFTING_PROGRAMS}


def drafting_inputs(chunk: bool, step: int = 0):
    """``(packed, shapes)`` of one speculative program's per-step arrays: a
    prefill chunk of row 0 (the token after it: 9, then the prompt's end),
    or two decode rows, the second of padding at ``step`` 1."""
    if chunk:
        tokens, ctx, new = _chunk(step)
        return llama.pack_inputs((tokens[:1], TABLE[:1], ctx[:1], new[:1],
                                  _i32(9 if step == 0 else -1)))
    tokens, ctx, new = _decode(step)
    return llama.pack_inputs((tokens, TABLE, ctx, new, _i32(5, 3),
                              _i32(-1, -1)))


def step_inputs(args, pools):
    """The per-step arrays among a program's positional arguments, in the
    order its step form packs them: the tokens, then what follows the
    pools."""
    return (args[2], *args[3 + len(pools):])


def init_pools(cfg, dtype=None):
    """The program's pools, filled: attention reads what a step did not
    write, so a write that lands elsewhere shows in the logits too."""
    if cfg.is_hybrid:
        pools = llama.init_kv_cache_hybrid(cfg, PAGES, SWA_PAGES, dtype)
    else:
        pools = llama.init_kv_cache(cfg, PAGES, dtype)
    keys = jax.random.split(jax.random.PRNGKey(7), len(pools))
    return tuple(jax.random.normal(k, p.shape, jnp.float32).astype(p.dtype)
                 for k, p in zip(keys, pools))


def step_form_jaxpr(name: str, prev: bool = False) -> str:
    """The step form of ``PROGRAMS[name]`` as the engine dispatches it, as
    its jaxpr's text (kernels included, as the equations of their bodies),
    without what differs between two checkouts of the same program: source
    positions and objects' addresses. ``prev``: a padded decode form as
    ``MiniEngine._launch_decode`` calls it, taking the last program's
    tokens on the device. What a model's programs ARE: a PR that means to
    leave them alone leaves this text alone
    (``test_model.py::TestStepFormsStayWhatTheyWere``)."""
    import re

    prog = PROGRAMS[name]
    params = llama.init_params(jax.random.PRNGKey(0), prog.cfg)
    pools = init_pools(prog.cfg)
    arrays = list(step_inputs(prog.args(params, prog.cfg, pools, 0), pools))
    static = dict(prog.static)
    if prog.chunk:
        static.update(last_only=True, keep_row=True)
    if prev:
        rows = arrays[0].shape[0]
        arrays.append(np.full((rows,), -1, np.int32))
        static["prev"] = jnp.zeros((rows,), jnp.int32)
    packed, shapes = llama.pack_inputs(arrays)
    text = str(prog.step.trace(
        params, prog.cfg, packed, pools, shapes=shapes, **static).jaxpr)
    text = re.sub(r" at (0x[0-9a-f]+|[^\s:]+:\d+)", "", text)
    return re.sub(r"\S*/[\w/.-]+\.py(:\d+)*", "", text)
