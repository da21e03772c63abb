"""Acceptance of drafted tokens, on the device, as a kernel of its own.

A speculative decode step (``models.llama._verify_and_draft``) runs the main
model over a row's last token and the prediction module's draft of the next,
and accepts the draft where it is the main model's own choice. That is a
handful of integer comparisons, and it is a Pallas kernel for one reason: a
device trace keeps an op's NAME and not the scope it was written in, and this
is the one named op between the main model's part of the step and the
module's. Everything the module does takes its tokens from this kernel's
output, so in a trace the ops of a step program that start behind
``mtp_accept`` are the drafter's (``kvbench/metrics/mtp_draft_share.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_ACCEPT = "mtp_accept"


def _accept_kernel(chosen_ref, drafts_ref, live_ref, tokens_ref, count_ref):
    for i in range(drafts_ref.shape[0]):
        first = chosen_ref[0, i]
        tokens_ref[0, i] = first
        tokens_ref[1, i] = chosen_ref[1, i]
        count_ref[i] = live_ref[i] * (
            1 + (first == drafts_ref[i]).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def mtp_accept(chosen: jax.Array, drafts: jax.Array, live: jax.Array, *,
               interpret: bool = False):
    """``chosen [2, rows]`` (the main model's choice at a row's two
    positions), ``drafts [rows]`` (what stood at the second), ``live
    [rows]`` (1, or 0 for a row of padding), all int32. Returns ``(chosen
    as it came, count [rows])``: 2 where a live row's draft was the first
    choice (both tokens count), 1 for any other live row, 0 for padding."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _accept_kernel,
        out_shape=(jax.ShapeDtypeStruct(chosen.shape, jnp.int32),
                   jax.ShapeDtypeStruct(drafts.shape, jnp.int32)),
        in_specs=[smem, smem, smem], out_specs=(smem, smem),
        interpret=interpret,
    )(chosen.astype(jnp.int32), drafts.astype(jnp.int32),
      live.astype(jnp.int32))
