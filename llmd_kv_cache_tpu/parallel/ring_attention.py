"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context sequence/context parallelism: Q, K, V are sharded along the
sequence dimension across the ``sp`` mesh axis; each device keeps its Q
shard resident and the K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbor exchanges), with flash-style online-softmax
accumulation so the full [S, S] score matrix never materializes. Exact
(not approximate) causal attention with O(S/n) memory per device and
communication fully overlappable with compute by XLA.

Implemented with ``shard_map`` — the collective schedule is explicit here
because the rotation pattern (not a sharding annotation) IS the algorithm;
everything around it stays in the annotate-and-let-XLA-partition style.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _flash_block(q, k, v, mask, m_prev, l_prev, acc_prev, scale):
    """Fold one K/V block into the online-softmax state.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; mask: [Sq, Sk] bool.
    State: m, l [B, H, Sq, 1]; acc [B, H, Sq, D].
    """
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    scores = jnp.where(mask[None, None], scores, _NEG_INF)

    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def _ring_attention_sharded(q, k, v, axis_name):
    """Per-device body under shard_map. q/k/v: [B, S_local, H, D] shards."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, s_local, heads, dim = q.shape
    scale = dim ** -0.5

    q_pos = my_idx * s_local + jnp.arange(s_local)  # global query positions

    m0 = jnp.full((batch, heads, s_local, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((batch, heads, s_local, dim), jnp.float32)

    def step(i, carry):
        m, l, acc, k_blk, v_blk = carry
        # Block i holds the K/V shard originally on device (my_idx - i) mod n.
        src_idx = (my_idx - i) % n
        k_pos = src_idx * s_local + jnp.arange(s_local)
        mask = q_pos[:, None] >= k_pos[None, :]  # causal on global positions

        m, l, acc = _flash_block(q, k_blk, v_blk, mask, m, l, acc, scale)

        # Rotate K/V to the next device (receive from the previous ring
        # neighbor). The final rotation is harmless and keeps the loop
        # uniform; XLA overlaps the permute with the next block's compute.
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, acc, k_blk, v_blk

    m, l, acc, _k, _v = jax.lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))

    out = acc / jnp.maximum(l, 1e-30)  # [B, H, Sq, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Sq, H, D]


def make_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        batch_axis: str | None = None,
                        head_axis: str | None = None):
    """Build a jitted ring-attention fn for ``mesh``.

    Returns ``fn(q, k, v) -> out`` where all tensors are [B, S, H, D] with
    S sharded over ``axis_name``. ``batch_axis``/``head_axis`` name the
    mesh axes sharding B and H so ring attention composes with dp/tp
    (those axes stay data-local; only K/V shards rotate over ``axis_name``).
    S must divide evenly by the axis size.
    """
    spec = P(batch_axis, axis_name, head_axis, None)
    # check_vma off: the fori_loop carry mixes axis-varying K/V blocks with
    # locally-created accumulators, which the varying-axis checker can't
    # unify even though the program is correct.
    sharded = shard_map(
        partial(_ring_attention_sharded, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(sharded)


def ring_attention_reference(q, k, v):
    """Dense causal reference for testing: same math, no sharding."""
    b, s, h, d = q.shape
    scale = d ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
