"""What the three recurrent decode-step kernels (``mamba2_step``,
``gdn_step``, ``kda_step``) owe a batch whose rows do not all decode: a row
is live where its slot is not the spare slot 0, whatever its position, and
only the live rows' states move. Shared by ``test_mamba2.py``,
``test_gated_deltanet.py`` and ``test_kimi_delta_attention.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from llmd_kv_cache_tpu.ops import gated_deltanet as gd

ROWS, SLOTS = 4, 7
# The rows' slots in a pool of SLOTS: live rows first as the engine puts
# them (1, 3 and all of the rows), a spare-slot row between two live rows
# and before the only one (the kernel reads the slots, not a position), and
# a batch in which nothing decodes.
CASES = pytest.mark.parametrize("slots", [
    (3, 0, 0, 0), (3, 5, 1, 0), (4, 2, 5, 6), (3, 0, 5, 0), (0, 0, 6, 0),
    (0, 0, 0, 0)],
    ids=["1_live", "3_live", "all_live", "spare_between", "spare_first",
         "none_live"])


def two_groups_a_row(monkeypatch, state_bytes: int) -> None:
    """Blocks of half a state: a toy row is two grid steps, as a served
    one is, so that a row that decodes nothing has a group to stand at that
    is not the one its neighbour starts with. A step jitted before keeps
    the blocks it was traced with: call the wrapper's ``__wrapped__``."""
    monkeypatch.setattr(gd, "_STEP_BLOCK_BYTES", state_bytes // 2)


def check(step, pool, slots) -> None:
    """``step(pool, slots, kernel) -> (out, pool)`` on layer 1 of ``pool
    [2, SLOTS, ...]`` (random, so nowhere zero): the interpreted kernel
    gives the live rows the XLA form's outputs and states, leaves slot 0,
    every slot no live row names and the other layer bit-equal to what
    they were, and gives the padded rows finite outputs."""
    slots = np.asarray(slots, np.int32)
    live = slots != 0
    out, new = step(jnp.asarray(pool), slots, True)
    ref, want = step(jnp.asarray(pool), slots, False)
    out, new = np.asarray(out), np.asarray(new)
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], atol=2e-5)
    np.testing.assert_allclose(new[1, slots[live]],
                               np.asarray(want)[1, slots[live]], atol=2e-5)
    if live.any():  # the rows did move their states
        assert np.abs(new[1, slots[live]] - pool[1, slots[live]]).max() > 1e-3
    rest = np.setdiff1d(np.arange(pool.shape[1]), slots[live])
    assert 0 in rest
    np.testing.assert_array_equal(new[1, rest].view(np.uint32),
                                  pool[1, rest].view(np.uint32))
    np.testing.assert_array_equal(new[0].view(np.uint32),
                                  pool[0].view(np.uint32))
    assert np.isfinite(out).all()
