"""Test configuration.

Tests run on the CPU backend with a virtual 8-device mesh, so multi-chip
sharding logic is exercised without hardware and Pallas kernels run in the
interpreter. The chip is reached through ``kvbench/run.py`` and
``chip_smoke.py``, never through pytest (``tests/test_kvbench_rehearsal.py``
walks the benchmark's cells at toy widths on the CPU). The environment must
be set before the first ``jax`` import, hence module level.
"""

import os

# Hard-set (not setdefault): a machine with a chip defaults JAX to it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound cumulative XLA state across the ~900-test single process.

    The CPU XLA compiler segfaulted twice deep into full-suite runs
    (92%/86%, inside backend_compile during a tp-serve compilation) while
    every implicated module passes in isolation — classic accumulated
    compiler/cache state. Dropping jit caches at module boundaries keeps
    per-module behavior identical (modules build their own engines) while
    capping what the process drags into its 800th compilation.
    """
    yield
    import jax

    jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _reset_lockdep_between_modules():
    """Clear the lockdep order graph at module boundaries.

    Under ``KVTPU_LOCKDEP=1`` the witness accumulates lock-order edges
    process-wide. Edges observed by one module's wiring are real for
    *that* wiring, but two modules that assemble components differently
    can legitimately acquire the same lock roles in different orders
    without either assembly being deadlock-prone. Module scope keeps the
    witness sensitive within a module (where one wiring holds) and
    unopinionated across them. No-op when the witness is disabled.
    """
    yield
    from llmd_kv_cache_tpu.utils import lockdep

    if lockdep.enabled():
        lockdep.reset()
