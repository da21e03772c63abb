"""Where the persistent XLA compile cache lives.

Every entry point that compiles (``chip_smoke.py``, ``kvbench``, the
example servers) calls :func:`enable_compile_cache` before its first
compile, so a second process or a second run finds the first one's
programs. The directory is part of JAX's cache key, hence a fixed path:
never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache, listed in .gitignore.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the compile cache; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    no path is set in code. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
