"""JAX's persistent compilation cache, placed for the entry points.

Called by ``chip_smoke.py``, ``python -m repro.launch.serve`` and
``benchmarks/run.py`` before their first compile, never at import.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: a fixed path inside the checkout (git-ignored), so
# every run from this checkout finds what earlier runs compiled.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
