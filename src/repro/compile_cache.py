"""Where JAX keeps its persistent compilation cache.

Entry-point scripts (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable_compile_cache` once before their first compile; library code
never does, so importing :mod:`repro` leaves JAX's configuration alone.
"""

from __future__ import annotations

import os

import jax

# A fixed directory inside the checkout: the cache key includes the path,
# so a temp-, pid- or time-derived name would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
