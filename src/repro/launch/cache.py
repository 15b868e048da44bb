"""JAX's persistent compilation cache, kept in one fixed place.

Entry points that compile (``chip_smoke.py``, ``benchmarks.run``) call
:func:`enable_compile_cache` before their first jit.  A cache entry is only
found again from the same directory, so the path is fixed: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the variable
itself, and no other location is set here), else ``.jax_cache/`` at the
root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the solver and simulators compile many small
    # ones whose sum is what a warm start saves
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
