"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call ``enable_compile_cache()`` once at start-up; nothing turns the cache on
at import time, so library users and the tests keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at a fixed path inside the
checkout: the directory is part of each entry's key, so a path that moved
between runs (a temporary directory, a pid, a time) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
