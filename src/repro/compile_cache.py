"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py`` and the benchmarks) call
:func:`enable_compile_cache` once, before their first compile; nothing
calls it at import, so the test suite runs without a persistent cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
stays in charge: no other directory is set here.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache``
(gitignored).  The path is fixed, never a temporary name, because a
cache that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # The TCAM kernels compile in well under JAX's default one-second
    # threshold; cache every program so a warm run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
