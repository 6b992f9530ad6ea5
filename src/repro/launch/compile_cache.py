"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``examples/serve_lm.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; importing this module does
nothing.  The cache key includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself and this
module sets nothing), otherwise ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Every
    compiled program is cached (no minimum compile time), so a second run
    of the same entry point on the same machine compiles nothing."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env or str(REPO_CACHE_DIR)
