"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, the serving
benchmarks) call :func:`configure_compile_cache` once at start, before
the first compile.  Library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache is keyed by what is
# compiled, and a directory that moved between runs would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is configured here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
