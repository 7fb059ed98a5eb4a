"""Where the repo's entry-point scripts keep JAX's persistent compile cache.

Compiling the serving step or a QNN plan for the chip takes seconds to
minutes; the persistent cache lets a later process (or a later run of the
same script) skip it.  The cache key includes the directory, so the
default is one fixed path inside the checkout, never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache directory: ``.jax_cache/`` at the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is overridden; otherwise the cache goes to ``DEFAULT_DIR``.
    Call once from a script's ``main`` before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
