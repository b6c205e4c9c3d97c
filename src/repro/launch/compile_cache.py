"""Where the entry points keep JAX's persistent compilation cache.

``use_compile_cache()`` is called once, before the first compile, by
``bmf_train``, ``bmf_serve`` and ``chip_smoke.py``; tests leave the cache
alone.

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
    else is set in code.
  - unset: the cache goes to ``<checkout>/.jax_cache``, resolved from this
    file's location.  The directory is part of the cache key, so it is
    fixed by the checkout and never derived from a temp dir, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
