"""JAX persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/quickstart.py``, the
benchmark harness) call :func:`enable_compile_cache` once before their
first compile.  Tests never do.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module sets nothing.
* unset: the cache goes to ``<checkout>/.jax_cache``.  The path is fixed
  because it is part of every entry's lookup — a directory named after a
  temp dir, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
