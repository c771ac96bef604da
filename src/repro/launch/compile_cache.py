"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` once, before the first compile
(``launch/serve.py`` and ``chip_smoke.py`` do). Importing this module
changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and the cache
  stays there; no other path is set in code.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``). The path is fixed: it is part of the cache
  key, so a path built from a temporary name, a PID or the time would
  never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
