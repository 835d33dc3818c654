"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
mains) call ``enable_compile_cache()`` before their first compile, so a
second run of the same program loads its executables instead of
compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at import and keeps the
  cache there; nothing is set in code.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed — never a temporary name, a pid or the time — because
  it is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
