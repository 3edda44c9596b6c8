"""JAX's persistent compilation cache, one rule for every entry point.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable` before anything jits, so a second run on the same machine
loads the sweep kernels instead of compiling them again:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  keeps its cache there; no other directory is set in code.
* Not set: the cache lives at ``<repo>/.jax_cache`` (:data:`DEFAULT_DIR`,
  listed in ``.gitignore``).  The path is fixed — never a temporary
  directory, a pid or the time — because a cache that moves never hits.

Either way every entry is kept: the sweep kernels compile in well under
JAX's default one-second floor, which would otherwise leave most of them
out of the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache`` — resolved from this file (``src/repro/core/``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory.  Call before the first jit: JAX opens the
    cache at its first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
