"""JAX's persistent compilation cache, switched on by the entry points.

A process on the chip compiles every kernel shape it meets; the cache
keeps those programs across processes.  The cache directory is part of
each entry's key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
  the variable itself; no other directory is set here);
* otherwise ``<checkout>/.jax_cache`` — one fixed path, listed in
  ``.gitignore``.

Only entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/``, ``repro.launch``) call :func:`enable_compile_cache`;
importing the library never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed in-checkout cache directory used when the environment names none
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Every compiled program is cached, however fast it
    compiled: a cold chip run compiles hundreds of small kernels."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
