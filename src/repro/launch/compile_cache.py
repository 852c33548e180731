"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``benchmarks.run``)
call :func:`use_compile_cache` once before compiling; importing ``repro``
never touches the cache.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and no other
  directory is set in code;
* otherwise: ``<checkout>/.jax_cache/`` — a fixed path, because the path is
  part of what makes a later run find the entries again.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
