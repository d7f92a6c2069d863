"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile the stream steps at deployment size (the
chip smoke run, the benchmark harness) call :func:`use_compile_cache`
once before their first compile, so a second run on the same machine
loads the executables instead of compiling them again.  Library code
and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Cache directory when the environment names none: fixed inside the
#: checkout, so every run from this checkout finds the same entries.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own
    setting and is left to JAX; otherwise the cache is
    :data:`CHECKOUT_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
