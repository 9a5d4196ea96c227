"""JAX's persistent compilation cache, in one place.

Entry points call :func:`enable_compile_cache` from ``main()`` — never at
import, so tests and library users keep JAX's defaults.  The cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, or else in ``.jax_cache/`` at the
root of the checkout (git ignores it).  The path is part of the cache key,
so it is fixed rather than temporary.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
