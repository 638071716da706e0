"""Persistent XLA compilation cache, placed once per entry point.

Every program of a run compiles from cold unless JAX finds it in a
persistent cache.  ``JAX_COMPILATION_CACHE_DIR``, when set, is where the
cache lives: JAX reads it at import, and this module changes nothing.
Otherwise the cache sits at a fixed ``<checkout>/.jax_cache`` (listed in
``.gitignore``).  The path is part of what a cache entry is found by, so it
is never built from a temporary name, a process id or the time.

Entry points call :func:`enable_compile_cache` from ``main()``; importing a
module never touches the cache, and tests never enable it.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
