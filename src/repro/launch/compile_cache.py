"""JAX's persistent compilation cache, switched on by the entry points.

A cold run on the chip compiles every dispatch; the cache lets the next
process on the same machine load them instead.  Its directory is part
of every entry's key, so it must not move between runs: the directory
is ``JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise the
fixed ``<checkout>/.jax_cache`` (git-ignored).

Call :func:`enable_compile_cache` from a process entry point (a
``main``), before the first compile, never at import time: importing a
module must not change the JAX configuration of the process that
imports it.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path  # JAX reads the variable itself; set nothing else
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
