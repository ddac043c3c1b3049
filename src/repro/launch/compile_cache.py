"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
The directory never depends on a temp dir, a pid or the time: a cache whose
path moves from run to run never hits.

The cache key includes the programs' metadata (op names, source lines).
JAX leaves it out by default, so a program that differs from a cached one
only in its names (``jax.named_scope``, a kernel's ``name``) would load the
cached executable, and its device profile would show the other program's
names.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; return that directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
