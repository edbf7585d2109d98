"""JAX's persistent compilation cache, kept at one fixed place.

A compiled executable is found again only where it was written, so the
cache lives at a path that does not move between runs: the directory that
``JAX_COMPILATION_CACHE_DIR`` names, or else ``<checkout>/.jax_cache``
(git-ignored). Never a temporary, per-process or time-stamped directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set this
    changes nothing. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
