"""Where compiled programs persist between processes.

A cold process recompiles every kernel and solver loop; JAX's persistent
compilation cache keeps them on disk. The cache key includes the directory,
so the directory must not move between runs: it is either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or the
fixed ``.jax_cache`` directory at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set. Call before the
    first compile.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
