"""JAX's persistent compilation cache for runs on the chip.

One multiply compiles dozens of small XLA and Mosaic programs, so a
process that starts cold spends much of its time compiling.  The cache
keeps them on disk between processes in the same checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# <repo>/.jax_cache: a fixed path, so a later run in the same checkout
# finds what an earlier one wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache before the first compile and return
    its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, stands as JAX read it;
    otherwise the cache lives in ``<repo>/.jax_cache``.  Compiles of any
    duration are cached: most of a multiply's programs compile in less
    than JAX's default one-second threshold.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
