"""JAX's persistent compilation cache for the entry points.

Called from ``main`` of an entry point (never at import): a compile that a
later run of the same program repeats is then read back instead of redone.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path, so every run of this checkout finds
# what an earlier one stored (no temporary name, pid or time in it)
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads
    it, and no other directory is set here."""
    # JAX's default keeps only compiles of a second or more; the GEMM
    # kernels compile faster than that and would never be kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
