"""JAX's persistent compilation cache, at one place per checkout.

Every entry point that compiles (the launch CLIs, ``chip_smoke.py``, the
process backend's children) calls ``enable_compile_cache`` before its
first compile, so repeated runs and sibling processes share compiled
programs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no path is set here; otherwise the cache lives in
``<checkout>/.jax_cache``.  The directory must not move between runs:
its path is part of the cache key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Turn the persistent cache on, for every program however quick its
    compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
