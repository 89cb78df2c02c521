"""Where JAX keeps compiled programs between runs.

Every entry point that compiles (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) calls ``enable_compile_cache`` before its first
compile, so a cold run on the chip does not recompile every kernel and
segment program that an earlier run already built.
"""

from __future__ import annotations

import os

import jax

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed,
#: inside the checkout and git-ignored. The path is part of the cache key,
#: so it must not move from run to run.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
    no other directory; otherwise the cache lives at ``REPO_CACHE_DIR``.
    Every program is cached, however fast it compiled: the Pallas kernels
    take well under JAX's default one-second threshold.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
