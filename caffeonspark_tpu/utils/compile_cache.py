"""The persistent XLA compile cache: one place decides where it lives.

Every entry point that traces (caffe_on_spark.main, mini_cluster,
bench.py) calls `enable_compile_cache()` before its first trace, so a
second run of the same program deserializes instead of compiling.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
directory is set in code — whoever runs the program places the cache.
Where it is unset, the cache lives at ONE fixed path inside the
checkout: the directory is part of what makes a later run find the
entries, so a temporary or per-run directory never hits.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — git-ignored; next to the package, not in it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def placed_by_env() -> bool:
    """True when the environment places the cache (code must not)."""
    return bool(os.environ.get(CACHE_ENV))


def enable_compile_cache() -> str:
    """Make compiled programs persist; returns the directory in use."""
    if placed_by_env():
        return os.environ[CACHE_ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
