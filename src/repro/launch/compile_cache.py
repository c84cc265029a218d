"""Where the persistent XLA compilation cache lives.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing here
overrides it. Otherwise :func:`enable_compile_cache` points the cache at one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored).
The path is part of the cache key, so it never depends on a temp dir, a pid
or the time: a second run from the same checkout finds the first run's
executables.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax  # here, so that the numpy-only CLIs import without JAX

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
