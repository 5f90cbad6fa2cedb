"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``chipbench``, ``benchmarks/*``) call
:func:`enable_compile_cache` once before their first compilation. The
directory is part of the cache key, so it must not move between runs:
no temp name, pid or time in the path.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIRNAME", "enable_compile_cache"]

CACHE_DIRNAME = ".jax_cache"  # git-ignored, at the checkout root


def enable_compile_cache() -> str:
    """Returns the cache directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it — touch
    nothing, the cache is placed from outside. Unset: one fixed path
    inside the checkout, the same from every process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
