"""The one persistent-compile-cache rule of this repository.

Every entry point that compiles for the device (``serving/run_server``,
``chip_smoke.py``, ``bench.py``) calls :func:`enable_compile_cache`
before its first compile. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX keeps its cache there by
  itself, and this module sets no directory in code.
- unset: the cache goes to ``<checkout>/.jax_cache`` — fixed and
  git-ignored. The directory is part of the cache key, so a path built
  from a temporary name, a pid or the time would never hit.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
