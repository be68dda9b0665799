"""JAX's persistent compilation cache, in one place for every entry point.

A serving process compiles its step programs before it can answer; kept
on disk, a second process on the same checkout skips that. The cache's
directory is part of its key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  overrides it.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points (``chip_smoke.py``, ``launch.serve.main``,
``benchmarks.run.main``) call ``enable_compile_cache()`` before their
first compile.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory: the environment's, else the checkout's."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
