"""Where JAX keeps its persistent compilation cache.

A cold run compiles every program; a warm one reads them back.  The
cache key includes the directory, so the directory must not move between
runs.  :func:`use_compile_cache` is called by every entry point before its
first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
  in code;
* unset — the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``), the same path on every run.
"""
from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
