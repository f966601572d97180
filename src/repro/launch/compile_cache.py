"""Where the persistent JAX compilation cache lives.

Entry points (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`init_compile_cache`
once at start-up; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: left alone — JAX reads it itself,
  and the cache goes there and nowhere else.
* unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed — never built from a temporary name, a pid or the time
  — because it is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["init_compile_cache", "DEFAULT_DIR"]
