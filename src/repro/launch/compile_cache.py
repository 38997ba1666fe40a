"""Persistent XLA compilation cache for the entry points.

One rule, kept in one place: when ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this module sets nothing; otherwise the cache
lives at one fixed directory inside the checkout (``<repo>/.jax_cache``,
listed in ``.gitignore``).  The path is part of every cache key, so it
must not move between runs: no temp, pid or time in it.

Called from ``main()`` of ``launch/serve.py`` and ``launch/train.py`` and
from ``chip_smoke.py`` — never at import, so importing a module leaves
the process's JAX configuration alone.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
