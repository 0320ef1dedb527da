"""Persistent compilation cache for the launchers and ``chip_smoke.py``.

Called once at start-up by each entry point, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing is
configured here. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path (the path is part of the cache key, so a directory named after
a pid, a time or a temporary name would never hit), ignored by git.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the cache directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
