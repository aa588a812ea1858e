"""Persistent XLA compilation cache for the entry points.

Called by the launchers and ``chip_smoke.py`` once they start, never on
import, so the tests never turn it on.
"""
from __future__ import annotations

import os
import pathlib

# a fixed path: the cache directory is part of what a later run must find
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return where.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already caches there and
    nothing is changed; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
