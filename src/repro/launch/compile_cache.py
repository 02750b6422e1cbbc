"""Persistent compilation cache placement for the launchers.

Called first by ``repro.launch.train.main``, ``repro.launch.serve.main``
and ``chip_smoke.py`` — never at import, so tests run without a cache.
A ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins and is left
to JAX; otherwise the cache lives at the fixed path ``<repo>/.jax_cache``
(the path is part of the cache key, so it never depends on a temporary
name, a pid or the time).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
