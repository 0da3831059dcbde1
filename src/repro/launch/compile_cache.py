"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call ``use_compile_cache()`` once at start-up,
never at import time. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this helper changes nothing. Otherwise the cache goes
to ``.jax_cache/`` at the repository root: a fixed path, because the path
is part of what the cache is keyed on, so a directory that moved between
runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
