"""Where compiled programs persist between runs.

Entry points (``chip_smoke.py``, ``examples/serve_recommender.py``,
``python -m repro.launch.train``) call ``use_compile_cache()`` once, before
their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the directory is part of every entry's key, so a cache
# that moved between runs would never hit.
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache is ``.jax_cache/`` at the
    repository root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
