"""Persistent compilation cache location for this repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) keep the cache at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
directory is part of the cache key, so a path built from a tmpdir, a pid
or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at ``REPO_CACHE`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    directory this call set (None when the environment decides)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return REPO_CACHE
