"""Persistent compilation cache for the entry points (``chip_smoke.py``,
``python -m repro.launch.serve``). Library code never calls this: importing
``repro`` leaves JAX's configuration alone."""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compilation_cache"]

REPO = pathlib.Path(__file__).resolve().parents[3]


def enable_compilation_cache() -> str:
    """Keep compiled programs across processes. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it: set nothing.
    Otherwise use ``<repo>/.jax_cache``, a fixed path, since the directory
    is part of what a cache hit has to match. Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
