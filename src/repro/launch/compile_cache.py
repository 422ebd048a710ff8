"""Persistent XLA compilation cache for the entry points.

Entry points call :func:`use_compile_cache` from their ``main``, before the
first compile; library modules never set the cache, and importing this
module sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this leaves it alone.  Otherwise the cache goes to
``.jax_cache`` at the root of the checkout: a fixed path, because the path
is part of the cache's key, so a temporary or per-process directory would
never be hit by a later run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = REPO_ROOT) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
