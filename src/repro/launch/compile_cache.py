"""Placement of JAX's persistent compilation cache for the entry points.

A compiled program is found again only under the same cache directory, so
the directory is fixed: the one ``JAX_COMPILATION_CACHE_DIR`` names when
it is set (JAX reads that variable itself), else ``<repo>/.jax_cache``
(listed in ``.gitignore``).  Never a path made from a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
