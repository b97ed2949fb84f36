"""Placement of JAX's persistent compilation cache for the entry points.

A compiled program is found again only under the same cache directory, so
the directory is fixed: the one ``JAX_COMPILATION_CACHE_DIR`` names when
it is set (JAX reads that variable itself), else ``<repo>/.jax_cache``
(listed in ``.gitignore``).  Never a path made from a temp name, a pid or
the time.

The key includes each op's name (its named-scope path): a compiled
program carries that metadata into the profiler's trace, and without it
in the key a program that differs from a cached one only in its scopes
would load the cached executable and show the stale scopes there.
Source locations are left out of the programs, so that the key does not
change with the checkout's path or a line moved in a file.  This holds
for the whole process: every caller (the benchmark, ``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``) compiles programs whose HLO and
profiler metadata carry op names but no file or line.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on, keyed with op names and
    without source locations (process-wide); returns its directory."""
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
