"""The persistent compile cache of the command-line entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
this module sets no other.  Otherwise the cache lives at one fixed path in
the checkout, ``.jax_cache/`` (gitignored): a directory whose name changes
from run to run (a temp name, a pid, a time) would never be hit again.

``chip_smoke.py`` and the ``examples/`` scripts call :func:`use_compile_cache`
under ``__main__``; library imports and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
