"""JAX's persistent compilation cache, as every entry point turns it on.

A cold compile of the 24-layer macro window is a large share of a short
run, so ``chip_smoke.py``, ``launch/serve.py`` and ``bench/cell.py``
keep compiled programs on disk. The directory is ``JAX_COMPILATION_CACHE_DIR``
when that is set, and otherwise the fixed ``<repo>/.jax_cache`` (ignored by
git): never a temporary, per-process or per-run path, since a directory that
moves is never hit again. Tests do not call this and stay uncached.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
