"""JAX persistent compilation cache at a fixed place.

The entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set.  Otherwise the cache lives at ``<repo>/.jax_cache`` (git-ignored):
a fixed path, because the directory is part of what makes a later run hit.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["REPO_ROOT", "CACHE_DIR", "enable_compile_cache"]

#: Root of the checkout this package runs from (``src/repro/..``).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
