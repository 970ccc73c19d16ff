"""Where JAX keeps its persistent compilation cache for this repo's entry
scripts (``chip_smoke.py``, ``bench/run.py``).

Called by those scripts only, never when the library is imported: a
library must not decide where its users' caches live.

  * ``$JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing
    else is configured.
  * otherwise: ``<repo>/.jax_cache`` (gitignored). The path is fixed — no
    temp names, pids or timestamps — because it is part of the cache's
    key: a directory that moves never hits.
"""
from __future__ import annotations

import os

__all__ = ["REPO_CACHE_DIR", "configure_compile_cache"]

#: src/repro/compile_cache.py -> repo root
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
