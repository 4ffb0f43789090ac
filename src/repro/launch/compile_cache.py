"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the launchers, the examples) call
``enable_compile_cache()`` once before they compile anything; importing the
package never does, so tests compile without touching a cache.  A
``JAX_COMPILATION_CACHE_DIR`` set in the environment wins and is left to JAX
alone.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed path,
since the directory is part of what makes a later run find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
