"""Where JAX keeps its persistent compilation cache for this program.

A compiled program is found again only under the same cache path, so the
path is fixed: the directory named by ``JAX_COMPILATION_CACHE_DIR`` where
the environment sets one (JAX reads that variable itself), otherwise
``.jax_cache`` at the root of the checkout.  It is never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; call before the first
    compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
