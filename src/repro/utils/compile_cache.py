"""Where JAX keeps its persistent compilation cache.

A chip run compiles the executor walks and Pallas kernels afresh unless a
cache survives between runs. The cache key includes the directory, so the
directory must not move: it is either what ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself) or the fixed ``.jax_cache`` at the
root of this checkout, never a temp, pid- or time-derived path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in ``.gitignore``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
