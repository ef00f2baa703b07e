"""JAX's persistent compilation cache, kept where every run finds it again.

The cache key includes the directory, so the directory must not move from
run to run: ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it
(JAX reads that variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
