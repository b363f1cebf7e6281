"""JAX's persistent compilation cache for the repo's entry points.

Called by ``chip_smoke.py`` and the ``examples/`` / ``benchmarks/`` mains,
never on import. The cache directory is part of the cache key, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
else ``<repo>/.jax_cache`` inside the checkout (git-ignored).
"""
from __future__ import annotations

import os

import jax

from repro.runtime.bench_io import repo_root

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = os.path.join(repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
