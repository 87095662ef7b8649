"""JAX persistent compilation cache location for the launchers.

The cache key includes the directory, so the directory must not move from
one run to the next: a path built from a temp name, a process id or the
time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled executables across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache lives at ``<checkout>/.jax_cache``.
    Call before the first compilation of the process.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
