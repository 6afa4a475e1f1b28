"""JAX's persistent compilation cache for the serving entry points.

A cold engine compiles every prefill bucket and every (chunk, ctx) decode
bucket; with the cache on disk a later process (or a fresh replica in the
same process) loads them instead. The directory is part of what makes a
later run hit, so it is a fixed path, never a temporary one."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile and return its
    directory. When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
    and no other directory is set here; otherwise the cache lives at
    `<checkout>/.jax_cache`."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
