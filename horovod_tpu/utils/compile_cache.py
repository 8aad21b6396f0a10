"""Where JAX's persistent compilation cache lives for the chip entry
points (``chip_smoke.py``, ``bench.py``)."""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def place(checkout: str) -> str:
    """One persistent compile cache for this process and its children;
    call before first backend use. Returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path (the path is part of the
    cache key, so a directory that moves never hits) — exported through
    the environment so child processes inherit it."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    os.environ[ENV] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
