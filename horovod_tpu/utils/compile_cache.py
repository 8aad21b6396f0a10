"""Where JAX's persistent compilation cache lives for the chip entry
points (``chip_smoke.py``, ``bench.py``), and how to compile past it."""

from __future__ import annotations

import contextlib
import os
import threading

ENV = "JAX_COMPILATION_CACHE_DIR"
# uncached() flips a process-wide switch and flips it back: one at a time
_UNCACHED = threading.RLock()


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


@contextlib.contextmanager
def uncached():
    """Compiles inside neither read nor write JAX's persistent cache:
    the executable that runs is the one the compiler made in this
    process, never one that went through serialization. For programs a
    reload does not bring back whole (``store.artifact_store.
    reload_keeps_layout``). Process-wide while it lasts; whether the
    cache is in use is re-read on both edges."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    with _UNCACHED:
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()
