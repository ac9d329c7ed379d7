"""The JAX platform this process serves on, and its compile cache.

Both are asked for when first needed, never at import: the service and
build packages import without touching JAX, so jax-free worker processes
can import them too. Errors propagate — a JAX that cannot start is a
fault to report, not a reason to serve from the host.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout cache path, used when JAX_COMPILATION_CACHE_DIR is
#: unset (the path is part of the cache key, so it must not move)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_cpu() -> bool:
    """True when JAX runs on the host CPU (Pallas kernels then run in
    interpret mode, and the XLA sorted join is the fast path)."""
    import jax
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself; nothing
    else is set), else :data:`DEFAULT_CACHE_DIR`. Every program is cached,
    however fast it compiled, so a second process recompiles nothing.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
