"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start-up, before their
first compile; importing a module never does.  The cache directory is part
of every entry's key, so it has to be a fixed place:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
  itself; nothing else is set then);
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable that took a noticeable compile, however small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
