"""Where JAX's persistent compilation cache lives.

The cache is placed from OUTSIDE the program: where the environment
names a directory (``JAX_COMPILATION_CACHE_DIR``), jax reads it itself
and no code sets another.  Only when it is unset does
:func:`ensure_compilation_cache` point jax at ``<checkout>/.jax_cache``
— one fixed path, because the path is part of what makes a later run
find the entries again (a temporary name, a pid or the time in it would
never hit).  ``bench.py`` hands its children the same default through
the environment variable.

Nothing here runs at import time; an entry point calls the helper before
its first compilation.
"""

from __future__ import annotations

import os

__all__ = ["ensure_compilation_cache", "cache_entries"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compilation_cache() -> str:
    """Make sure a persistent compilation cache is on; returns its
    directory.  A directory named by the environment is left alone."""
    import jax

    # cache every program, however quickly it compiled: the default
    # 1 s floor makes "did the second run add entries?" depend on
    # whether a small program happened to compile in 0.9 or 1.1 s
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of entries in the cache directory (0 when it does not
    exist yet — jax creates it on the first write)."""
    return len(os.listdir(path)) if os.path.isdir(path) else 0
