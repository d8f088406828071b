"""Where the persistent XLA compilation cache lives — decided in one place.

The directory is part of the cache key, so it has to be the same path on
every run: no temp names, pids or timestamps. Whoever runs the program
may place it from outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads
the variable itself); only when it is unset does the code pick a
directory, ``<checkout>/.jax_cache``.

Entry points call this before their first trace. Tests do not: the suite
keeps the cache off (``tests/conftest.py``).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Put the compile cache in force and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
