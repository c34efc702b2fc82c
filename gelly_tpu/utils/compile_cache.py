"""JAX's persistent compile cache for the entry points.

``chip_smoke.py``, ``bench.py`` and the example CLIs call
:func:`enable_compile_cache` before their first compile; the package
itself never does (the test suite runs without a persistent cache).
"""

from __future__ import annotations

import os

# Fixed in-checkout location (gitignored): the cache key includes the
# path, so it must never be built from a temporary name, a pid or a time.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set in code; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
