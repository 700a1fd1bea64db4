"""Where entry points keep JAX's persistent compilation cache.

Every process that reaches the chip (``chip_smoke.py``, ``bench.py``, the
benchmark scripts and demos) calls :func:`place_compile_cache` before its
first compile. The cache key includes the directory, so the path is fixed:
never a temp name, pid or time.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "place_compile_cache"]

# the repository checkout this package was imported from
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what jax itself reads;
    it is used as is and nothing else is set. Otherwise the cache lives
    at ``<checkout>/.jax_cache`` (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
