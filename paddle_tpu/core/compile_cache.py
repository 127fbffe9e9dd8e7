"""Where the persistent XLA compilation cache lives.

Every entry point that compiles (``chip_smoke.py``, ``bench.py`` and
its children, the ``tools/`` smokes) calls ``enable_compile_cache()``
once, before its first compile. The cache's PATH is part of its key,
so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it; nothing here
  (or anywhere else in the repo) points the cache elsewhere;
- unset: ``<checkout>/.jax_compile_cache`` (listed in ``.gitignore``)
  — never a temp, pid or time-stamped name, which would start every
  run cold.
"""
from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_IN_CHECKOUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_compile_cache")


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _IN_CHECKOUT


def enable_compile_cache() -> str:
    """Turn the persistent cache on at ``compile_cache_dir()`` and
    return that path."""
    path = compile_cache_dir()
    if path is _IN_CHECKOUT:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
