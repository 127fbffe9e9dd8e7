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

JAX's key leaves a computation's metadata out (it strips locations, and
with them every ``named_scope``), so a cache serves an executable
compiled by a commit whose traces recorded other scopes: the op roles
of ``compiler_engine._trace_ops`` would be missing from a device trace
although the running code writes them. Putting the metadata into the
key (``jax_compilation_cache_include_metadata_in_key``) is no cure: it
holds source paths and line numbers, so no two checkouts would share an
entry, and under a size cap (``JAX_COMPILATION_CACHE_MAX_SIZE``) a
parent and its change evict each other's programs in turn. The name of
a jitted function IS in the key: ``scoped_name`` puts
``SCOPES_VERSION`` into the name of each step the executor compiles,
which forks the key of those programs alone, once.
"""
from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache", "scoped_name",
           "SCOPES_VERSION"]

# bump when what a trace records in scopes changes while the computation
# does not (1: every op inside jax.named_scope("<role>/<op_type>"))
SCOPES_VERSION = 1


def scoped_name(name: str) -> str:
    """The ``__name__`` to give a function before ``jax.jit`` compiles
    it, if its trace goes through ``_trace_ops``."""
    return "%s_s%d" % (name, SCOPES_VERSION)


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
