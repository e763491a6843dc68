"""Persistent XLA compilation cache, switched on by the entry points.

A cold process compiles every program it runs: the fused write program of
a 1 Mi-element chunk alone takes tens of seconds, and the read side adds
one small decode program per distinct plane-group shape.  JAX's persistent
cache keeps the compiled executables on disk, keyed by program and by the
cache directory's path, so a later process on the same machine loads them.

``enable()`` is called by entry points (``chip_smoke.py``,
``benchmarks/run.py``, the ``examples/`` scripts), never while a library
module is imported:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it stands
  and no other directory is set;
* otherwise the cache lives at the fixed path ``<checkout>/.jax_cache``
  (gitignored), never one built from a temp name, a pid or the time.

Every compiled program is cached, however fast it compiled, so the per-shape
decode programs are found again too.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
