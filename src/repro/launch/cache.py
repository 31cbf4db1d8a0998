"""Where the entry points keep JAX's persistent compilation cache.

A cold process on the chip compiles every shape it meets; the persistent
cache lets the next process of the same checkout skip that.  The cache
directory is part of what makes an entry findable, so it is a fixed path:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself), otherwise ``<repo>/.jax_cache`` — never a name built from
a temporary directory, a pid or the time.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``examples/train_als_netflix.py``, ``benchmarks/run.py``); the tests do
not, so the suite never writes the cache.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the repository root: this file is ``<repo>/src/repro/launch/cache.py``
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return os.environ.get(ENV_VAR) or os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this leaves the directory to JAX.
    Every program is kept, however fast it compiled: JAX by default keeps
    only those that took a second or more, and the row-block ``lax.map``
    that ``core.als`` lowers afresh on every call (~0.8 s to compile with
    the Pallas solve on a v5e) would then compile again on every call.
    """
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
