"""Where XLA's persistent compile cache lives — the one site that decides.

A chip run starts with no compiled code, and compiling the flagship step and
the serving programs is most of a cold run's wall time, so every entry point
(``chip_smoke.py``, ``benchmark/suite/run.py``, the examples) calls
:func:`place` once before its first jit. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads that variable itself; the
  code sets nothing, so whoever runs the program places the cache.
* unset — ``<checkout>/.jax_cache``, derived from this package's own path.
  The path is part of the cache key, so it is never a temp name, pid or
  timestamp: a directory that moves never hits.

Every program is kept, however quickly it compiled: the eager layer issues
hundreds of small programs that JAX's default one-second floor would compile
again on every run (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` overrides).

Call it before anything compiles: JAX decides once per process, at the first
compilation, whether a cache is in use.
"""

from __future__ import annotations

import os

__all__ = ["place", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def place() -> str:
    """Apply the rule above; returns the directory JAX will use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
