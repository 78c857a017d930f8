"""JAX's persistent compilation cache, placed in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and this
module sets no other directory.  Otherwise the cache lives at one fixed
path inside the checkout (``.jax_cache/``, ignored by git).  The path is
part of what the cache is keyed by, so it never depends on a temporary
name, a process id or the time: a second process finds what the first
one compiled.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Thresholds drop to zero: the aggregation ladder compiles many small
    bucket programs, exactly the population JAX's default minimum compile
    time would leave out.  Idempotent and process-wide."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
