"""Persistent compilation cache for the repo's entry points.

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()          # before the first jit compile

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set this helper
leaves it alone and configures no other cache.  Otherwise the cache lives at
the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path is
part of every cache key, so it never comes from a temporary name, a PID or
the clock — a moved directory never hits.

Called by ``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/kscale_case.py`` and the examples.  Library code and tests never
call it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
