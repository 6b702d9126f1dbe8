"""Where JAX's persistent compilation cache lives.

A cache entry is found again only by a process that looks in the same
directory, so the directory must not move between runs: never a temp
name, a pid or a time.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used
as it is (JAX reads the variable itself and nothing here overrides it).
Otherwise the cache goes to the fixed ``<checkout>/.jax_cache``.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once, before their
first compile.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout: src/repro/launch/compile_cache.py -> root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
