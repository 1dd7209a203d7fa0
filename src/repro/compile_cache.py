"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the examples) call
`enable()` once, before their first compile. Library code never calls it,
and importing this module changes nothing.

The cache directory is part of what JAX keys a cached program on, so it
must not move between runs: either the directory `$JAX_COMPILATION_CACHE_DIR`
names, which JAX reads by itself, or `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When `$JAX_COMPILATION_CACHE_DIR` is set, JAX already uses it and this
    sets nothing. Otherwise the cache goes to `DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
