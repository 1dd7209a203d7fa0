"""JAX's persistent compilation cache, kept at one fixed place, and the
process's count of what it compiles.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the examples) call
`enable()` once, before their first compile. Library code never calls it,
and importing this module changes nothing: the count's listeners are
registered by the first `snapshot()`.

The cache directory is part of what JAX keys a cached program on, so it
must not move between runs: either the directory `$JAX_COMPILATION_CACHE_DIR`
names, which JAX reads by itself, or `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
import threading
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


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_BUILD = (_TRACE, _LOWER, _COMPILE)
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0, "build_s": 0.0}
_open = threading.local()  # build phases open on this thread
_listening = False


def _build_started(event, _value, **_):
    if event in _BUILD:
        _open.n = getattr(_open, "n", 0) + 1


def _build_ended(event, seconds, **_):
    if event not in _BUILD:
        return
    depth = getattr(_open, "n", 0)
    _open.n = max(depth - 1, 0)
    with _lock:
        # a jit traced inside another's trace or lowering reports its own
        # phase; only the outermost one's seconds are counted
        if depth == 1:
            _counts["build_s"] += seconds
        if event == _COMPILE:
            _counts["compiles"] += 1


def _cache_event(event, **_):
    key = {_HIT: "cache_hits", _MISS: "cache_misses"}.get(event)
    if key:
        with _lock:
            _counts[key] += 1


def snapshot() -> dict:
    """The process's compile count so far; callers take deltas.

    `compiles`: backend compiles, persistent-cache reads included;
    `cache_hits`, `cache_misses`: persistent-cache lookups; `build_s`:
    seconds of jaxpr tracing, lowering to MLIR and backend compile or cache
    read. Counting starts at the first call, which registers the listeners
    on JAX's monitoring events."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_scalar_listener(_build_started)
            jax.monitoring.register_event_duration_secs_listener(_build_ended)
            jax.monitoring.register_event_listener(_cache_event)
            _listening = True
        return dict(_counts)
