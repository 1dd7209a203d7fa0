"""Compilations and persistent-cache reads, counted from JAX's monitoring events.

Each backend-compile event times one call of JAX's compile-or-read-the-cache
step. A cache hit is signalled inside that call, before the event, so each
duration is booked either as a cache read (`cache_read_s`) or as a compile
(`compiled_s`: the programs the cache did not hold, written to it or too
small to be written).
"""
from __future__ import annotations

import collections

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileLog:
    def __init__(self):
        self.seconds = 0.0
        self.hit_seconds = 0.0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.names = collections.Counter()
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1
            self.names[str(fun_name)] += 1
            if self._hit:
                self.hit_seconds += duration
            self._hit = False
        elif event == _TRACE_EVENT:
            self.trace_seconds += duration
        elif event == _LOWER_EVENT:
            self.lower_seconds += duration

    def _event(self, event, **_):
        if event == _HIT_EVENT:
            self.hits += 1
            self._hit = True
        elif event == _MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"names": dict(self.names), "backend_compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses,
                "backend_compile_s": self.seconds,
                "cache_read_s": self.hit_seconds,
                "compiled_s": self.seconds - self.hit_seconds,
                "trace_s": self.trace_seconds,
                "lower_s": self.lower_seconds}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        out = {k: after[k] - before[k] for k in after if k != "names"}
        names = collections.Counter(after["names"])
        names.subtract(before["names"])
        out["names"] = {k: v for k, v in names.most_common(8) if v > 0}
        return out
