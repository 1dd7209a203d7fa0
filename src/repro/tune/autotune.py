"""Empirical tile/chunk autotuner (ROADMAP item 2).

On the FIRST use of a kernel at a given problem key, the tuner times every
admissible block configuration (`repro.tune.search` — the auditor-gated
ladder) with a short micro-benchmark and persists the winner in the JSON
store (`repro.tune.cache`). Every later use, in this process or any other,
is a pure lookup: a warm cache performs ZERO timing runs (`timing_runs()`
is the witness the tests assert on).

Tuning is opt-in on every backend: only `REPRO_TUNE=1` (or the test
override) turns it on. Without it `best_blocks` / `best_chunk` return the
fallback (module-default blocks / DEFAULT_CHUNK) at once, reading no cache
file and starting no stopwatch, so what runs is built only from the
repository's own files. With it, resolution is:

  1. in-process memo (dict hit — the per-training-step cost),
  2. persistent cache file,
  3. measure, store, return the winner.

Cache keys carry `(dtype, M, Q, backend, device_kind)` so winners never
leak across machines, dtypes, or problem shapes; N is deliberately absent
(the datapoint axis is streamed — block goodness is N-independent).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.analysis import lockdep
from repro.analysis.pallas_audit import Problem, registry_entry
from repro.tune import cache, search

__all__ = [
    "MEASURE_PROBLEM",
    "best_blocks",
    "best_chunk",
    "cached_interpret_max_n",
    "clear_memo",
    "enabled",
    "measure_blocks",
    "measure_chunks",
    "timing_runs",
]

# Test-visible override: None = env/backend policy, True/False force.
_ENABLED_OVERRIDE: Optional[bool] = None

# One lock guards the whole resolve-measure-store cycle, so two threads
# racing the same cold key serialize and agree on one winner (the second
# thread lands on the memo the first one filled). Routed through lockdep
# (canonical name = its rank in concurrency.LOCK_HIERARCHY) so the serve
# battery's runtime verifier sees autotune -> cache acquisitions.
_LOCK = lockdep.named_lock("repro.tune.autotune._LOCK", kind="rlock")
_MEMO: Dict[Tuple[str, str], Any] = {}  # (cache path, key) -> winner

_TIMING_RUNS = 0

# Representative measurement sizes: N is streamed by every kernel, so a
# modest value keeps first-call tuning cheap without changing the ranking.
MEASURE_PROBLEM = Problem(N=1024, M=256, Q=4, D=2)

_WARMUP = 1
_ITERS = 3


def enabled() -> bool:
    """Is tuning on? Only when $REPRO_TUNE is "1", on any backend; the test
    override wins over that. Disabled keys resolve to the defaults without
    reading the cache file."""
    if _ENABLED_OVERRIDE is not None:
        return bool(_ENABLED_OVERRIDE)
    return os.environ.get("REPRO_TUNE") == "1"


def timing_runs() -> int:
    """Micro-benchmark invocations this process has performed. The warm-
    cache contract is that a second process over the same cache file keeps
    this at zero."""
    return _TIMING_RUNS


def clear_memo() -> None:
    """Drop the in-process memo (NOT the persistent file) — tests use this
    to re-exercise the cache-file path within one process."""
    with _LOCK:
        _MEMO.clear()


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def make_key(kind: str, name: str, dtype, m: int, q: int,
             extra: str = "") -> str:
    """The persistent-store key: what the winner is FOR (kind+name) and
    what it was measured ON (dtype, M, Q, backend, device kind)."""
    import jax.numpy as jnp

    dt = str(jnp.dtype(jnp.float32 if dtype is None else dtype))
    parts = [kind, name, dt, f"M={int(m)}", f"Q={int(q)}",
             jax.default_backend(), _device_kind()]
    if extra:
        parts.append(extra)
    return "|".join(parts)


def _time_fn(fn: Callable[[], Any]) -> float:
    """Median-of-_ITERS wall time of one candidate, after warmup, with
    block_until_ready. Monkeypatchable in tests; `timing_runs` is counted
    by the measure_* callers, not here, so fake timers still register."""
    for _ in range(_WARMUP):
        jax.block_until_ready(fn())
    times = []
    for _ in range(_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def measure_blocks(kernel_name: str, candidates, *,
                   problem: Problem = MEASURE_PROBLEM, dtype=None,
                   ) -> Dict[Tuple[int, int], float]:
    """Wall time per candidate block on the real kernel wrapper. Inputs are
    concrete ones (timing is value-independent); interpret mode follows
    `ops.interpret_mode()` so the CPU smoke lane drives the same code path
    the accelerators tune for real."""
    global _TIMING_RUNS
    import jax.numpy as jnp

    from repro.kernels import ops

    dtype = jnp.float32 if dtype is None else jnp.dtype(dtype)
    fn, build = registry_entry(kernel_name)
    args = [jnp.ones(a.shape, a.dtype) for a in build(problem, dtype)]
    interp = ops.interpret_mode()
    out: Dict[Tuple[int, int], float] = {}
    for blk in candidates:
        blk = (int(blk[0]), int(blk[1]))
        _TIMING_RUNS += 1
        out[blk] = _time_fn(
            functools.partial(fn, *args, interpret=interp, block=blk))
    return out


def measure_chunks(candidates, *, n: int, m: int, q: int, d: int,
                   dtype=None, backend: str = "jnp",
                   bwd_backend: str = "auto") -> Dict[int, float]:
    """Wall time per streaming chunk size through the real
    `gp.stats.streaming_suff_stats` scan (expected statistics under an RBF
    kernel — the paper's hot path)."""
    global _TIMING_RUNS
    import jax.numpy as jnp

    from repro.gp.kernels import RBF
    from repro.gp.stats import ExpectedBatch, streaming_suff_stats

    dtype = jnp.float32 if dtype is None else jnp.dtype(dtype)
    kern = RBF(int(q))
    params = {k: v.astype(dtype) for k, v in kern.init().items()}
    batch = ExpectedBatch(
        mu=jnp.ones((n, q), dtype),
        S=jnp.full((n, q), 0.5, dtype),
        Y=jnp.ones((n, d), dtype),
        Z=jnp.ones((m, q), dtype),
    )
    out: Dict[int, float] = {}
    for c in candidates:
        _TIMING_RUNS += 1
        out[int(c)] = _time_fn(functools.partial(
            streaming_suff_stats, kern, params, batch, backend=backend,
            chunk=int(c), bwd_backend=bwd_backend))
    return out


def _resolve(key: str, fallback, measure: Callable[[], Any]):
    """The shared fallback-when-disabled -> memo -> file -> measure/store
    ladder."""
    if not enabled():
        return fallback
    path = cache.cache_path()
    memo_key = (path, key)
    with _LOCK:
        if memo_key in _MEMO:
            return _MEMO[memo_key]
        hit = cache.lookup(key, path)
        if isinstance(hit, dict) and "winner" in hit:
            win = hit["winner"]
            _MEMO[memo_key] = win
            return win
        value = measure()
        if value is None:
            value = fallback
        else:
            cache.store(key, value if isinstance(value, dict)
                        else {"winner": value}, path)
            value = value["winner"] if isinstance(value, dict) else value
        _MEMO[memo_key] = value
        return value


def best_blocks(kernel_name: str, *, dtype=None, m: int, q: int,
                problem: Optional[Problem] = None) -> Optional[Tuple[int, int]]:
    """The tuned (tile_n, tile_m) for one registered kernel at one problem
    key, or None meaning "use the module defaults". Every `kernels.ops`
    entry point resolves its blocks through here — in both directions."""
    key = make_key("blocks", kernel_name, dtype, m, q)

    def measure():
        prob = problem or dataclasses.replace(
            MEASURE_PROBLEM, M=int(m), Q=int(q))
        cands = search.candidate_blocks(kernel_name, problem=prob,
                                        dtype=dtype)
        if not cands:
            return None
        timings = measure_blocks(kernel_name, cands, problem=prob,
                                 dtype=dtype)
        win = min(timings, key=timings.get)
        return {"winner": list(win), "kernel": kernel_name,
                "timings_s": {f"{a}x{b}": t
                              for (a, b), t in timings.items()}}

    win = _resolve(key, None, measure)
    return None if win is None else (int(win[0]), int(win[1]))


def best_chunk(*, n: int, m: int, q: int, d: int, dtype=None,
               backend: str = "jnp", bwd_backend: str = "auto") -> int:
    """The tuned `lax.scan` chunk for the streaming suff-stats path —
    what `chunk="auto"` resolves to. Falls back to `search.DEFAULT_CHUNK`
    (the historical constant) when tuning is disabled and nothing is
    cached."""
    key = make_key("chunk", "streaming_suff_stats", dtype, m, q,
                   extra=f"backend={backend}")

    def measure():
        n_meas = max(1, min(int(n), 4 * max(search.CHUNK_CANDIDATES)))
        cands = search.candidate_chunks(n_meas)
        if not cands:
            return None
        timings = measure_chunks(cands, n=n_meas, m=m, q=q, d=d,
                                 dtype=dtype, backend=backend,
                                 bwd_backend=bwd_backend)
        win = min(timings, key=timings.get)
        return {"winner": int(win), "kernel": "streaming_suff_stats",
                "timings_s": {str(c): t for c, t in timings.items()}}

    return int(_resolve(key, search.DEFAULT_CHUNK, measure))


def cached_interpret_max_n() -> Optional[int]:
    """Optional tuned override of the off-accelerator interpret-vs-streaming
    dispatch threshold (`ops.fused_interpret_max_n`). Nothing writes this
    key automatically; pin it manually in the store under
    ``interpret_max_n|<backend>`` (docs/tuning.md) after measuring where
    interpret-mode cost crosses the streaming twin on a given host. Read
    only when tuning is `enabled()`."""
    if not enabled():
        return None
    key = "|".join(["interpret_max_n", jax.default_backend()])
    path = cache.cache_path()
    memo_key = (path, key)
    with _LOCK:
        if memo_key in _MEMO:
            return _MEMO[memo_key]
        hit = cache.lookup(key, path)
        if isinstance(hit, dict):
            hit = hit.get("winner")
        value = int(hit) if isinstance(hit, (int, float)) else None
        _MEMO[memo_key] = value
        return value
