"""repro.tune: empirical tile/chunk autotuner with a persistent cache.

The hand-picked Pallas tile constants and the magic streaming chunk become
MEASURED decisions: on first use of a kernel at a problem key the tuner
times every auditor-admissible block configuration and persists the winner
(`~/.cache/repro/tune.json`, override with $REPRO_TUNE_CACHE); every later
process is a pure lookup with zero timing runs. `kernels.ops` consults
`best_blocks()` for all seven registered kernels, and `chunk="auto"`
anywhere a chunk is accepted resolves through `best_chunk()`.

Tuning is opt-in on every backend: only REPRO_TUNE=1 turns it on (the CI
smoke lane does, with a 2-candidate grid via $REPRO_TUNE_MAX_CANDIDATES).
See docs/tuning.md.
"""
from repro.tune.autotune import (
    MEASURE_PROBLEM,
    best_blocks,
    best_chunk,
    cached_interpret_max_n,
    clear_memo,
    enabled,
    make_key,
    measure_blocks,
    measure_chunks,
    timing_runs,
)
from repro.tune.cache import (
    SCHEMA_VERSION,
    cache_path,
    load_entries,
    lookup,
    store,
)
from repro.tune.search import (
    CHUNK_CANDIDATES,
    DEFAULT_CHUNK,
    TILE_M_CANDIDATES,
    TILE_N_CANDIDATES,
    admissible,
    candidate_blocks,
    candidate_chunks,
    default_blocks,
)

__all__ = [
    "MEASURE_PROBLEM",
    "SCHEMA_VERSION",
    "CHUNK_CANDIDATES",
    "DEFAULT_CHUNK",
    "TILE_M_CANDIDATES",
    "TILE_N_CANDIDATES",
    "admissible",
    "best_blocks",
    "best_chunk",
    "cache_path",
    "cached_interpret_max_n",
    "candidate_blocks",
    "candidate_chunks",
    "clear_memo",
    "default_blocks",
    "enabled",
    "load_entries",
    "lookup",
    "make_key",
    "measure_blocks",
    "measure_chunks",
    "store",
    "timing_runs",
]
