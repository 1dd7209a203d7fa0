"""The repro.tune autotuner: persistent-cache semantics (round-trip, schema
rejection, corrupt-file tolerance, concurrency), the zero-timing warm-cache
contract (in-process and across processes), tuned-block resolution through
`kernels.ops`, chunk="auto" parity, the bounded op-factory cache, and the
tune-cache-backed interpret-dispatch threshold."""
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.analysis.pallas_audit import Problem, audit_candidate, vmem_estimate
from repro.kernels import ops
from repro.tune import autotune, cache, search

SMALL = Problem(N=64, M=128, Q=3, D=2)


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated cache file + clean memo + tuning force-DISABLED."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    monkeypatch.setattr(autotune, "_ENABLED_OVERRIDE", False)
    tune.clear_memo()
    yield path
    tune.clear_memo()


@pytest.fixture
def tuning_on(tune_env, monkeypatch):
    """Same isolation, but with the measuring path live."""
    monkeypatch.setattr(autotune, "_ENABLED_OVERRIDE", True)
    return tune_env


def _runs():
    return tune.timing_runs()


# ---------------------------------------------------------------------------
# persistent cache store
# ---------------------------------------------------------------------------

def test_cache_round_trip(tune_env):
    cache.store("k1", {"winner": [32, 128]}, tune_env)
    cache.store("k2", {"winner": 2048}, tune_env)
    assert cache.lookup("k1", tune_env) == {"winner": [32, 128]}
    assert cache.lookup("k2", tune_env) == {"winner": 2048}
    # the file itself is schema-stamped, whole-document JSON
    doc = json.load(open(tune_env))
    assert doc["schema_version"] == cache.SCHEMA_VERSION
    assert set(doc["entries"]) == {"k1", "k2"}


def test_cache_schema_mismatch_rejected(tune_env):
    with open(tune_env, "w") as f:
        json.dump({"schema_version": cache.SCHEMA_VERSION + 1,
                   "entries": {"k": {"winner": [8, 128]}}}, f)
    assert cache.load_entries(tune_env) == {}
    assert cache.lookup("k", tune_env) is None


@pytest.mark.parametrize("content", [
    "", "{", "[1, 2, 3]", '{"entries": {"k": 1}}', "\x00\x01garbage",
    '{"schema_version": 1, "entries": "not a dict"}',
])
def test_cache_corrupt_file_falls_back_without_raising(tune_env, content):
    with open(tune_env, "w") as f:
        f.write(content)
    assert cache.load_entries(tune_env) == {}
    # and a resolve over the corrupt file still answers (defaults)
    assert tune.best_blocks("kfu_pallas", dtype=jnp.float32, m=128,
                            q=3) is None
    assert _runs() == 0


def test_cache_store_over_corrupt_file_recovers(tune_env):
    with open(tune_env, "w") as f:
        f.write("definitely not json")
    cache.store("k", {"winner": [64, 128]}, tune_env)
    assert cache.lookup("k", tune_env) == {"winner": [64, 128]}


def test_cache_missing_file_is_empty(tune_env):
    assert not os.path.exists(tune_env)
    assert cache.load_entries(tune_env) == {}


def test_cache_path_env_override(tune_env):
    assert cache.cache_path() == tune_env


# ---------------------------------------------------------------------------
# resolution: disabled -> defaults with zero timing, cached -> winner
# ---------------------------------------------------------------------------

def test_disabled_resolution_returns_defaults_without_timing(tune_env):
    # disabled tuning ignores even a cached winner: only the committed
    # default tiles run unless REPRO_TUNE=1
    key = autotune.make_key("blocks", "psi1_pallas", jnp.float32, 128, 3)
    cache.store(key, {"winner": [64, 128]}, tune_env)
    tune.clear_memo()
    before = _runs()
    assert tune.best_blocks("psi1_pallas", dtype=jnp.float32, m=128,
                            q=3) is None
    assert tune.best_chunk(n=512, m=16, q=2, d=1) == tune.DEFAULT_CHUNK
    assert _runs() == before


@pytest.mark.parametrize("value, on", [
    (None, False), ("1", True), ("0", False), ("true", False), ("", False)])
def test_enabled_only_with_repro_tune_1(monkeypatch, value, on):
    """Opt-in on every backend, accelerators included."""
    monkeypatch.setattr(autotune, "_ENABLED_OVERRIDE", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if value is None:
        monkeypatch.delenv("REPRO_TUNE", raising=False)
    else:
        monkeypatch.setenv("REPRO_TUNE", value)
    assert tune.enabled() is on


def test_cached_winner_resolves_without_timing(tuning_on):
    key = autotune.make_key("blocks", "kfu_pallas", jnp.float32, 128, 3)
    cache.store(key, {"winner": [64, 128]}, tuning_on)
    tune.clear_memo()
    before = _runs()
    assert tune.best_blocks("kfu_pallas", dtype=jnp.float32, m=128,
                            q=3) == (64, 128)
    assert _runs() == before


def test_first_call_measures_and_persists(tuning_on, monkeypatch):
    timed = []
    monkeypatch.setattr(autotune, "_time_fn",
                        lambda fn: float(len(timed)) + (timed.append(1) or 1.0))
    monkeypatch.setenv("REPRO_TUNE_MAX_CANDIDATES", "2")
    before = _runs()
    win = tune.best_blocks("kfu_pallas", dtype=jnp.float32, m=SMALL.M,
                           q=SMALL.Q, problem=SMALL)
    assert win is not None and len(win) == 2
    assert _runs() == before + 2  # counted even with the fake stopwatch
    # persisted: a fresh memo resolves from the file with no new timing
    tune.clear_memo()
    assert tune.best_blocks("kfu_pallas", dtype=jnp.float32, m=SMALL.M,
                            q=SMALL.Q, problem=SMALL) == win
    assert _runs() == before + 2


def test_concurrent_first_call_resolves_to_one_winner(tuning_on, monkeypatch):
    calls = []

    def fake_time(fn):
        calls.append(1)
        return float(len(calls))  # monotone: first candidate always wins

    monkeypatch.setattr(autotune, "_time_fn", fake_time)
    monkeypatch.setenv("REPRO_TUNE_MAX_CANDIDATES", "2")
    results = []

    def worker():
        results.append(tune.best_blocks(
            "kfu_pallas", dtype=jnp.float32, m=SMALL.M, q=SMALL.Q,
            problem=SMALL))

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 2 and results[0] == results[1]
    # exactly one thread measured: one 2-candidate sweep, not two
    assert len(calls) == 2
    entries = cache.load_entries(tuning_on)
    assert sum(1 for k in entries if k.startswith("blocks|")) == 1


def test_warm_cache_second_process_does_zero_timing_runs(tmp_path):
    path = str(tmp_path / "tune.json")
    env = dict(os.environ, REPRO_TUNE="1", REPRO_TUNE_CACHE=path,
               REPRO_TUNE_MAX_CANDIDATES="2", JAX_PLATFORMS="cpu")
    prog = (
        "import jax.numpy as jnp\n"
        "from repro import tune\n"
        "from repro.analysis.pallas_audit import Problem\n"
        "p = Problem(N=64, M=128, Q=3, D=2)\n"
        "w = tune.best_blocks('kfu_pallas', dtype=jnp.float32, m=128, q=3,"
        " problem=p)\n"
        "assert w is not None\n"
        "print('RUNS', tune.timing_runs())\n"
    )
    first = subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True)
    assert first.returncode == 0, first.stderr
    assert "RUNS 2" in first.stdout
    second = subprocess.run([sys.executable, "-c", prog], env=env,
                            capture_output=True, text=True)
    assert second.returncode == 0, second.stderr
    assert "RUNS 0" in second.stdout  # the warm-cache contract


# ---------------------------------------------------------------------------
# search space: auditor-gated candidates
# ---------------------------------------------------------------------------

def test_candidates_start_with_default_and_pass_audit():
    cands = search.candidate_blocks("kfu_pallas", problem=SMALL)
    assert cands[0] == search.default_blocks("kfu_pallas")
    for blk in cands:
        audit = audit_candidate("kfu_pallas", blk, problem=SMALL)
        assert audit.fits
        assert not any(f.code in ("TILE001", "IDX001")
                       for f in audit.findings)


def test_candidate_limit_env(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_MAX_CANDIDATES", "2")
    assert len(search.candidate_blocks("psi1_pallas", problem=SMALL)) == 2


def test_over_budget_candidates_are_filtered():
    # a tiny budget admits nothing: every candidate is gated by the
    # auditor's single VMEM model
    audit = audit_candidate("suffstats_pallas", (32, 128), problem=SMALL,
                            vmem_budget_bytes=1024)
    assert not audit.fits


def test_vmem_estimate_is_the_shared_model():
    assert vmem_estimate(100, 10, 5) == 2 * 100 + 10 + 5
    audit = audit_candidate("kfu_pallas", (32, 128), problem=SMALL)
    assert audit.vmem_estimate_bytes == vmem_estimate(
        audit.streamed_bytes, audit.resident_bytes,
        audit.body_workspace_bytes)


def test_chunk_candidates_respect_n():
    cands = search.candidate_chunks(1500)
    assert cands[0] == search.DEFAULT_CHUNK
    assert 1500 in cands
    assert all(c <= 1500 or c == search.DEFAULT_CHUNK for c in cands)


# ---------------------------------------------------------------------------
# ops integration: tuned blocks flow into the kernels, numerics unchanged
# ---------------------------------------------------------------------------

def _psi_args(n=24, m=16, q=3, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (n, q)),
            jnp.exp(jax.random.normal(k[1], (n, q)) * 0.2),
            jax.random.normal(k[2], (m, q)),
            jnp.exp(jax.random.normal(k[3], ()) * 0.1),
            jnp.exp(jax.random.normal(k[4], (q,)) * 0.1))


def test_explicit_block_override_matches_defaults(tune_env):
    mu, S, Z, var, ls = _psi_args()
    base = ops.psi1(mu, S, Z, var, ls)
    alt = ops.psi1(mu, S, Z, var, ls, block=(64, 128), bwd_block=(64, 128))
    np.testing.assert_allclose(np.asarray(base), np.asarray(alt), rtol=1e-12)

    g = jax.grad(lambda *a: ops.psi2(*a).sum(), argnums=(0, 1))(mu, S, Z,
                                                                var, ls)
    g_alt = jax.grad(
        lambda *a: ops.psi2(*a, block=(64, 256), bwd_block=(64, 256)).sum(),
        argnums=(0, 1))(mu, S, Z, var, ls)
    for a, b in zip(g, g_alt):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9)


def test_tuned_winner_is_consulted_by_ops(tuning_on, monkeypatch):
    """A cached winner changes which block reaches the Pallas wrapper."""
    seen = {}
    real = ops.kfu_pallas

    def spy(*args, **kw):
        seen["block"] = kw.get("block")
        return real(*args, **kw)

    monkeypatch.setattr(ops, "kfu_pallas", spy)
    for name in ("kfu_pallas", "psi1_bwd_pallas"):
        key = autotune.make_key("blocks", name, jnp.float64, 16, 3)
        cache.store(key, {"winner": [64, 128]}, tuning_on)
    tune.clear_memo()
    X = jnp.ones((8, 3)); Z = jnp.ones((16, 3))
    out = ops.kfu(X, Z, jnp.asarray(1.0), jnp.ones(3))
    assert seen["block"] == (64, 128)
    # ...and the numbers match the default-block path exactly
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(real(X, Z, jnp.asarray(1.0), jnp.ones(3),
                        interpret=True)),
        rtol=1e-12)


def test_all_seven_kernels_resolve_through_tune(tune_env, monkeypatch):
    """Every registered kernel's entry point consults tune.best_blocks for
    its direction — forward AND reverse."""
    asked = []
    real = tune.best_blocks

    def spy(name, **kw):
        asked.append(name)
        return real(name, **kw)

    monkeypatch.setattr("repro.tune.best_blocks", spy)
    mu, S, Z, var, ls = _psi_args()
    Y = jnp.ones((mu.shape[0], 2), mu.dtype)
    X = mu
    jax.grad(lambda *a: ops.kfu(*a).sum())(X, Z, var, ls)
    jax.grad(lambda *a: ops.psi1(*a).sum())(mu, S, Z, var, ls)
    jax.grad(lambda *a: ops.psi2(*a).sum())(mu, S, Z, var, ls)
    jax.grad(lambda *a: sum(o.sum() for o in ops.suffstats(*a)))(
        mu, S, Y, Z, var, ls)
    assert set(asked) == {
        "kfu_pallas", "psi1_pallas", "psi2_pallas", "suffstats_pallas",
        "suffstats_bwd_pallas", "psi1_bwd_pallas", "psi2_bwd_pallas"}


def test_chunk_auto_matches_explicit(tune_env):
    from repro.gp.kernels import RBF
    from repro.gp.stats import ExpectedBatch, suff_stats

    kern = RBF(2)
    params = kern.init()
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = ExpectedBatch(
        jax.random.normal(k[0], (37, 2)),
        jnp.exp(jax.random.normal(k[1], (37, 2)) * 0.2),
        jax.random.normal(k[2], (37, 1)),
        jnp.linspace(-1, 1, 8)[:, None] * jnp.ones((8, 2)))
    auto = suff_stats(kern, params, batch, backend="jnp", chunk="auto")
    explicit = suff_stats(kern, params, batch, backend="jnp",
                          chunk=tune.DEFAULT_CHUNK)
    for a, b in zip(auto, explicit):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-10)
    with pytest.raises(ValueError, match="auto"):
        suff_stats(kern, params, batch, backend="jnp", chunk="turbo")


def test_chunk_auto_uses_cached_winner(tuning_on, monkeypatch):
    from repro.gp import stats as gp_stats
    from repro.gp.kernels import RBF

    key = autotune.make_key("chunk", "streaming_suff_stats", jnp.float64,
                            8, 2, extra="backend=jnp")
    cache.store(key, {"winner": 7}, tuning_on)
    tune.clear_memo()
    kern = RBF(2)
    params = kern.init()
    batch = gp_stats.ExpectedBatch(
        jnp.ones((21, 2)), jnp.full((21, 2), 0.4), jnp.ones((21, 1)),
        jnp.ones((8, 2)))

    # the facade accepts "auto" too (no int() coercion in the constructor)
    from repro.gp.models import BayesianGPLVM
    model = BayesianGPLVM(RBF(2), M=8, chunk="auto")
    assert model.chunk == "auto"

    resolved = tune.best_chunk(n=21, m=8, q=2, d=1, dtype=jnp.float64,
                               backend="jnp")
    assert resolved == 7
    auto = gp_stats.suff_stats(kern, params, batch, backend="jnp",
                               chunk="auto")
    explicit = gp_stats.suff_stats(kern, params, batch, backend="jnp",
                                   chunk=7)
    for a, b in zip(auto, explicit):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-10)


# ---------------------------------------------------------------------------
# satellite: bounded op-factory cache + debug hook
# ---------------------------------------------------------------------------

def test_op_factory_cache_is_bounded_with_info():
    info = ops.cache_info()
    assert set(info) == {"kfu", "psi1", "psi2", "suffstats"}
    for stats in info.values():
        assert stats.maxsize == ops._OP_CACHE_SIZE
    before = ops.cache_info()["kfu"].currsize
    X = jnp.ones((8, 3)); Z = jnp.ones((8, 3))
    # blocks no other test uses, so these two knob keys are fresh
    ops.kfu(X, Z, jnp.asarray(1.0), jnp.ones(3), block=(96, 128))
    ops.kfu(X, Z, jnp.asarray(1.0), jnp.ones(3), block=(160, 128))
    after = ops.cache_info()["kfu"]
    assert after.currsize == min(before + 2, ops._OP_CACHE_SIZE)
    assert after.currsize <= ops._OP_CACHE_SIZE


# ---------------------------------------------------------------------------
# satellite: interpret-dispatch threshold (named constant + hooks)
# ---------------------------------------------------------------------------

def test_interpret_threshold_default_and_module_getattr(tune_env):
    assert ops.fused_interpret_max_n() == ops.DEFAULT_FUSED_INTERPRET_MAX_N
    # back-compat attribute still reads (call-time fresh)
    assert ops.FUSED_INTERPRET_MAX_N == ops.DEFAULT_FUSED_INTERPRET_MAX_N


def test_interpret_threshold_override_hook(tune_env, monkeypatch):
    monkeypatch.setattr(ops, "_INTERPRET_MAX_N_OVERRIDE", 7)
    assert ops.fused_interpret_max_n() == 7
    assert ops.FUSED_INTERPRET_MAX_N == 7


def test_interpret_threshold_reads_tune_cache(tuning_on):
    key = "|".join(["interpret_max_n", jax.default_backend()])
    cache.store(key, {"winner": 512}, tuning_on)
    tune.clear_memo()
    assert tune.cached_interpret_max_n() == 512
    assert ops.fused_interpret_max_n() == 512
    assert ops.FUSED_INTERPRET_MAX_N == 512
