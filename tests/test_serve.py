"""The `repro.serve` online-prediction subsystem: exported state vs facade
parity, online update vs from-scratch refold (all backends), downdate as the
monoid inverse (+ the condition guard), bucket-padded predict exactness, the
micro-batching server round-trip, the facade posterior cache, and the
million-point no-(N, M)-materialization guarantee — same trace-assertion
style as tests/test_streaming.py."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.core import svgp
from repro.core.psi_stats import SuffStats
from repro.gp import BayesianGPLVM, SparseGPRegression, get, suff_stats
from repro.gp.stats import ExactBatch
from repro.analysis import assert_no_scaling
from repro.serve import GPServer, online


def _f64(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float64), tree)


def _data(key, N, Q=2, D=3, M=12):
    X = jax.random.normal(key, (N, Q), jnp.float64)
    w = jnp.arange(1, D + 1, dtype=jnp.float64)
    Y = jnp.sin(X.sum(axis=1))[:, None] * w + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (N, D), jnp.float64)
    Z = X[:: max(N // M, 1)][:M]
    return X, Y, Z


def _params(Z, *, log_beta=2.0):
    kern = _f64(get("rbf")(Z.shape[1]).init(1.3, 0.8))
    return {"kern": kern, "Z": Z, "log_beta": jnp.asarray(log_beta, jnp.float64)}


def _state_from(kernel, params, X, Y, **kw):
    stats = suff_stats(kernel, params["kern"], ExactBatch(X, Y, params["Z"]), **kw)
    return serve.build_state(kernel, params, stats)


def _assert_stats_close(a: SuffStats, b: SuffStats, rtol=1e-8, atol=1e-10):
    for x, y, name in zip(a, b, a._fields):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol, err_msg=name)


def _fitted_gp(key, N=300, M=16, steps=60):
    X = jnp.sort(jax.random.uniform(key, (N, 1), jnp.float64, -3.0, 3.0), axis=0)
    Y = jnp.sin(2.0 * X) + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), (N, 1), jnp.float64)
    gp = SparseGPRegression(kernel=get("rbf")(1), M=M).fit(X, Y, steps=steps)
    return gp, X, Y


# ---------------------------------------------------------------------------
# export_state: the cached posterior serves identically to the facade
# ---------------------------------------------------------------------------

def test_export_state_predicts_like_the_facade():
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(0))
    st = gp.export_state()
    assert st.M == 16 and st.D == 1 and float(st.stats.n) == X.shape[0]
    mean_f, var_f = gp.predict(X[:17])
    mean_s, var_s = serve.predict(gp.kernel, st, X[:17])
    np.testing.assert_allclose(np.asarray(mean_s), np.asarray(mean_f),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(np.asarray(var_s), np.asarray(var_f),
                               rtol=1e-10, atol=1e-12)
    # full covariance: diagonal agrees with the marginal variance, and the
    # matrix is symmetric PSD-ish (small negative eigenvalues = roundoff)
    mean_c, cov = serve.predict(gp.kernel, st, X[:17], diag=False)
    np.testing.assert_allclose(np.asarray(mean_c), np.asarray(mean_f),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(np.diagonal(np.asarray(cov)), np.asarray(var_f),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(cov), np.asarray(cov).T, atol=1e-12)
    assert float(np.min(np.linalg.eigvalsh(np.asarray(cov)))) > -1e-8


def test_export_state_gplvm_decodes_like_the_facade():
    key = jax.random.PRNGKey(1)
    from repro.data.synthetic import gplvm_synthetic

    _, Y = gplvm_synthetic(key, N=96, D=3, Q=1)
    lvm = BayesianGPLVM(kernel=get("rbf")(1), M=10).fit(
        Y.astype(jnp.float64), steps=30, lr=5e-2, key=key)
    st = lvm.export_state()
    Xstar = jnp.linspace(-2.0, 2.0, 9)[:, None].astype(jnp.float64)
    mean_f, var_f = lvm.predict(Xstar)
    mean_s, var_s = serve.predict(lvm.kernel, st, Xstar)
    np.testing.assert_allclose(np.asarray(mean_s), np.asarray(mean_f),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(np.asarray(var_s), np.asarray(var_f),
                               rtol=1e-10, atol=1e-12)


def test_export_state_gplvm_keeps_the_shifted_epilogue():
    """In float32 at M = 48 the GP-LVM's epilogue shifts Kuu and A by
    M^2 eps of their mean diagonals (`svgp.cholesky_shift`), above the
    default jitter and floor; the exported state holds the same factors as
    the facade's posterior."""
    key = jax.random.PRNGKey(3)
    from repro.data.synthetic import gplvm_synthetic

    _, Y = gplvm_synthetic(key, N=256, D=4, Q=2)
    lvm = BayesianGPLVM(kernel=get("rbf")(2), M=48).fit(
        Y.astype(jnp.float32), steps=3, key=key)
    assert svgp.cholesky_shift(48, jnp.float32) > 100 * svgp.DEFAULT_JITTER
    st, post = lvm.export_state(), lvm.posterior()
    assert post.LA.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(st.L), np.asarray(post.L))
    np.testing.assert_array_equal(np.asarray(st.LA), np.asarray(post.LA))


# ---------------------------------------------------------------------------
# online update: monoid fold == from-scratch statistics build
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("jnp", "pallas", "fused"))
def test_update_matches_from_scratch_build(backend):
    """Fitting on X[:n] then folding the remaining b points must equal the
    statistics (and refold) built from scratch on X[:n+b] — at 1e-8 in f64,
    on every statistics backend."""
    key = jax.random.PRNGKey(2)
    n, b = 200, 57  # non-dividing split
    X, Y, Z = _data(key, n + b)
    params = _params(Z)
    kernel = get("rbf")(2)
    st0 = _state_from(kernel, params, X[:n], Y[:n])
    up = online.update(kernel, st0, X[n:], Y[n:], backend=backend)
    scratch = _state_from(kernel, params, X, Y)
    _assert_stats_close(up.stats, scratch.stats)
    # the refold epilogue agrees too (conditioning can amplify the stats
    # delta into the factors, hence the looser bar)
    mean_u, var_u = serve.predict(kernel, up, X[:9])
    mean_s, var_s = serve.predict(kernel, scratch, X[:9])
    np.testing.assert_allclose(np.asarray(mean_u), np.asarray(mean_s),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(var_u), np.asarray(var_s),
                               rtol=1e-6, atol=1e-8)


def test_update_streams_and_composes():
    """chunk= streams the incremental batch; two sequential updates equal
    one combined update (monoid associativity)."""
    key = jax.random.PRNGKey(3)
    X, Y, Z = _data(key, 300)
    params = _params(Z)
    kernel = get("rbf")(2)
    st0 = _state_from(kernel, params, X[:100], Y[:100])
    one = online.update(kernel, st0, X[100:], Y[100:], chunk=64)
    two = online.update(kernel,
                        online.update(kernel, st0, X[100:200], Y[100:200]),
                        X[200:], Y[200:])
    _assert_stats_close(one.stats, two.stats, rtol=1e-10)
    scratch = _state_from(kernel, params, X, Y)
    _assert_stats_close(one.stats, scratch.stats)


def test_downdate_inverts_update():
    key = jax.random.PRNGKey(4)
    X, Y, Z = _data(key, 260)
    params = _params(Z)
    kernel = get("rbf")(2)
    st0 = _state_from(kernel, params, X[:200], Y[:200])
    round_trip = online.downdate(
        kernel, online.update(kernel, st0, X[200:], Y[200:]), X[200:], Y[200:])
    _assert_stats_close(round_trip.stats, st0.stats)
    mean_r, var_r = serve.predict(kernel, round_trip, X[:9])
    mean_0, var_0 = serve.predict(kernel, st0, X[:9])
    np.testing.assert_allclose(np.asarray(mean_r), np.asarray(mean_0),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(var_r), np.asarray(var_0),
                               rtol=1e-6, atol=1e-8)


def test_downdate_guard_raises_on_indefinite_statistics():
    """Subtracting statistics that were never added drives Kuu + beta Psi2
    indefinite; the guard escalates jitter, fails to repair it, and raises
    rather than serving NaN."""
    key = jax.random.PRNGKey(5)
    X, Y, Z = _data(key, 120)
    params = _params(Z)
    kernel = get("rbf")(2)
    st = _state_from(kernel, params, X[:40], Y[:40])
    with pytest.raises(FloatingPointError, match="indefinite"):
        online.downdate(kernel, st, X, 10.0 * Y)


def test_refit_recovers_perturbed_noise_from_stats_alone():
    """log_beta is the one hyperparameter the cached statistics don't
    depend on: refit must improve the bound from the stats, no data."""
    key = jax.random.PRNGKey(6)
    X, Y, Z = _data(key, 240)
    kernel = get("rbf")(2)
    good = _state_from(kernel, _params(Z, log_beta=2.0), X, Y)
    bad = _state_from(kernel, _params(Z, log_beta=-3.0), X, Y)
    refitted, history = online.refit(kernel, bad, steps=200, lr=5e-2)
    assert history[-1] < history[0] - 1e-3  # the bound improved
    # beta moved toward the well-fit value (within a decade)
    assert abs(float(refitted.log_beta) - 2.0) < abs(-3.0 - 2.0)
    # statistics are untouched: refit is an epilogue-only operation
    _assert_stats_close(refitted.stats, bad.stats, rtol=0.0, atol=0.0)
    del good


# ---------------------------------------------------------------------------
# GPServer: bucket padding + compile cache + micro-batching queue
# ---------------------------------------------------------------------------

def _assert_ulp_equal(a, b):
    # bucket padding must not leak into the real rows. XLA specializes
    # matmul codegen per shape, so cross-shape comparisons can differ in the
    # last ulp of the accumulated terms — and the variance is a cancelling
    # difference of O(0.1) terms, so one ulp there is ~1e-16 absolute.
    # Anything beyond that means the padding perturbed the math.
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12,
                               atol=1e-14)


def test_bucketed_predict_matches_unpadded_exactly():
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(7))
    st = gp.export_state()
    srv = GPServer(buckets=(4, 16, 64))
    srv.register("gp", gp)
    sizes = (1, 3, 4, 5, 16, 23, 64, 150)
    unpadded = {B: serve.predict(gp.kernel, st, X[:B]) for B in sizes}
    for B in sizes:
        mean_b, var_b = srv.predict("gp", X[:B])  # 150 > 64: bucket slices
        _assert_ulp_equal(mean_b, unpadded[B][0])
        _assert_ulp_equal(var_b, unpadded[B][1])
        # at exactly a bucket shape no padding happens at all: bit-identical
        if B in srv.buckets:
            np.testing.assert_array_equal(np.asarray(mean_b),
                                          np.asarray(unpadded[B][0]))
    # a full covariance cannot be stitched from largest-bucket slices
    with pytest.raises(ValueError, match="bucket"):
        srv.predict("gp", X[:150], diag=False)
    # the compile cache is bounded by the bucket set: 8 request shapes
    # mapped onto <= 3 jitted specializations of the entry's own closure
    # (owned per entry so dropped registrations free their executables)
    assert srv._models["gp"].fns[True]._cache_size() <= 3


def test_server_submit_round_trip_and_concurrency():
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(8))
    st = gp.export_state()
    with GPServer() as srv:
        srv.register("gp", kernel=gp.kernel, state=st)
        # many concurrent submitters; the worker coalesces compatible
        # requests into shared device calls — answers must be per-request
        futs, errs = {}, []

        def client(i):
            try:
                futs[i] = srv.submit("gp", X[3 * i: 3 * i + 3])
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i, fut in futs.items():
            mean, var = fut.result(timeout=30)
            mean_u, var_u = serve.predict(gp.kernel, st, X[3 * i: 3 * i + 3])
            np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_u),
                                       rtol=1e-12, atol=1e-14)
            np.testing.assert_allclose(np.asarray(var), np.asarray(var_u),
                                       rtol=1e-12, atol=1e-14)
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit("gp", X[:1])
    with pytest.raises(KeyError, match="registered"):
        srv.predict("nope", X[:1])


def test_malformed_requests_rejected_in_caller_and_worker_survives():
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(12), steps=5)
    with GPServer() as srv:
        srv.register("gp", gp)
        # shape validation happens in the SUBMITTING thread, not the worker
        for bad in (X[:, 0], X[0, 0], X[:0]):
            with pytest.raises(ValueError, match="batches"):
                srv.submit("gp", bad)
            with pytest.raises(ValueError, match="batches"):
                srv.predict("gp", bad)
        # the worker is still alive and serving after the rejections
        mean, _ = srv.submit("gp", X[:3]).result(timeout=30)
        np.testing.assert_allclose(np.asarray(mean),
                                   np.asarray(srv.predict("gp", X[:3])[0]),
                                   rtol=1e-12, atol=1e-14)


def test_models_iteration_safe_under_concurrent_register():
    """Registry reads (`models()` / `state()` lookups) snapshot under the
    registry lock: a register() storm while another thread iterates must
    never raise "dictionary changed size during iteration" or hand back a
    torn view (regression for the unlocked reads)."""
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(13), steps=5)
    st = gp.export_state()
    srv = GPServer()
    srv.register("base", kernel=gp.kernel, state=st)
    errs, stop = [], threading.Event()

    def reader():
        try:
            while not stop.is_set():
                names = srv.models()
                assert "base" in names
                assert srv.state("base") is not None
        except Exception as e:  # pragma: no cover - the regression itself
            errs.append(e)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers:
        t.start()
    try:
        for i in range(300):
            srv.register(f"m{i}", kernel=gp.kernel, state=st)
    finally:
        stop.set()
        for t in readers:
            t.join()
    assert not errs, errs
    assert len(srv.models()) == 301


def test_cancelled_future_does_not_poison_coalesced_group():
    """A caller cancelling its Future while the request waits in the queue
    must not break delivery for the rest of the coalesced group: the worker
    claims each dequeued future (set_running_or_notify_cancel) and skips
    the cancelled ones (regression for the InvalidStateError abort)."""
    from concurrent.futures import Future

    from repro.serve.server import _Request

    gp, X, _ = _fitted_gp(jax.random.PRNGKey(14), steps=5)
    st = gp.export_state()
    with GPServer() as srv:
        srv.register("gp", kernel=gp.kernel, state=st)
        # enqueue a request by hand BEFORE the worker exists, then cancel it:
        # the next submit() starts the worker, which drains both requests as
        # one group with the cancelled future first
        cancelled = Future()
        with srv._cv:
            srv._queue.append(_Request("gp", X[:2], True, cancelled))
        assert cancelled.cancel()
        live = [srv.submit("gp", X[3 * i: 3 * i + 3]) for i in range(3)]
        for i, fut in enumerate(live):
            mean, var = fut.result(timeout=30)  # InvalidStateError poisoned
            want_mean, want_var = srv.predict("gp", X[3 * i: 3 * i + 3])
            np.testing.assert_allclose(np.asarray(mean), np.asarray(want_mean),
                                       rtol=1e-12, atol=1e-14)
            np.testing.assert_allclose(np.asarray(var), np.asarray(want_var),
                                       rtol=1e-12, atol=1e-14)
        assert cancelled.cancelled()


def test_server_online_update_shifts_predictions():
    key = jax.random.PRNGKey(9)
    X, Y, Z = _data(key, 300, Q=1, D=1, M=10)
    kernel = get("rbf")(1)
    params = _params(Z)
    srv = GPServer()
    srv.register("m", kernel=kernel, state=_state_from(kernel, params,
                                                       X[:150], Y[:150]))
    before = srv.predict("m", X[:5])
    srv.update("m", X[150:], Y[150:])
    assert float(srv.state("m").stats.n) == 300
    after = srv.predict("m", X[:5])
    assert not np.allclose(np.asarray(before[1]), np.asarray(after[1]))
    srv.downdate("m", X[150:], Y[150:])
    restored = srv.predict("m", X[:5])
    np.testing.assert_allclose(np.asarray(restored[0]), np.asarray(before[0]),
                               rtol=1e-7, atol=1e-9)
    hist = srv.refit("m", steps=5)
    assert len(hist) >= 2 and np.isfinite(hist[-1])


# ---------------------------------------------------------------------------
# fault injection: a production queue must degrade per-request, never
# per-server (the worker survives everything a request can throw at it)
# ---------------------------------------------------------------------------

def _gate_model(srv, name):
    """Wrap a registered entry's predict closures behind a gate: the worker
    blocks inside the device call until `release.set()`, and `started` flags
    that the worker has dequeued (so the queue length is deterministic)."""
    entry = srv._models[name]
    orig = dict(entry.fns)
    started, release = threading.Event(), threading.Event()

    def gated(state, X):
        started.set()
        assert release.wait(30), "test gate never released"
        return orig[True](state, X)

    entry.fns = {True: gated, False: orig[False]}
    return started, release


def test_poisoned_device_call_fails_only_its_own_futures():
    """An exception out of one model's device call lands on that group's
    futures; other groups in the same drain complete, and the worker is
    alive for the next drain."""
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(20), steps=5)
    st = gp.export_state()
    boom = RuntimeError("injected device failure")
    with GPServer() as srv:
        srv.register("ok", kernel=gp.kernel, state=st)
        srv.register("bad", kernel=gp.kernel, state=st)
        srv._models["bad"].fns = {True: _raiser(boom), False: _raiser(boom)}
        # hold the worker so both models' requests land in ONE drain
        started, release = _gate_model(srv, "ok")
        first = srv.submit("ok", X[:2])
        assert started.wait(30)
        bad_futs = [srv.submit("bad", X[:3]) for _ in range(3)]
        ok_futs = [srv.submit("ok", X[3 * i: 3 * i + 3]) for i in range(3)]
        release.set()
        for fut in bad_futs:  # the poisoned group: ITS futures fail
            with pytest.raises(RuntimeError, match="injected"):
                fut.result(timeout=30)
        for i, fut in enumerate(ok_futs):  # siblings in the drain complete
            mean, _ = fut.result(timeout=30)
            want, _ = serve.predict(gp.kernel, st, X[3 * i: 3 * i + 3])
            np.testing.assert_allclose(np.asarray(mean), np.asarray(want),
                                       rtol=1e-12, atol=1e-14)
        first.result(timeout=30)
        # worker survived: a fresh healthy request round-trips
        srv.submit("ok", X[:2]).result(timeout=30)


def _raiser(exc):
    def fn(state, X):
        raise exc

    return fn


def test_expired_deadline_fails_only_its_own_future():
    """A request that waits past its deadline gets TimeoutError on its own
    future at claim time; the rest of the coalesced group is served. Expiry
    happens AFTER set_running_or_notify_cancel, so it can never race a
    caller-side cancel() into InvalidStateError."""
    from concurrent.futures import Future

    from repro.serve.server import _Request

    gp, X, _ = _fitted_gp(jax.random.PRNGKey(21), steps=5)
    st = gp.export_state()
    with GPServer() as srv:
        srv.register("gp", kernel=gp.kernel, state=st)
        # enqueue an already-expired request by hand BEFORE the worker
        # exists (same trick as the cancelled-future regression test): the
        # next submit() starts the worker, which drains both as one group
        expired = Future()
        with srv._cv:
            srv._queue.append(_Request("gp", X[:2], True, expired,
                                       deadline=-1.0))
        live = [srv.submit("gp", X[3 * i: 3 * i + 3]) for i in range(3)]
        for i, fut in enumerate(live):
            mean, _ = fut.result(timeout=30)
            want, _ = srv.predict("gp", X[3 * i: 3 * i + 3])
            np.testing.assert_allclose(np.asarray(mean), np.asarray(want),
                                       rtol=1e-12, atol=1e-14)
        with pytest.raises(TimeoutError, match="deadline"):
            expired.result(timeout=30)
        assert srv.metrics()["expired"] == 1
        # the queue is not wedged: the next submit round-trips
        srv.submit("gp", X[:2]).result(timeout=30)


def test_admission_control_rejects_at_max_pending():
    """Submits past max_pending fail fast with QueueFullError in the CALLER
    (the request never enters the queue); accepted requests are unaffected
    and complete once the worker unblocks."""
    from repro.serve import QueueFullError

    gp, X, _ = _fitted_gp(jax.random.PRNGKey(22), steps=5)
    with GPServer(max_pending=2) as srv:
        srv.register("gp", gp)
        started, release = _gate_model(srv, "gp")
        first = srv.submit("gp", X[:2])  # worker dequeues this and blocks
        assert started.wait(30)
        accepted = [srv.submit("gp", X[:2]) for _ in range(2)]  # fills queue
        with pytest.raises(QueueFullError, match="max_pending"):
            srv.submit("gp", X[:2])
        assert srv.metrics()["rejected"] == 1
        release.set()
        for fut in (first, *accepted):  # rejection did not poison anyone
            mean, var = fut.result(timeout=30)
            assert mean.shape == (2, 1) and var.shape == (2,)
    # queue empties after the drain -> no lingering admission debt
    assert srv.metrics()["rejected"] == 1


def test_close_drains_inflight_submits_deterministically():
    """close() during in-flight submits: every accepted Future completes
    (graceful drain), late submits fail with ServerClosedError, and close()
    is idempotent."""
    from repro.serve import ServerClosedError

    gp, X, _ = _fitted_gp(jax.random.PRNGKey(23), steps=5)
    srv = GPServer()
    srv.register("gp", gp)
    started, release = _gate_model(srv, "gp")
    first = srv.submit("gp", X[:2])
    assert started.wait(30)
    queued = [srv.submit("gp", X[:3]) for _ in range(8)]  # sit in the queue
    closer = threading.Thread(target=srv.close)
    closer.start()
    release.set()
    closer.join(timeout=30)
    assert not closer.is_alive()
    for fut in (first, *queued):  # accepted before close() => completed
        mean, _ = fut.result(timeout=30)
        assert np.all(np.isfinite(np.asarray(mean)))
    srv.close()  # idempotent: second close is a no-op, not an error
    with pytest.raises(ServerClosedError, match="closed"):
        srv.submit("gp", X[:2])
    with pytest.raises(ServerClosedError, match="closed"):
        srv.register("gp2", gp)


def test_default_timeout_applies_to_submits(tmp_path):
    """ctor default_timeout stamps a deadline on every submit: a request
    stuck behind a blocked worker past it expires with TimeoutError."""
    gp, X, _ = _fitted_gp(jax.random.PRNGKey(24), steps=5)
    with GPServer(default_timeout=0.05) as srv:
        srv.register("gp", gp)
        started, release = _gate_model(srv, "gp")
        first = srv.submit("gp", X[:2], timeout=30.0)  # explicit override
        assert started.wait(30)
        doomed = srv.submit("gp", X[:2])  # inherits the 50ms default
        time.sleep(0.2)  # let the deadline lapse while queued
        release.set()
        first.result(timeout=30)
        with pytest.raises(TimeoutError, match="deadline"):
            doomed.result(timeout=30)
        assert srv.metrics()["expired"] == 1


# ---------------------------------------------------------------------------
# facade posterior cache (satellite): one statistics pass per fit
# ---------------------------------------------------------------------------

def test_facade_caches_statistics_across_predict_calls():
    gp, X, Y = _fitted_gp(jax.random.PRNGKey(10), steps=5)
    calls = []
    inner = gp._stats_fn()
    gp._stats_cache = (gp.kernel, lambda *a: (calls.append(1), inner(*a))[1])
    gp.predict(X[:7])
    gp.predict(X[9:20])
    gp.posterior()
    gp.export_state()
    assert len(calls) == 1  # one O(N M^2) pass serves them all
    gp.fit(X, Y, steps=1)  # fit invalidates both caches...
    gp._stats_cache = (gp.kernel, lambda *a: (calls.append(1), inner(*a))[1])
    gp.predict(X[:7])
    assert len(calls) == 2  # ...so the next predict recomputes once


# ---------------------------------------------------------------------------
# million-point scale: update + submit without any (N, M) intermediate
# ---------------------------------------------------------------------------

def _no_nm_intermediate(fn, *args, N, M):
    """The guarantee stated once, via the analyzer: no intermediate anywhere
    in the trace scales like O(N*M) (default margin 4 reads "nothing within
    4x of an (N, M) array" — streaming would be broken)."""
    assert_no_scaling(fn, *args, axis="N", worse_than="N*M",
                      sizes={"N": N, "M": M})


def test_million_point_online_serving_round_trip():
    """The acceptance scenario: a state over 1e6 total datapoints, reached
    by an online update, matching the from-scratch refold — plus the trace
    assertion that folding a million-point chunk materializes nothing of
    size (N, M), and a live submit() round-trip against the updated state."""
    N_total, b, M, chunk = 1_000_000, 8192, 100, 8192
    n0 = N_total - b
    key = jax.random.PRNGKey(11)
    X = jax.random.uniform(key, (N_total, 1), jnp.float64, -3.0, 3.0)
    Y = jnp.sin(2.0 * X) + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), (N_total, 1), jnp.float64)
    kernel = get("rbf")(1)
    params = _params(X[:: N_total // M][:M])

    # trace-level guarantee first (traces only — nothing executes): folding
    # a MILLION-point batch into a served state stays chunk-sized
    st_small = _state_from(kernel, params, X[:512], Y[:512])

    def fold_million(st, Xb, Yb):
        return online.update(kernel, st, Xb, Yb, chunk=chunk)

    _no_nm_intermediate(fold_million, st_small, X, Y, N=N_total, M=M)

    # executed: (N_total - b) streamed base state + one online b-point fold
    # == the from-scratch build over all 1e6 points
    st0 = _state_from(kernel, params, X[:n0], Y[:n0], chunk=chunk)
    up = online.update(kernel, st0, X[n0:], Y[n0:], chunk=chunk)
    scratch = _state_from(kernel, params, X, Y, chunk=chunk)
    _assert_stats_close(up.stats, scratch.stats, rtol=1e-8, atol=1e-8)

    # live micro-batched serving against the million-point state
    with GPServer() as srv:
        srv.register("big", kernel=kernel, state=up)
        futs = [srv.submit("big", X[i * 16: (i + 1) * 16]) for i in range(8)]
        ref = serve.predict(kernel, up, X[: 8 * 16])
        for i, f in enumerate(futs):
            mean, var = f.result(timeout=60)
            np.testing.assert_allclose(
                np.asarray(mean), np.asarray(ref[0][i * 16: (i + 1) * 16]),
                rtol=1e-10, atol=1e-12)
            np.testing.assert_allclose(
                np.asarray(var), np.asarray(ref[1][i * 16: (i + 1) * 16]),
                rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# benchmark schema validation (satellite)
# ---------------------------------------------------------------------------

def test_committed_bench_files_carry_current_schema(tmp_path):
    from benchmarks.run import validate_bench_files

    names = validate_bench_files()  # the repo's committed BENCH_*.json
    assert {"BENCH_gp.json", "BENCH_serve.json"} <= set(names)

    bad = tmp_path / "BENCH_bad.json"
    bad.write_text('{"meta": {"schema_version": 0}, "rows": []}')
    with pytest.raises(ValueError, match="schema_version"):
        validate_bench_files(tmp_path)
    bad.write_text("not json")
    with pytest.raises(ValueError, match="parse"):
        validate_bench_files(tmp_path)
