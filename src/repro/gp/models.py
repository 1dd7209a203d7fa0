"""GPy-style model facades over the distributed collapsed bound.

    gp = SparseGPRegression(kernel=get("rbf")(1), M=32, mesh=make_gp_mesh())
    gp.fit(X, Y, optimizer="adam", steps=300)
    mean, var = gp.predict(Xt)

The facades own exactly the wiring `examples/quickstart.py` used to hand-roll:
parameter init, the (optionally distributed) loss, the optimizer driver, and
the posterior/prediction epilogue. The math stays where it was — svgp.py for
the bound, the kernel objects for statistics, core.distributed for the
shard_map+psum decomposition — so the facade path and the hand-wired path
produce bit-identical losses.

`mesh=` selects the paper's data-parallel path (shard_map over the data axes,
one psum of the sufficient statistics); `backend=` routes the statistics
through Pallas TPU kernels ("pallas") or the fused suffstats op ("fused" —
expected statistics for the GP-LVM, exact ones for regression via S -> 0);
`bwd_backend=` picks the reverse-pass implementation of the kernelized
backends — the fused op and the single-statistic pallas ops all backward
through hand-derived Pallas reverse kernels or their streaming jnp twins
("auto" dispatches like the forward); `chunk=` streams the statistics over
N in chunks of that size (or `chunk="auto"`, sized by the `repro.tune`
autotuner) so
training AND prediction peak at O(chunk * M + M^2) memory regardless of N.
All of these come from the constructor so serving/config code can pick them
by string/int without touching model internals. See docs/api.md for the
full public surface and docs/architecture.md for how the layers fit.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import distributed, gplvm, inference, svgp
from repro.gp.kernels import Kernel, RBF, default_rbf
from repro.gp.stats import ExactBatch, suff_stats

Params = Dict[str, jax.Array]

_OPTIMIZERS = ("adam", "lbfgs")


def _as_2d(Y: jax.Array) -> jax.Array:
    return Y[:, None] if Y.ndim == 1 else Y


def _place_data(mesh: Mesh, *arrays: jax.Array) -> tuple:
    """Shard per-datapoint arrays over the mesh's data axes, as the
    shard_map'd losses expect, so no device holds the whole data set."""
    return tuple(jax.device_put(a, distributed.data_sharded(mesh))
                 for a in arrays)


def _pick_inducing(X: jax.Array, M: int) -> jax.Array:
    """Every (N // M)-th datapoint — the quickstart's deterministic subset."""
    N = X.shape[0]
    if M >= N:
        return X
    return X[:: max(N // M, 1)][:M]


class _CollapsedGPModel:
    """Shared facade plumbing: kernel/mesh/backend/chunk state + optimizer
    driver + the (possibly distributed, possibly streaming) posterior
    statistics pass."""

    # whether the epilogue shifts its factored matrices by the Cholesky's
    # rounding (`svgp.cholesky_shift`), as the model's loss does
    _shift = False

    def __init__(self, kernel: Optional[Kernel], M: int, *,
                 mesh: Optional[Mesh] = None, backend: str = "jnp",
                 chunk: Optional[Union[int, str]] = None,
                 bwd_backend: str = "auto"):
        self.kernel = kernel
        self.M = int(M)
        self.mesh = mesh
        self.backend = backend
        self.bwd_backend = bwd_backend
        # chunk: None (one shot), a positive int, or "auto" (resolved by the
        # repro.tune autotuner inside gp.stats.streaming_suff_stats)
        if chunk is None or chunk == "auto":
            self.chunk = chunk
        elif isinstance(chunk, str):
            raise ValueError(
                f'chunk must be None, a positive int or "auto", got {chunk!r}')
        else:
            self.chunk = int(chunk)
        self.params: Optional[Params] = None
        self.history: list = []
        self._loss_cache = None  # (kernel, built_loss): rebuilt if kernel changes
        self._stats_cache = None  # (kernel, built_stats_fn)
        self._posterior_cache: Optional[svgp.Posterior] = None  # cleared by fit
        self._stats_value_cache = None  # fitted-data SuffStats, cleared by fit

    # -- subclass hooks ----------------------------------------------------
    def _build_loss(self):
        raise NotImplementedError

    def _build_stats(self):
        raise NotImplementedError

    def _loss_fn(self):
        """Build the (possibly shard_map'd) loss once per kernel — repeated
        elbo()/fit() calls reuse the same closure so jit caching holds."""
        if self._loss_cache is None or self._loss_cache[0] is not self.kernel:
            self._loss_cache = (self.kernel, self._build_loss())
        return self._loss_cache[1]

    def _stats_fn(self):
        """The posterior/predict-time statistics pass, built once per kernel.
        With `mesh=` it shard_maps + psums like the training losses (the
        ROADMAP's distributed-prediction item); with `chunk=` it streams."""
        if self._stats_cache is None or self._stats_cache[0] is not self.kernel:
            self._stats_cache = (self.kernel, jax.jit(self._build_stats()))
        return self._stats_cache[1]

    def _require_fitted(self):
        if self.params is None:
            raise RuntimeError(f"{type(self).__name__} is not fitted yet — call .fit() first")

    def _optimize(self, loss_fn, params: Params, data: tuple, *, optimizer: str,
                  steps: int, lr: float, log_every: int) -> Params:
        self._posterior_cache = None
        self._stats_value_cache = None
        if optimizer == "adam":
            params, self.history = inference.fit_adam(
                loss_fn, params, data, steps=steps, lr=lr, log_every=log_every)
        elif optimizer == "lbfgs":
            params, final = inference.fit_lbfgs(loss_fn, params, data, maxiter=steps)
            self.history = [final]
        else:
            raise ValueError(f"optimizer must be one of {_OPTIMIZERS}, got {optimizer!r}")
        return params

    def _fitted_stats(self):
        """SuffStats of the fitted data at the fitted params, computed once
        per fit (the O(N M^2) pass) and shared by `posterior()` and
        `export_state()`. Invalidated by `fit()`."""
        self._require_fitted()
        if self._stats_value_cache is None:
            self._stats_value_cache = self._stats_fn()(self.params, *self._data)
        return self._stats_value_cache

    def posterior(self) -> svgp.Posterior:
        """Optimal q(u) implied by the collapsed bound at the fitted params.
        Cached: the O(N M^2) statistics pass and the O(M^3) factorization
        run once per fit, not per predict call — sharded over the mesh
        and/or streamed by `chunk=`, exactly like the training losses."""
        self._require_fitted()
        if self._posterior_cache is not None:
            return self._posterior_cache
        p = self.params
        beta = jnp.exp(p["log_beta"])
        factors = svgp.posterior_factors(self.kernel.K(p["kern"], p["Z"]),
                                         self._fitted_stats(), beta,
                                         shift=self._shift)
        self._posterior_cache = svgp.optimal_qu(factors, beta)
        return self._posterior_cache

    def export_state(self):
        """Freeze the fitted model into a `repro.serve.PosteriorState`: the
        Cholesky factors, woodbury vector, hyperparameters, and the raw
        `SuffStats` monoid — everything `repro.serve` needs to predict in
        O(M B + M^2 B) and to absorb new data without the training set."""
        from repro.serve.state import build_state

        self._require_fitted()
        return build_state(self.kernel, self.params, self._fitted_stats(),
                           shift=self._shift)

    def elbo(self) -> float:
        """Evidence lower bound (total, not per-datapoint) on the training data."""
        self._require_fitted()
        loss = self._loss_fn()
        n = self._data[0].shape[0]
        return float(-loss(self.params, *self._data) * n)


class SparseGPRegression(_CollapsedGPModel):
    """Sparse GP regression on the collapsed (Titsias) bound, paper eq. (2)-(3).

    Args:
      kernel: any `repro.gp.kernels.Kernel`; default RBF (inferred input dim).
      M: number of inducing points (initialized as a subset of X).
      mesh: optional jax Mesh — statistics shard over its data axes and merge
        with one psum (the paper's MPI scheme); None = single-device math.
      backend: "jnp" | "pallas" | "fused" statistics path ("fused" rides the
        fused suffstats kernel with S -> 0, so the supervised hot path is
        one kernelized pass over N in both directions of differentiation).
      chunk: stream the O(N) statistics in chunks of this size (training and
        prediction both peak at O(chunk * M + M^2) memory); None = one shot.
      bwd_backend: "auto" | "pallas" | "jnp" — reverse-pass implementation
        of the kernelized backends ("pallas" and "fused"; ignored by "jnp").
    """

    def __init__(self, kernel: Optional[Kernel] = None, M: int = 32, *,
                 mesh: Optional[Mesh] = None, backend: str = "jnp",
                 chunk: Optional[Union[int, str]] = None,
                 bwd_backend: str = "auto"):
        super().__init__(kernel, M, mesh=mesh, backend=backend, chunk=chunk,
                         bwd_backend=bwd_backend)
        self._data: Optional[Tuple[jax.Array, jax.Array]] = None

    def _build_loss(self):
        if self.mesh is not None:
            return distributed.sgpr_loss_dist(self.mesh, kernel=self.kernel,
                                              backend=self.backend,
                                              chunk=self.chunk,
                                              bwd_backend=self.bwd_backend)
        kernel, backend, chunk = self.kernel, self.backend, self.chunk
        bwd_backend = self.bwd_backend

        def loss(params: Params, X: jax.Array, Y: jax.Array) -> jax.Array:
            kern = default_rbf(kernel, params["Z"].shape[1])
            stats = suff_stats(kern, params["kern"],
                               ExactBatch(X, Y, params["Z"]), backend=backend,
                               chunk=chunk, bwd_backend=bwd_backend)
            Kuu = kern.K(params["kern"], params["Z"])
            terms = svgp.collapsed_bound(Kuu, stats, jnp.exp(params["log_beta"]),
                                         Y.shape[1])
            return -terms.bound / stats.n

        return loss

    def _build_stats(self):
        if self.mesh is not None:
            return distributed.sgpr_stats_dist(self.mesh, kernel=self.kernel,
                                               backend=self.backend,
                                               chunk=self.chunk,
                                               bwd_backend=self.bwd_backend)
        kernel, backend, chunk = self.kernel, self.backend, self.chunk
        bwd_backend = self.bwd_backend

        def stats_fn(params: Params, X: jax.Array, Y: jax.Array):
            kern = default_rbf(kernel, params["Z"].shape[1])
            return suff_stats(kern, params["kern"],
                              ExactBatch(X, Y, params["Z"]), backend=backend,
                              chunk=chunk, bwd_backend=bwd_backend)

        return stats_fn

    def init_params(self, X: jax.Array, Y: jax.Array, *,
                    log_beta: float = 2.0) -> Params:
        if self.kernel is None:
            self.kernel = RBF(X.shape[1])
        return {
            "kern": self.kernel.init(),
            "Z": _pick_inducing(X, self.M),
            "log_beta": jnp.asarray(log_beta, X.dtype),
        }

    def fit(self, X: jax.Array, Y: jax.Array, *, optimizer: str = "adam",
            steps: int = 300, lr: float = 3e-2, log_every: int = 0,
            params: Optional[Params] = None) -> "SparseGPRegression":
        with inference.fit_span(type(self).__name__, optimizer, steps,
                                rows=X.shape[0]):
            Y = _as_2d(Y)
            if params is None:
                params = self.init_params(X, Y)
            elif self.kernel is None:
                self.kernel = RBF(params["Z"].shape[1])
            if self.mesh is not None:
                X, Y = _place_data(self.mesh, X, Y)
                params = distributed.shard_gp_params(params, self.mesh)
            self._data = (X, Y)
            self.params = self._optimize(self._loss_fn(), params, (X, Y),
                                         optimizer=optimizer, steps=steps,
                                         lr=lr, log_every=log_every)
        return self

    def predict(self, Xt: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Posterior mean (N*, D) and marginal variance (N*,) of f at Xt."""
        self._require_fitted()
        p = self.params
        post = self.posterior()
        return svgp.predict_f(post, self.kernel.K(p["kern"], Xt, p["Z"]),
                              self.kernel.Kdiag(p["kern"], Xt))


class BayesianGPLVM(_CollapsedGPModel):
    """Bayesian GP-LVM (paper eq. (4)): latent X with factorized Gaussian q(X).

    Args:
      kernel: kernel with closed-form psi statistics (RBF/Linear or their
        Sum/Product composites); default RBF(Q).
      Q: latent dimensionality.
      M: number of inducing points.
      mesh / backend / chunk / bwd_backend: as for SparseGPRegression;
        backend="fused" is the fused suffstats op (one pass over N producing
        psi2/psiY together), backend="pallas" the single-statistic
        psi1/psi2 kernels — both differentiable via the hand-derived
        reverse passes, kernelized when bwd_backend is "auto"/"pallas".
    """

    _shift = True

    def __init__(self, kernel: Optional[Kernel] = None, M: int = 100,
                 Q: Optional[int] = None, *,
                 mesh: Optional[Mesh] = None, backend: str = "jnp",
                 chunk: Optional[Union[int, str]] = None,
                 bwd_backend: str = "auto"):
        super().__init__(kernel, M, mesh=mesh, backend=backend, chunk=chunk,
                         bwd_backend=bwd_backend)
        if kernel is not None and Q is not None and Q != kernel.input_dim:
            raise ValueError(
                f"Q={Q} conflicts with kernel.input_dim={kernel.input_dim}; "
                f"pass one or make them agree"
            )
        self.Q = kernel.input_dim if kernel is not None else (Q if Q is not None else 1)
        self._data: Optional[Tuple[jax.Array]] = None

    def _build_loss(self):
        if self.mesh is not None:
            return distributed.gplvm_loss_dist(self.mesh, kernel=self.kernel,
                                               backend=self.backend,
                                               chunk=self.chunk,
                                               bwd_backend=self.bwd_backend)
        return functools.partial(gplvm.loss, kernel=self.kernel,
                                 backend=self.backend, chunk=self.chunk,
                                 bwd_backend=self.bwd_backend)

    def _build_stats(self):
        if self.mesh is not None:
            return distributed.gplvm_stats_dist(self.mesh, kernel=self.kernel,
                                                backend=self.backend,
                                                chunk=self.chunk,
                                                bwd_backend=self.bwd_backend)
        return functools.partial(gplvm.local_stats, kernel=self.kernel,
                                 backend=self.backend, chunk=self.chunk,
                                 bwd_backend=self.bwd_backend)

    def fit(self, Y: jax.Array, *, optimizer: str = "adam", steps: int = 400,
            lr: float = 2e-2, log_every: int = 0,
            init_X: Optional[jax.Array] = None,
            key: Optional[jax.Array] = None,
            params: Optional[Params] = None) -> "BayesianGPLVM":
        with inference.fit_span(type(self).__name__, optimizer, steps,
                                rows=Y.shape[0]):
            Y = _as_2d(Y)
            if self.kernel is None:
                self.kernel = RBF(self.Q)
            if params is None:
                params = gplvm.init_params(
                    key if key is not None else jax.random.PRNGKey(0),
                    np.asarray(Y), self.Q, self.M, init_X=init_X,
                    kernel=self.kernel)
            if self.mesh is not None:
                (Y,) = _place_data(self.mesh, Y)
                params = distributed.shard_gp_params(params, self.mesh)
            self._data = (Y,)
            self.params = self._optimize(self._loss_fn(), params, (Y,),
                                         optimizer=optimizer, steps=steps,
                                         lr=lr, log_every=log_every)
        return self

    def latent(self) -> Tuple[jax.Array, jax.Array]:
        """Variational posterior over the latents: (q_mu, q_S)."""
        self._require_fitted()
        return self.params["q_mu"], jnp.exp(self.params["q_logS"])

    def predict(self, Xstar: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Decode latent coordinates Xstar to data space: mean (N*, D), var (N*,)."""
        self._require_fitted()
        p = self.params
        post = self.posterior()
        return svgp.predict_f(post, self.kernel.K(p["kern"], Xstar, p["Z"]),
                              self.kernel.Kdiag(p["kern"], Xstar))


# ---------------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------------

_BACKENDS = ("collapsed", "temporal")


def regression(kernel: Optional[Kernel] = None, *, backend: str = "collapsed",
               **kwargs):
    """GP regression facade picked by compute backend.

    backend="collapsed" (default) -> `SparseGPRegression`: the paper's
    distributed collapsed bound, any kernel/input_dim, O(N M^2) via
    inducing points; kwargs = (M, mesh, backend, chunk, bwd_backend) —
    note the statistics-path knob is the SparseGPRegression constructor's
    own `backend=`, spelled `stats_backend=` here to avoid clashing.

    backend="temporal" -> `repro.temporal.TemporalGPRegression`: exact
    state-space inference for 1-D stationary kernels (Matern family and
    Sum/Product of it — `kernel.supports_sde()`), O(N) with a parallel
    associative-scan path; kwargs = (parallel,).

    Fails fast with the capability error of the chosen backend (e.g. an
    RBF kernel under backend="temporal", or psi-less Materns in a GP-LVM).
    """
    if backend == "collapsed":
        if "stats_backend" in kwargs:
            kwargs["backend"] = kwargs.pop("stats_backend")
        return SparseGPRegression(kernel, **kwargs)
    if backend == "temporal":
        from repro.temporal import TemporalGPRegression

        return TemporalGPRegression(kernel, **kwargs)
    raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
