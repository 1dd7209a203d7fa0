"""Cached posterior state for online serving (paper §2, pushed to its
logical conclusion).

The collapsed bound consumes only the O(M^2) `SuffStats` summary, and the
posterior epilogue (`svgp.posterior_factors`) is a pure function of that
summary — so a *fitted* model is fully described by

    PosteriorState = (kernel hyperparams, Z, log_beta,
                      SuffStats,                      # the raw monoid
                      L, LA, Kuu_inv_mean)            # factorized epilogue

Everything per-request is then O(M B + M^2 B): one cross-covariance block,
two triangular solves, no Cholesky. The raw `SuffStats` rides along so the
state can absorb new data (`repro.serve.online.update`) or shed old data
(`downdate`) and refactorize in O(M^3) without ever revisiting the training
set — the monoid structure that makes the paper's MPI decomposition work is
exactly what makes online serving work.

The kernel OBJECT is deliberately not a field: `PosteriorState` is a plain
pytree (jit-traceable, checkpointable, psum-able), and kernels are static
code, not data. Every function here takes the kernel alongside the state;
`GPServer` (repro.serve.server) pairs them up under a registered name.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import svgp
from repro.core.psi_stats import SuffStats
from repro.gp.kernels import Kernel

Params = Dict[str, jax.Array]


class PosteriorState(NamedTuple):
    """Everything a fitted collapsed-bound GP needs to serve and to learn
    online. A pure pytree of arrays (see module docstring)."""

    kern: Params  # kernel hyperparameters (log-transformed)
    Z: jax.Array  # (M, Q) inducing inputs
    log_beta: jax.Array  # scalar log noise precision
    stats: SuffStats  # the raw sufficient-statistics monoid
    L: jax.Array  # (M, M) chol(Kuu + jitter)
    LA: jax.Array  # (M, M) chol(Kuu + beta Psi2 + jitter)
    Kuu_inv_mean: jax.Array  # (M, D) woodbury vector Kuu^-1 mean_u

    @property
    def M(self) -> int:
        return self.Z.shape[0]

    @property
    def D(self) -> int:
        return self.Kuu_inv_mean.shape[1]

    @property
    def nbytes(self) -> int:
        """Resident bytes of the whole state pytree — what the server's
        byte-budgeted LRU charges per model. Constant for a registration:
        every field's shape is fixed by (M, Q, D), and online
        update/downdate swap same-shaped arrays."""
        return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(self)))


def build_state(kernel: Kernel, params: Params, stats: SuffStats, *,
                jitter: float = svgp.DEFAULT_JITTER,
                shift: bool = False) -> PosteriorState:
    """The O(M^3) refold: statistics -> factorized posterior state.

    `params` needs the model keys ("kern", "Z", "log_beta"); extra keys
    (e.g. the GP-LVM's q(X)) are ignored — the state never holds per-
    datapoint parameters. Used both at export time (facade
    `export_state()`) and after every online update/downdate. `shift` is
    `svgp.posterior_factors`'s: the GP-LVM facade exports with it, as its
    loss and `posterior()` factor; the online refolds use the default.
    """
    kern_p, Z, log_beta = params["kern"], params["Z"], params["log_beta"]
    beta = jnp.exp(log_beta)
    Kuu = kernel.K(kern_p, Z)
    factors = svgp.posterior_factors(Kuu, stats, beta, jitter=jitter,
                                     shift=shift)
    post = svgp.optimal_qu(factors, beta)
    return PosteriorState(kern=kern_p, Z=Z, log_beta=log_beta, stats=stats,
                          L=post.L, LA=post.LA, Kuu_inv_mean=post.Kuu_inv_mean)


def _as_posterior(state: PosteriorState) -> svgp.Posterior:
    """View the state through the svgp.Posterior lens prediction expects.
    mean_u / cov_u are not needed by predict_f — fill with the woodbury
    vector's shape-compatible factors to keep the NamedTuple total."""
    return svgp.Posterior(mean_u=state.Kuu_inv_mean, cov_u=state.LA,
                          Kuu_inv_mean=state.Kuu_inv_mean,
                          L=state.L, LA=state.LA)


def _predict_closure(kernel: Kernel, diag: bool):
    """The (unjitted) predict epilogue closed over a kernel. `GPServer`
    entries jit their own copy so dropping a registration frees its XLA
    executables; the module-level `predict` shares one via the lru cache
    below (jit adds the per-shape level in both cases)."""

    def fn(state: PosteriorState, Xt: jax.Array):
        Ksu = kernel.K(state.kern, Xt, state.Z)
        post = _as_posterior(state)
        if diag:
            return svgp.predict_f(post, Ksu, kernel.Kdiag(state.kern, Xt))
        return svgp.predict_f_full(post, Ksu, kernel.K(state.kern, Xt))

    return fn


@functools.lru_cache(maxsize=None)
def _predict_fn(kernel: Kernel, diag: bool):
    """One jitted predict closure per (kernel, diag), for the functional
    `predict` API. Process-lifetime cache — value-hashable kernels (the
    frozen dataclasses) share entries, so repeated `get("rbf")(Q)` lookups
    cost one compile."""
    return jax.jit(_predict_closure(kernel, bool(diag)))


def predict(kernel: Kernel, state, Xt: jax.Array, *,
            diag: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Posterior p(f*) at Xt from the cached state: mean (B, D) plus either
    the marginal variance (B,) (`diag=True`) or the full (B, B) covariance.

    For a `PosteriorState`: O(M B + M^2 B) per call — cross-covariances and
    triangular solves against the cached Cholesky factors; no per-request
    factorization. For a `repro.temporal.TemporalState`: O(B d^3) marginal
    forecasts from the terminal filtered state (diag only — per-row
    forecasts are independent, so there is no full joint to return without
    the training timeline; use `TemporalGPRegression.predict`). The jitted
    closure is cached per (kernel, diag) either way, so repeated calls at
    the same batch shape reuse one XLA executable.
    """
    from repro.temporal.model import TemporalState, forecast

    if isinstance(state, TemporalState):
        if not diag:
            raise ValueError(
                "diag=False (full predictive covariance) is not available "
                "for a TemporalState: the served forecast state carries "
                "per-timestamp marginals only; use "
                "TemporalGPRegression.predict on the fitted model for "
                "smoothed joint structure")
        return forecast(kernel, state, Xt)
    return _predict_fn(kernel, bool(diag))(state, Xt)
