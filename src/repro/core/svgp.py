"""Collapsed variational bound for sparse GPs (paper eq. (2)-(3)).

Implemented via direct Cholesky of (Kuu + beta Psi2) — NOT the whitened
GPy form chol(I + beta L^-1 Psi2 L^-T): in float32 the whitening squares
Kuu's condition number and I + beta A goes numerically indefinite for
closely-spaced inducing points (NaN at step 0 of the quickstart). The
direct matrix gains PSD mass from beta Psi2 and factors robustly; the
trace term still uses chol(Kuu + jitter), whose failure mode is additive
error, not NaN. Jitter is relative to mean(diag Kuu) and dtype-aware.

`shift=True` shifts both factored matrices by M^2 eps of their mean
diagonal (`cholesky_shift`): the size of an M x M Cholesky's rounding in
that dtype, below which a pivot can go negative. The Bayesian GP-LVM needs
it in float32: as its lengthscales grow, Kuu + beta Psi2 reaches a
condition number past 1 / eps, and the default floor (100 eps) is M^2 / 100
times too small to keep its Cholesky from breaking down.

    L   = chol(Kuu + jitter I)
    LA  = chol(Kuu + beta Psi2 + jitter I)
    c   = LA^-1 PsiY                             (M, D)

    F = D N/2 log(beta / 2 pi) - D/2 (log|LA LA^T| - log|L L^T|)
        - beta/2 yy + beta^2/2 ||c||_F^2
        - beta D/2 psi0 + beta D/2 tr(L^-1 Psi2 L^-T)

The bound consumes only a `SuffStats` — it never sees the N datapoints. That
separation IS the paper's contribution: stats are produced shard-locally
(core.distributed) or on-accelerator (repro.kernels), combined by a psum, and
this O(M^3 + M^2 D) "indistributable" epilogue runs replicated on every
device (paper Fig 1b measures exactly this epilogue's share of runtime).

Gradients w.r.t. (theta, Z, beta, q(X)) come from jax.grad straight through
this function + the statistics code — the transpose of the psum reproduces the
paper's "broadcast dL/dPsi, dL/dPhi back to workers" step automatically.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.psi_stats import SuffStats

DEFAULT_JITTER = 1e-6


class BoundTerms(NamedTuple):
    bound: jax.Array
    logdet_term: jax.Array
    quad_term: jax.Array
    trace_term: jax.Array
    # epilogue intermediates reused by prediction
    L: jax.Array  # chol(Kuu + jitter)
    LA: jax.Array  # chol(Kuu + beta Psi2 + jitter)
    c: jax.Array  # LA^-1 PsiY


class PosteriorFactors(NamedTuple):
    """The O(M^3) factorization epilogue on its own: everything prediction
    (and the serving layer's cached `PosteriorState`) needs, without the
    bound value. `collapsed_bound` builds on exactly these factors, so a
    posterior refold after an online statistics update is the same code
    path the training loss exercises."""

    L: jax.Array  # chol(Kuu + jitter)
    LA: jax.Array  # chol(Kuu + beta Psi2 + jitter)
    c: jax.Array  # LA^-1 PsiY


def _jitter_eff(Kuu: jax.Array, jitter: float) -> jax.Array:
    """Relative, dtype-aware jitter: f32 needs ~100x f64's."""
    scale = jnp.mean(jnp.diagonal(Kuu))
    boost = 1.0 if Kuu.dtype == jnp.float64 else 100.0
    return jitter * boost * jnp.maximum(scale, 1e-12)


def cholesky_shift(M: int, dtype) -> float:
    """M^2 eps: the share of mean(diag A) by which an M x M Cholesky's
    rounding can move A's eigenvalues (backward error ~ M eps tr A). A
    diagonal shift of that size keeps every pivot positive."""
    return M * M * float(jnp.finfo(dtype).eps)


def posterior_factors(
    Kuu: jax.Array,
    stats: SuffStats,
    beta: jax.Array,
    *,
    jitter: float = DEFAULT_JITTER,
    shift: bool = False,
) -> PosteriorFactors:
    """Factorize the posterior epilogue from sufficient statistics alone:
    L = chol(Kuu + jit I), LA = chol(Kuu + beta Psi2 + jit I), c = LA^-1 PsiY.
    O(M^3 + M^2 D); never sees the N datapoints. `shift=True` raises the
    jitter and A's floor to `cholesky_shift` of their mean diagonals."""
    dtype = Kuu.dtype
    M = Kuu.shape[0]
    eye = jnp.eye(M, dtype=dtype)
    jit_eff = _jitter_eff(Kuu, jitter)
    eps = jnp.finfo(dtype).eps
    floor = 100.0 * eps
    if shift:
        floor = max(floor, cholesky_shift(M, dtype))
        jit_eff = jnp.maximum(jit_eff, floor * jnp.mean(jnp.diagonal(Kuu)))

    # ONE consistent jittered model: every consumer below works on
    # Kuu_j = Kuu + jit I (mixing different jitters across terms breaks the
    # lower-bound property when Kuu is near-singular, e.g. Z = X).
    Kuu_j = Kuu + jit_eff * eye
    L = jnp.linalg.cholesky(Kuu_j)
    psi2 = 0.5 * (stats.psi2 + stats.psi2.T)
    Abig = Kuu_j + beta * psi2
    # eps-scaled floor for Psi2's own roundoff (~eps * ||Psi2||): negligible
    # in f64 (preserves the bound to ~1e-10); in f32, `shift` sizes it to
    # the factorization's own rounding
    LA = jnp.linalg.cholesky(Abig + floor * jnp.mean(jnp.diagonal(Abig)) * eye)
    c = jax.scipy.linalg.solve_triangular(LA, stats.psiY, lower=True)  # (M, D)
    return PosteriorFactors(L, LA, c)


def collapsed_bound(
    Kuu: jax.Array,
    stats: SuffStats,
    beta: jax.Array,
    D: int,
    *,
    jitter: float = DEFAULT_JITTER,
    shift: bool = False,
) -> BoundTerms:
    """The paper's eq. (3), evaluated from sufficient statistics.

    Args:
      Kuu: (M, M) inducing covariance k(Z, Z).
      stats: accumulated sufficient statistics (possibly psum'd).
      beta: noise precision (scalar).
      D: number of output dimensions.
      shift: shift both factored matrices by `cholesky_shift`.
    """
    with jax.named_scope("gp.epilogue"):
        N = stats.n
        L, LA, c = posterior_factors(Kuu, stats, beta, jitter=jitter,
                                     shift=shift)
        psi2 = 0.5 * (stats.psi2 + stats.psi2.T)

        # log|Kuu + beta Psi2| - log|Kuu| (== log|B| of the whitened form)
        logdetB = 2.0 * (jnp.sum(jnp.log(jnp.diagonal(LA)))
                         - jnp.sum(jnp.log(jnp.diagonal(L))))
        # tr(Kuu^-1 Psi2) via the (jittered) Kuu factor
        tmp = jax.scipy.linalg.solve_triangular(L, psi2, lower=True)
        A = jax.scipy.linalg.solve_triangular(L, tmp.T, lower=True).T

        logdet_term = 0.5 * D * N * jnp.log(beta / (2.0 * jnp.pi)) - 0.5 * D * logdetB
        quad_term = -0.5 * beta * stats.yy + 0.5 * beta**2 * jnp.sum(c * c)
        trace_term = -0.5 * beta * D * stats.psi0 + 0.5 * beta * D * jnp.trace(A)

        bound = logdet_term + quad_term + trace_term
        return BoundTerms(bound, logdet_term, quad_term, trace_term, L, LA, c)


class Posterior(NamedTuple):
    """Optimal q(u) = N(mean_u, cov_u) implied by the collapsed bound."""

    mean_u: jax.Array  # (M, D)
    cov_u: jax.Array  # (M, M)
    Kuu_inv_mean: jax.Array  # (M, D)  Kuu^-1 mean_u, cached for prediction
    L: jax.Array
    LA: jax.Array


def optimal_qu(terms: "BoundTerms | PosteriorFactors", beta: jax.Array) -> Posterior:
    """q(u): mean = beta Kuu (Kuu + beta Psi2)^-1 PsiY,
    cov = Kuu (Kuu + beta Psi2)^-1 Kuu — in Cholesky factors.

    Accepts either the full `BoundTerms` (training path) or the bare
    `PosteriorFactors` (serving path) — both carry (L, LA, c)."""
    L, LA, c = terms.L, terms.LA, terms.c
    # Kuu^-1 mean_u = beta (Kuu + beta Psi2)^-1 PsiY = beta LA^-T c
    Kuu_inv_mean = beta * jax.scipy.linalg.solve_triangular(LA, c, lower=True, trans=1)
    Kuu = L @ L.T
    mean_u = Kuu @ Kuu_inv_mean
    # cov_u = Kuu (Kuu + beta Psi2)^-1 Kuu = (LA^-1 Kuu)^T (LA^-1 Kuu)
    LAiK = jax.scipy.linalg.solve_triangular(LA, Kuu, lower=True)
    cov_u = LAiK.T @ LAiK
    return Posterior(mean_u, cov_u, Kuu_inv_mean, L, LA)


def predict_f(
    post: Posterior,
    Ksu: jax.Array,
    Kss_diag: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Posterior p(f*) at test points: mean (N*, D) and marginal var (N*,).

    mean = Ksu Kuu^-1 mean_u
    var  = Kss_diag - diag(Ksu [Kuu^-1 - (Kuu + beta Psi2)^-1] Kus)
    """
    mean = Ksu @ post.Kuu_inv_mean
    v1 = jax.scipy.linalg.solve_triangular(post.L, Ksu.T, lower=True)
    v2 = jax.scipy.linalg.solve_triangular(post.LA, Ksu.T, lower=True)
    var = Kss_diag - jnp.sum(v1 * v1, axis=0) + jnp.sum(v2 * v2, axis=0)
    return mean, var


def predict_f_full(
    post: Posterior,
    Ksu: jax.Array,
    Kss: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Posterior p(f*) with the FULL (N*, N*) covariance:

    mean = Ksu Kuu^-1 mean_u
    cov  = Kss - Ksu [Kuu^-1 - (Kuu + beta Psi2)^-1] Kus

    Same triangular-solve structure as `predict_f` (no new factorization);
    the serving layer uses this for `diag=False` requests.
    """
    mean = Ksu @ post.Kuu_inv_mean
    v1 = jax.scipy.linalg.solve_triangular(post.L, Ksu.T, lower=True)
    v2 = jax.scipy.linalg.solve_triangular(post.LA, Ksu.T, lower=True)
    cov = Kss - v1.T @ v1 + v2.T @ v2
    return mean, cov


def exact_gp_log_marginal(
    Kff: jax.Array, Y: jax.Array, beta: jax.Array, *, jitter: float = DEFAULT_JITTER
) -> jax.Array:
    """O(N^3) exact GP log marginal likelihood — the oracle the collapsed
    bound must lower-bound (tests) and converge to as Z -> X."""
    N, D = Y.shape
    Ky = Kff + (1.0 / beta + jitter) * jnp.eye(N, dtype=Kff.dtype)
    L = jnp.linalg.cholesky(Ky)
    alpha = jax.scipy.linalg.solve_triangular(L, Y, lower=True)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
    return -0.5 * D * N * jnp.log(2.0 * jnp.pi) - 0.5 * D * logdet - 0.5 * jnp.sum(alpha**2)
