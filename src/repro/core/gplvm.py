"""Bayesian GP-LVM (paper eq. (4)) — the unsupervised model the paper's
experiments use.

X is latent with prior p(x_n) = N(0, I_Q) and factorized Gaussian variational
posterior q(x_n) = N(mu_n, diag(S_n)). The collapsed bound of svgp.py is
reused verbatim; the only changes are (a) the sufficient statistics become
expectations under q(X) (kernel.expected_suff_stats), and (b) the KL term:

    log p(Y) >= <F>_q(X) - sum_n KL(q(x_n) || p(x_n))

Both changes preserve the sum-over-n structure, so the same distributed
accumulation applies (mu, S are *local* parameters living on the shard that
owns datapoint n — exactly the paper's local/global parameter split).

Every entry point takes an optional `kernel` (any `repro.gp.kernels.Kernel`
with closed-form psi statistics); the default is the paper's RBF.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import psi_stats, svgp
from repro.gp.kernels import Kernel, default_rbf
from repro.gp.stats import ExpectedBatch, suff_stats

Params = Dict[str, jax.Array]


def init_params(
    key: jax.Array,
    Y: jax.Array,
    Q: int,
    M: int,
    *,
    init_X: jax.Array | None = None,
    kernel: Optional[Kernel] = None,
) -> Params:
    """PCA-style init of q(X) means (or user-provided), Z from q(X) samples."""
    N, D = Y.shape
    if init_X is None:
        # PCA init: project Y onto its top-Q principal directions
        Yc = Y - jnp.mean(Y, 0)
        _, _, Vt = jnp.linalg.svd(Yc, full_matrices=False)
        init_X = Yc @ Vt[:Q].T
        init_X = init_X / (jnp.std(init_X, 0) + 1e-6)
    kern = default_rbf(kernel, Q).init()
    idx = jax.random.choice(key, N, (M,), replace=N < M)
    return {
        "kern": kern,
        "Z": init_X[idx],
        "log_beta": jnp.asarray(jnp.log(100.0), jnp.float32),
        "q_mu": init_X,
        "q_logS": jnp.full((N, Q), jnp.log(0.1), jnp.float32),
    }


def kl_qp(q_mu: jax.Array, q_logS: jax.Array) -> jax.Array:
    """sum_n KL(N(mu_n, diag(S_n)) || N(0, I)) — also a plain sum over n."""
    S = jnp.exp(q_logS)
    return 0.5 * jnp.sum(S + q_mu**2 - q_logS - 1.0)


def local_stats(params: Params, Y_local: jax.Array, *,
                kernel: Optional[Kernel] = None,
                backend: str = "jnp",
                chunk: Optional[int] = None,
                bwd_backend: str = "auto") -> psi_stats.SuffStats:
    """Sufficient statistics for the local data shard, kernel-dispatched.
    `chunk=` streams the shard's datapoints (O(chunk * M) live memory);
    `bwd_backend` picks the reverse-pass implementation of the kernelized
    backends ("pallas" single-statistic ops and the "fused" op alike)."""
    kern = default_rbf(kernel, params["q_mu"].shape[1])
    S = jnp.exp(params["q_logS"])
    return suff_stats(kern, params["kern"],
                      ExpectedBatch(params["q_mu"], S, Y_local, params["Z"]),
                      backend=backend, chunk=chunk, bwd_backend=bwd_backend)


def bound(params: Params, Y: jax.Array, *, kernel: Optional[Kernel] = None,
          backend: str = "jnp", chunk: Optional[int] = None,
          bwd_backend: str = "auto") -> jax.Array:
    """Single-device (or per-shard-complete) GP-LVM evidence lower bound."""
    stats = local_stats(params, Y, kernel=kernel, backend=backend, chunk=chunk,
                        bwd_backend=bwd_backend)
    return bound_from_stats(params, stats, kl_qp(params["q_mu"], params["q_logS"]),
                            Y.shape[1], kernel=kernel)


def bound_from_stats(
    params: Params, stats: psi_stats.SuffStats, kl: jax.Array, D: int,
    *, kernel: Optional[Kernel] = None,
) -> jax.Array:
    """The indistributable epilogue: O(M^3), runs replicated after the psum."""
    kern = default_rbf(kernel, params["Z"].shape[1])
    Kuu = kern.K(params["kern"], params["Z"])
    beta = jnp.exp(params["log_beta"])
    terms = svgp.collapsed_bound(Kuu, stats, beta, D, shift=True)
    return terms.bound - kl


def loss(params: Params, Y: jax.Array, *, kernel: Optional[Kernel] = None,
         backend: str = "jnp", chunk: Optional[int] = None,
         bwd_backend: str = "auto") -> jax.Array:
    """Negative ELBO per datapoint (scale-stable objective for Adam)."""
    return -bound(params, Y, kernel=kernel, backend=backend, chunk=chunk,
                  bwd_backend=bwd_backend) / Y.shape[0]
