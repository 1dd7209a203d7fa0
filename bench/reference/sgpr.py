"""Reference for sparse GP regression with the RBF-ARD kernel.

Exact statistics of a block of rows: K = k(X, Z), psi0 = B s^2,
Psi2 = K^T K, PsiY = K^T Y, yy = sum Y^2. Loss = -F / n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common

BLOCK = 4096


def block_stats(mm: common.Matmul):
    def stats(params: dict, dat: dict) -> dict:
        X, Y = dat["X"], dat["Y"]
        K = common.rbf(mm, params["kern"], X, params["Z"])
        return {"psi0": X.shape[0] * jnp.exp(params["kern"]["log_variance"]),
                "psi2": mm.dot(K.T, K), "psiY": mm.dot(K.T, Y),
                "yy": jnp.sum(Y * Y)}
    return stats


def _epilogue(mm: common.Matmul, cfg: dict, n: int, d: int):
    def epilogue(glob, st):
        Kuu = common.rbf(mm, glob["kern"], glob["Z"], glob["Z"])
        return -common.collapsed_bound(Kuu, st, jnp.exp(glob["log_beta"]), n,
                                       d, cfg["jitter"]) / n
    return epilogue


def value_and_grad_fn(cfg: dict, data, precision: str = "highest",
                      rows: slice = slice(None)):
    """params -> (loss, grads) over data[rows], at `precision`."""
    X, Y = (a[rows] for a in data)
    n, d = Y.shape
    size = min(BLOCK, n)
    mm = common.Matmul(precision)

    # the data goes in as arguments, not as constants of the program, so
    # that every seed reads the same compiled program from the cache
    @jax.jit
    def vg(params, X, Y):
        with jax.default_matmul_precision(precision):
            return common.streamed_value_and_grad(
                block_stats(mm), _epilogue(mm, cfg, n, d), params,
                {"X": X, "Y": Y}, size)

    return lambda params: vg(params, X, Y)
