"""Reference for the Bayesian GP-LVM with the RBF-ARD kernel.

q(x_n) = N(mu_n, diag S_n), S_n = exp(q_logS_n), prior N(0, I). The
expected statistics of a block of rows are those of Titsias & Lawrence
(2010), with a_nq = 1 / (l_q^2 + S_nq) and b_nq = 1 / (l_q^2 + 2 S_nq):

  psi0 = B s^2
  Psi1[n, m] = s^2 prod_q (1 + S_nq / l_q^2)^(-1/2)
               exp(-1/2 sum_q a_nq (mu_nq - z_mq)^2)
  Psi2 = sum_n s^4 prod_q (1 + 2 S_nq / l_q^2)^(-1/2)
         exp(-sum_q (z_mq - z_m'q)^2 / (4 l_q^2) - sum_q b_nq (mu_nq - zbar_q)^2)
         with zbar = (z_m + z_m') / 2
  PsiY = Psi1^T Y,  yy = sum Y^2,
  KL = 1/2 sum_nq (S_nq + mu_nq^2 - log S_nq - 1).

The squared distances are expanded into products over Q, as
`common.rbf` expands them. Loss = -(F - KL) / n, F the collapsed bound of
`common.collapsed_bound`. With the configuration's `jitter.shift`, the
jitter of Kuu and the floor of A = Kuu + beta Psi2 are raised to M^2 eps
of their mean diagonals: the rounding of an M x M Cholesky in float32,
below which A's pivots are not determined.

Streaming: the global parameters (kern, Z, log_beta) go to every block of
rows, the local ones (q_mu, q_logS) are sliced with the block's rows, and
their gradients come back block by block. A block of BLOCK rows holds a
few (BLOCK, M, M) float32 intermediates: 1,024 rows at M = 256 are 268 MB
each.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common

BLOCK = 1024
LOCAL = ("q_mu", "q_logS")


def _sq(mm: common.Matmul, w, A, B):
    """sum_q w_nq (A_nq - B_kq)^2 for every n and k, as products over q."""
    return (jnp.sum(w * A * A, -1)[:, None] - 2.0 * mm.dot(w * A, B.T)
            + mm.dot(w, (B * B).T))


def block_stats(mm: common.Matmul):
    def stats(glob: dict, loc: dict, Y) -> dict:
        kern, Z = glob["kern"], glob["Z"]
        var = jnp.exp(kern["log_variance"])
        l2 = jnp.exp(2.0 * kern["log_lengthscale"])
        mu, logS = loc["q_mu"], loc["q_logS"]
        S = jnp.exp(logS)
        m = Z.shape[0]

        a = 1.0 / (l2 + S)
        psi1 = var * jnp.exp(-0.5 * jnp.sum(jnp.log1p(S / l2), -1)[:, None]
                             - 0.5 * _sq(mm, a, mu, Z))

        b = 1.0 / (l2 + 2.0 * S)
        zbar = (0.5 * (Z[:, None, :] + Z[None, :, :])).reshape(m * m, -1)
        zz = jnp.sum((Z[:, None, :] - Z[None, :, :]) ** 2 / (4.0 * l2), -1)
        expo = (-0.5 * jnp.sum(jnp.log1p(2.0 * S / l2), -1)[:, None]
                - _sq(mm, b, mu, zbar))
        psi2 = var ** 2 * jnp.exp(-zz) * jnp.sum(jnp.exp(expo), 0).reshape(m, m)
        return {"psi0": Y.shape[0] * var, "psi2": psi2,
                "psiY": mm.dot(psi1.T, Y), "yy": jnp.sum(Y * Y),
                "kl": 0.5 * jnp.sum(S + mu * mu - logS - 1.0)}
    return stats


def jitter(cfg: dict, m: int) -> dict:
    """The configuration's jitter, shifted as its `shift` says."""
    jit = dict(cfg["jitter"])
    if jit.pop("shift", False):
        s = float(m * m)
        jit["kuu_rel"] = max(jit["kuu_rel"], s * float(jnp.finfo(jnp.float32).eps))
        jit["a_rel_eps"] = max(jit["a_rel_eps"], s)
    return jit


def _epilogue(mm: common.Matmul, cfg: dict, n: int, d: int):
    def epilogue(glob, st):
        Kuu = common.rbf(mm, glob["kern"], glob["Z"], glob["Z"])
        F = common.collapsed_bound(Kuu, st, jnp.exp(glob["log_beta"]), n, d,
                                   jitter(cfg, Kuu.shape[0]))
        return -(F - st["kl"]) / n
    return epilogue


def streamed_value_and_grad(block_fn, epilogue, params: dict, Y, size: int):
    """loss = epilogue(glob, sum_b block_fn(glob, loc_b, Y_b)) and its
    gradient: the global leaves' summed over blocks, the local leaves'
    block by block."""
    glob = {k: v for k, v in params.items() if k not in LOCAL}
    loc = {k: common.blocks(params[k], size) for k in LOCAL}
    yb = common.blocks(Y, size)
    first = jax.tree.map(lambda a: a[0], loc)
    zero = jax.tree.map(jnp.zeros_like,
                        jax.eval_shape(block_fn, glob, first, yb[0]))

    def fwd(acc, xs):
        lb, y = xs
        return jax.tree.map(jnp.add, acc, block_fn(glob, lb, y)), None

    stats, _ = lax.scan(fwd, zero, (loc, yb))
    loss, epi_vjp = jax.vjp(epilogue, glob, stats)
    g_glob, g_stats = epi_vjp(jnp.ones_like(loss))

    def bwd(acc, xs):
        lb, y = xs
        _, f_vjp = jax.vjp(lambda g, l: block_fn(g, l, y), glob, lb)
        dg, dl = f_vjp(g_stats)
        return jax.tree.map(jnp.add, acc, dg), dl

    g_blocks, g_loc = lax.scan(bwd, jax.tree.map(jnp.zeros_like, glob),
                               (loc, yb))
    grads = jax.tree.map(jnp.add, g_glob, g_blocks)
    grads.update({k: v.reshape(params[k].shape) for k, v in g_loc.items()})
    return loss, grads


def value_and_grad_fn(cfg: dict, data, precision: str = "highest",
                      rows: slice = slice(None)):
    """params -> (loss, grads) over data[rows], at `precision`. With a
    slice of the rows, q(X) of the other rows takes no part and gets a zero
    gradient."""
    (Y,) = data
    Y = Y[rows]
    n, d = Y.shape
    size = min(BLOCK, n)
    mm = common.Matmul(precision)

    # the data goes in as an argument, not as a constant of the program, so
    # that every seed reads the same compiled program from the cache
    @jax.jit
    def vg(params, Y):
        with jax.default_matmul_precision(precision):
            part = dict(params, **{k: params[k][rows] for k in LOCAL})
            loss, g = streamed_value_and_grad(
                block_stats(mm), _epilogue(mm, cfg, n, d), part, Y, size)
            for k in LOCAL:
                g[k] = jnp.zeros_like(params[k]).at[rows].set(g[k])
            return loss, g

    return lambda params: vg(params, Y)
