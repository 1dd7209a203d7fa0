"""Plain float32 references of the sparse-GP mathematics.

Nothing here imports the program or takes anything it made. Each function
is the textbook form: the RBF-ARD covariance, the collapsed bound of
Titsias (2009) from sufficient statistics, Adam as Kingma & Ba (2015) state
it in their section 2.

The work over N is streamed in blocks of rows, so a reference at the timed
sizes fits beside what the run already holds. Gradients are taken in two
passes over the blocks: the forward sum of the per-block statistics, the
VJP of the O(M^3) epilogue, then one VJP per block with the epilogue's
cotangent.

Every matrix product goes through a `Matmul`: "highest" is float32 (six
bfloat16 passes on a TPU), the reference's precision; "high" is three
bfloat16 passes with float32 accumulation, the control's. "high" is written
out as those three products, so it reads the same on a TPU and on a CPU,
whose backend ignores precision settings. The factorizations and
triangular solves run under `jax.default_matmul_precision` of the same name.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LOG_2PI = math.log(2.0 * math.pi)


PRECISIONS = ("highest", "high")


class Matmul:
    """Products at one precision: `dot(a, b)` and `einsum(spec, a, b)`."""

    def __init__(self, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.precision = precision

    def _split(self, *xs):
        hi = [x.astype(jnp.bfloat16) for x in xs]
        lo = [(x - h.astype(x.dtype)).astype(jnp.bfloat16)
              for x, h in zip(xs, hi)]
        return hi, lo

    def einsum(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        if self.precision == "highest":
            return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
        (ah, bh), (al, bl) = self._split(a, b)
        f = lambda x, y: jnp.einsum(spec, x, y,
                                    preferred_element_type=jnp.float32)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    def dot(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self.einsum("ij,jk->ik", a, b)


def rbf(mm: Matmul, kern: dict, A: jax.Array, B: jax.Array) -> jax.Array:
    """k(a, b) = s^2 exp(-|a - b|^2_l / 2), distances expanded into a product."""
    ls = jnp.exp(kern["log_lengthscale"])
    As, Bs = A / ls, B / ls
    d2 = (jnp.sum(As * As, -1)[:, None] + jnp.sum(Bs * Bs, -1)[None, :]
          - 2.0 * mm.dot(As, Bs.T))
    return jnp.exp(kern["log_variance"]) * jnp.exp(-0.5 * jnp.maximum(d2, 0.0))


def _jittered(Kuu: jax.Array, psi2: jax.Array, beta, jitter: dict):
    """Kuu + j I and A = Kuu + j I + beta Psi2 + a I, as the model states."""
    eye = jnp.eye(Kuu.shape[0], dtype=Kuu.dtype)
    Kj = Kuu + jitter["kuu_rel"] * jnp.mean(jnp.diagonal(Kuu)) * eye
    A = Kj + beta * 0.5 * (psi2 + psi2.T)
    eps = jnp.finfo(Kuu.dtype).eps
    A = A + jitter["a_rel_eps"] * eps * jnp.mean(jnp.diagonal(A)) * eye
    return Kj, A


def collapsed_bound(Kuu, st: dict, beta, n: int, d: int, jitter: dict):
    """Titsias's collapsed bound F from statistics psi0, psi2, psiY, yy:

    F = n d/2 log(beta / 2 pi) - d/2 (log|A| - log|Kj|) - beta/2 yy
        + beta^2/2 psiY^T A^-1 psiY - beta d/2 psi0 + beta d/2 tr(Kj^-1 Psi2)
    """
    Kj, A = _jittered(Kuu, st["psi2"], beta, jitter)
    L = jnp.linalg.cholesky(Kj)
    LA = jnp.linalg.cholesky(A)
    c = jax.scipy.linalg.solve_triangular(LA, st["psiY"], lower=True)
    logdet = 2.0 * (jnp.sum(jnp.log(jnp.diagonal(LA)))
                    - jnp.sum(jnp.log(jnp.diagonal(L))))
    W = jax.scipy.linalg.solve_triangular(L, 0.5 * (st["psi2"] + st["psi2"].T),
                                          lower=True)
    W = jax.scipy.linalg.solve_triangular(L, W.T, lower=True)
    return (0.5 * d * n * (jnp.log(beta) - LOG_2PI) - 0.5 * d * logdet
            - 0.5 * beta * st["yy"] + 0.5 * beta ** 2 * jnp.sum(c * c)
            - 0.5 * beta * d * st["psi0"] + 0.5 * beta * d * jnp.trace(W))


def blocks(a: jax.Array, size: int) -> jax.Array:
    n = a.shape[0]
    if n % size:
        raise ValueError(f"{n} rows do not split into blocks of {size}")
    return a.reshape((n // size, size) + a.shape[1:])


def streamed_value_and_grad(block_stats, epilogue, params: dict, data: dict,
                            size: int):
    """loss = epilogue(params, sum_b block_stats(params, data_b)) and its
    gradient with respect to `params`, over blocks of `size` rows."""
    db = jax.tree.map(lambda a: blocks(a, size), data)
    first = jax.tree.map(lambda a: a[0], db)
    zero = jax.tree.map(jnp.zeros_like,
                        jax.eval_shape(block_stats, params, first))

    def fwd(acc, dat):
        return jax.tree.map(jnp.add, acc, block_stats(params, dat)), None

    stats, _ = lax.scan(fwd, zero, db)
    loss, epi_vjp = jax.vjp(epilogue, params, stats)
    g_params, g_stats = epi_vjp(jnp.ones_like(loss))

    def bwd(acc, dat):
        _, f_vjp = jax.vjp(lambda p: block_stats(p, dat), params)
        (dp,) = f_vjp(g_stats)
        return jax.tree.map(jnp.add, acc, dp), None

    g_blocks, _ = lax.scan(bwd, jax.tree.map(jnp.zeros_like, params), db)
    return loss, jax.tree.map(jnp.add, g_params, g_blocks)


def adam(params, grads, state, t: int, opt: dict):
    """One Adam step (Kingma & Ba 2015, section 2, with the bias corrections
    folded into the step size); state = (m, v), t counts from 1."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m, v = state
    m = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, grads)
    alpha = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    params = jax.tree.map(lambda p, m, v: p - alpha * m / (jnp.sqrt(v) + eps),
                          params, m, v)
    return params, (m, v)


def train(value_and_grad, params: dict, steps: int, opt: dict):
    """`steps` Adam steps from `params`: (losses, first gradient, params)."""
    state = (jax.tree.map(jnp.zeros_like, params),
             jax.tree.map(jnp.zeros_like, params))
    losses, first = [], None
    for t in range(1, steps + 1):
        loss, grads = value_and_grad(params)
        losses.append(float(loss))
        if first is None:
            first = grads
        params, state = adam(params, grads, state, t, opt)
    return losses, first, params
