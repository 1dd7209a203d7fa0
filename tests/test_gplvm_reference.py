"""The Bayesian GP-LVM's loss and gradient against a plain oracle, and its
float32 epilogue against the Cholesky's rounding.

The oracle is built from `repro.kernels.ref`'s psi statistics and a dense
collapsed bound (slogdet and solves, no Cholesky factors) in float64. On
the CPU the fused op runs its kernel bodies in interpret mode up to 1,024
rows; inside `shard_map` they fail there (a known interpreter limit), so
the one-device mesh takes 1,100 rows, where the fused op's jnp twins run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed, gplvm, svgp
from repro.gp import BayesianGPLVM, get
from repro.kernels import ref


def _problem(n, q=3, m=8, d=4, seed=0, dtype=jnp.float64):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    params = {
        "kern": {"log_variance": 0.3 * jax.random.normal(k[0], ()),
                 "log_lengthscale": 0.3 * jax.random.normal(k[1], (q,))},
        "Z": jax.random.normal(k[2], (m, q)),
        "log_beta": jnp.log(20.0) + 0.2 * jax.random.normal(k[3], ()),
        "q_mu": jax.random.normal(k[4], (n, q)),
        "q_logS": jnp.log(0.1) + 0.5 * jax.random.normal(k[5], (n, q)),
    }
    Y = jax.random.normal(k[6], (n, d))
    return jax.tree.map(lambda x: x.astype(dtype), params), Y.astype(dtype)


def _oracle_loss(params, Y):
    """-(F - KL) / N with F the dense collapsed bound of Titsias & Lawrence
    (2010), jittered as the program's float64 epilogue."""
    n, d = Y.shape
    var = jnp.exp(params["kern"]["log_variance"])
    ls = jnp.exp(params["kern"]["log_lengthscale"])
    mu, S, Z = params["q_mu"], jnp.exp(params["q_logS"]), params["Z"]
    beta = jnp.exp(params["log_beta"])
    psi0 = ref.psi0_rbf(mu, S, var, ls)
    psi1 = ref.psi1_rbf(mu, S, Z, var, ls)
    psi2 = jnp.sum(ref.psi2_n_rbf(mu, S, Z, var, ls), 0)
    Kuu = ref.kfu_rbf(Z, Z, var, ls)
    eye = jnp.eye(Z.shape[0])
    Kj = Kuu + svgp.DEFAULT_JITTER * jnp.mean(jnp.diagonal(Kuu)) * eye
    A = Kj + beta * psi2
    A = A + 100 * jnp.finfo(A.dtype).eps * jnp.mean(jnp.diagonal(A)) * eye
    psiY = psi1.T @ Y
    F = (0.5 * d * n * jnp.log(beta / (2 * jnp.pi))
         - 0.5 * d * (jnp.linalg.slogdet(A)[1] - jnp.linalg.slogdet(Kj)[1])
         - 0.5 * beta * jnp.sum(Y * Y)
         + 0.5 * beta ** 2 * jnp.sum(psiY * jnp.linalg.solve(A, psiY))
         - 0.5 * beta * d * psi0
         + 0.5 * beta * d * jnp.trace(jnp.linalg.solve(Kj, psi2)))
    kl = 0.5 * jnp.sum(S + mu ** 2 - params["q_logS"] - 1.0)
    return -(F - kl) / n


@pytest.mark.parametrize("backend,mesh,n", [
    ("jnp", False, 64), ("jnp", True, 64),
    ("fused", False, 64), ("fused", True, 1100)],
    ids=["jnp", "jnp-mesh", "fused-interpret", "fused-mesh"])
def test_loss_and_gradient_match_dense_oracle(backend, mesh, n):
    params, Y = _problem(n)
    model = BayesianGPLVM(kernel=get("rbf")(3), M=8, backend=backend,
                          mesh=distributed.make_gp_mesh(1) if mesh else None)
    loss, grads = jax.value_and_grad(model._loss_fn())(params, Y)
    want, want_grads = jax.value_and_grad(_oracle_loss)(params, Y)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-9)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-9 * float(jnp.max(jnp.abs(w))))


def test_float32_pivots_clear_the_cholesky_rounding():
    """The divergence: as the lengthscales grow, Kuu + beta Psi2 passes a
    condition number of 1 / eps, and a float32 Cholesky of it meets pivots
    at its own rounding, about M u tr(A) (u = eps / 2); the old floor of
    100 eps mean(diag A) lies M^2 / 100 below that, and a pivot that goes
    negative turns the loss NaN. At M = 16, lengthscales of 8 and beta =
    1000, cond(A) > 1 / (M u): every factored matrix of the GP-LVM's
    float32 epilogue keeps its smallest eigenvalue above that rounding."""
    n, q, m, d = 1024, 8, 16, 8
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    mu = jax.random.normal(k1, (n, q), jnp.float32)
    Y = jax.random.normal(k2, (n, d), jnp.float32)
    params = {"kern": {"log_variance": jnp.zeros((), jnp.float32),
                       "log_lengthscale": jnp.full((q,), np.log(8.0),
                                                   jnp.float32)},
              "Z": mu[:: n // m], "log_beta": jnp.asarray(np.log(1000.0),
                                                          jnp.float32),
              "q_mu": mu, "q_logS": jnp.full((n, q), np.log(0.1), jnp.float32)}
    model = BayesianGPLVM(kernel=get("rbf")(q), M=m)
    model.fit(Y, params=params, steps=0)
    u = np.finfo(np.float32).eps / 2
    psi2 = np.asarray(gplvm.local_stats(params, Y).psi2, np.float64)
    A = np.asarray(model.kernel.K(params["kern"], params["Z"]),
                   np.float64) + 1000.0 * psi2
    eig = np.linalg.eigvalsh(A)
    assert eig[-1] / eig[0] > 1.0 / (m * u)
    post = model.posterior()
    for L in (post.L, post.LA):
        L = np.asarray(L, np.float64)
        F = L @ L.T
        assert np.linalg.eigvalsh(F)[0] > m * u * np.trace(F)
    loss, grads = jax.value_and_grad(model._loss_fn())(params, Y)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
