"""Bayesian GP-LVM (`BayesianGPLVM`) on synthetic latent-variable data.

The data generator is a copy of the random-Fourier-feature branch of
`repro.data.synthetic.gplvm_synthetic` (kept here so that a change to the
program cannot change the yardstick): X ~ U(-2, 2)^Q, Y = phi(X) W + noise,
with phi the `features` random Fourier features of an RBF kernel of the
stated lengthscale and W ~ N(0, I).

The start point is the paper's: q(X) means from PCA of Y (each latent
dimension scaled to unit spread), S at a constant, Z every (N // M)-th
mean, kernel and noise as stated.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.harness.seeds import jax_key


def make_data(cfg: dict, seed: int, n: int):
    """(Y (n, D),) made on the device in one jitted call."""
    q, d, dat = cfg["Q"], cfg["D"], cfg["data"]
    feats, ls, noise = dat["features"], dat["lengthscale"], dat["noise_std"]

    @jax.jit
    def gen(key):
        kx, kw, kb, kw2, kn = jax.random.split(key, 5)
        X = jax.random.uniform(kx, (n, q), jnp.float32, -2.0, 2.0)
        omega = jax.random.normal(kw, (q, feats), jnp.float32) / ls
        b = jax.random.uniform(kb, (feats,), jnp.float32, 0.0, 2 * math.pi)
        phi = math.sqrt(2.0 / feats) * jnp.cos(X @ omega + b)
        W = jax.random.normal(kw2, (feats, d), jnp.float32)
        return (phi @ W + noise * jax.random.normal(kn, (n, d), jnp.float32),)

    return gen(jax_key(seed, 1))


def init_params(cfg: dict, data, seed: int) -> dict:
    """PCA means, S and the kernel as stated, Z every (N // M)-th mean."""
    (Y,) = data
    q, m, init = cfg["Q"], cfg["M"], cfg["init"]

    @jax.jit
    def start(Y):
        Yc = Y - jnp.mean(Y, 0)
        _, vecs = jnp.linalg.eigh(Yc.T @ Yc)
        X = Yc @ vecs[:, ::-1][:, :q]
        X = X / jnp.std(X, 0)
        return {
            "kern": {"log_variance": jnp.asarray(init["log_variance"],
                                                 jnp.float32),
                     "log_lengthscale": jnp.full((q,), init["log_lengthscale"],
                                                 jnp.float32)},
            "Z": X[:: max(X.shape[0] // m, 1)][:m],
            "log_beta": jnp.asarray(init["log_beta"], jnp.float32),
            "q_mu": X,
            "q_logS": jnp.full(X.shape, math.log(init["S"]), jnp.float32),
        }

    return start(Y)


def build(cfg: dict, mesh):
    from repro.gp import BayesianGPLVM, get

    # a program whose facade does not shift the epilogue as the
    # configuration's `jitter.shift` states runs another model: refuse it
    if getattr(BayesianGPLVM, "_shift", False) != cfg["jitter"]["shift"]:
        raise SystemExit("bench/models/gplvm.py: the program's BayesianGPLVM "
                         "does not apply the configuration's jitter.shift")
    return BayesianGPLVM(kernel=get(cfg["kernel"])(cfg["Q"]), M=cfg["M"],
                         mesh=mesh, **cfg["facade_kwargs"])


def fit(model, data, params, steps: int, **kw):
    (Y,) = data
    return model.fit(Y, params=params, steps=steps, lr=kw.pop("lr"), **kw)
