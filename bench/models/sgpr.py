"""Sparse GP regression (`SparseGPRegression`) on synthetic regression data.

The data generator is a copy of the one in `chip_smoke.regression_and_serving`
(kept here so that a change to the program cannot change the yardstick):
X ~ N(0, I_Q), Y = sin(2 X w) + noise, w ~ N(0, I / Q).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.harness.seeds import jax_key


def make_data(cfg: dict, seed: int, n: int):
    """(X (n, Q), Y (n, D)) made on the device in one jitted call."""
    q, d = cfg["Q"], cfg["D"]
    freq, noise = cfg["data"]["frequency"], cfg["data"]["noise_std"]

    @jax.jit
    def gen(key):
        kx, kw, kn = jax.random.split(key, 3)
        X = jax.random.normal(kx, (n, q), jnp.float32)
        w = jax.random.normal(kw, (q, d), jnp.float32) / math.sqrt(q)
        Y = jnp.sin(freq * X @ w) + noise * jax.random.normal(
            kn, (n, d), jnp.float32)
        return X, Y

    return gen(jax_key(seed, 1))


def init_params(cfg: dict, data, seed: int) -> dict:
    """Start point: Z every (N // M)-th input; kernel and noise as stated."""
    X, _ = data
    n, m, q = X.shape[0], cfg["M"], cfg["Q"]
    init = cfg["init"]
    return {
        "kern": {"log_variance": jnp.asarray(init["log_variance"], jnp.float32),
                 "log_lengthscale": jnp.full((q,), init["log_lengthscale"],
                                             jnp.float32)},
        "Z": X[:: max(n // m, 1)][:m],
        "log_beta": jnp.asarray(init["log_beta"], jnp.float32),
    }


def build(cfg: dict, mesh):
    from repro.gp import SparseGPRegression, get

    return SparseGPRegression(kernel=get(cfg["kernel"])(cfg["Q"]), M=cfg["M"],
                              mesh=mesh, **cfg["facade_kwargs"])


def fit(model, data, params, steps: int, **kw):
    X, Y = data
    return model.fit(X, Y, params=params, steps=steps, lr=kw.pop("lr"), **kw)
