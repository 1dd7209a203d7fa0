"""Serve quickstart: fit once, serve forever — online updates included.

    PYTHONPATH=src python examples/serve_quickstart.py [--steps 150]

Fits a sparse GP on the LEFT half of the input range only, exports the
O(M^2) posterior state into a `GPServer`, serves concurrent predictions
through the micro-batching queue, then streams the RIGHT half of the data
in through `server.update()` — no refit, no access to the original training
set — and shows the predictions on the new region snapping into place.
"""
import argparse
import sys
from concurrent.futures import Future
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.gp import SparseGPRegression, get


def rmse(mean, truth) -> float:
    return float(jnp.sqrt(jnp.mean((mean[:, 0] - truth) ** 2)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--n", type=int, default=2000)
    args = ap.parse_args()
    compile_cache.enable()

    from repro.serve import GPServer

    key = jax.random.PRNGKey(0)
    N, M = args.n, 32
    X = jnp.sort(jax.random.uniform(key, (N, 1), minval=-3.0, maxval=3.0), axis=0)
    f = jnp.sin(2.0 * X[:, 0])
    Y = (f + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (N,)))[:, None]
    left = X[:, 0] < 0.0

    # --- fit on the left half only; the right half arrives "in production".
    # Inducing points span the FULL expected input domain (not just the
    # fitted half): online updates can only sharpen the posterior inside
    # span{k(., z_m)}, so serving deployments place Z over the domain they
    # intend to serve, not over the data they happen to start with.
    gp = SparseGPRegression(kernel=get("rbf")(1), M=M)
    params = gp.init_params(X[left], Y[left])
    params["Z"] = jnp.linspace(-3.0, 3.0, M)[:, None]
    gp.fit(X[left], Y[left], steps=args.steps, lr=3e-2, params=params)

    server = GPServer()
    server.register("demo", gp)  # export_state(): Choleskys + SuffStats
    print(f"registered state: M={server.state('demo').M}, "
          f"n={float(server.state('demo').stats.n):.0f} points absorbed")

    # --- concurrent predictions through the micro-batching queue
    Xt = jnp.linspace(0.1, 3.0, 128)[:, None]  # the UNSEEN region
    f_t = jnp.sin(2.0 * Xt[:, 0])
    futures: list[Future] = [server.submit("demo", Xt[i: i + 16])
                             for i in range(0, 128, 16)]
    mean_before = jnp.concatenate([fut.result(timeout=60)[0] for fut in futures])
    before = rmse(mean_before, f_t)
    print(f"RMSE on unseen region before update: {before:.3f}")

    # --- stream the right half in: monoid fold + O(M^3) refold, no refit
    right_idx = jnp.where(~left)[0]
    for start in range(0, int(right_idx.size), 256):
        sl = right_idx[start: start + 256]
        server.update("demo", X[sl], Y[sl])
    print(f"absorbed {int(right_idx.size)} new points online "
          f"(n={float(server.state('demo').stats.n):.0f})")

    mean_after, var_after = server.predict("demo", Xt)
    after = rmse(mean_after, f_t)
    inside = float(jnp.mean(jnp.abs(mean_after[:, 0] - f_t)
                            < 2.0 * jnp.sqrt(var_after)))
    print(f"RMSE on unseen region after update:  {after:.3f} "
          f"({inside * 100:.0f}% of truth inside 2-sigma)")
    server.close()

    assert after < 0.5 * before, (before, after)
    assert after < 0.2, after
    print("serve quickstart OK")


if __name__ == "__main__":
    main()
