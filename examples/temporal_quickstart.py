"""Temporal quickstart: streaming forecasts from a state-space GP.

    PYTHONPATH=src python examples/temporal_quickstart.py [--n 100000]

Fits `TemporalGPRegression` (backend="temporal") on the LEFT half of a
long, non-uniformly sampled time series — the O(N) parallel-scan Kalman
path, no (N, N) matrix anywhere — exports the O(d^2) `TemporalState`
into a `GPServer`, then streams the RIGHT half in chunks through
`server.update()`. After each chunk it forecasts the next window and
reports the rolling forecast RMSE: the error stays near the noise floor
because every update advances the filter to the newest timestamp.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro import compile_cache
from repro.gp import get, regression


def rmse(mean, truth) -> float:
    return float(jnp.sqrt(jnp.mean((mean[:, 0] - truth) ** 2)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    compile_cache.enable()

    from repro.serve import GPServer

    key = jax.random.PRNGKey(0)
    n = args.n
    # non-uniform timestamps: mean gap 1e-3, so ~half the series spans ~50
    # characteristic times of the signal below
    gaps = jax.random.uniform(key, (n,), jnp.float64,
                              minval=0.5e-3, maxval=1.5e-3)
    t = jnp.cumsum(gaps)[:, None]
    f = jnp.sin(2.0 * jnp.pi * 0.8 * t[:, 0])
    noise = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (n,),
                                    jnp.float64)
    Y = (f + noise)[:, None]
    half = n // 2

    # --- fit on the left half only; the right half arrives "in production"
    gp = regression(get("matern32")(1), backend="temporal")
    gp.fit(t[:half], Y[:half], steps=args.steps, lr=5e-2)
    print(f"fitted temporal GP on {half} points "
          f"(lml/N={float(gp.lml()) / half:.3f})")

    server = GPServer()
    server.register("sensor", gp)  # export_state(): terminal (m, P), O(d^2)
    state = server.state("sensor")
    print(f"registered TemporalState: d={state.d}, {state.nbytes} bytes, "
          f"n={int(state.n)} points absorbed")

    # --- stream the right half in chunks: before absorbing each chunk,
    # forecast a short window past the current frontier (a GP forecast is
    # only informative within ~a lengthscale of the last observation — a
    # long-horizon forecast correctly reverts to the prior mean), then
    # filter the whole chunk forward.
    chunk = max(64, (n - half) // 20)
    horizon = 64
    errors = []
    for start in range(half, n, chunk):
        sl = slice(start, min(start + chunk, n))
        h = slice(start, min(start + horizon, n))
        mean, var = server.predict("sensor", t[h])  # forecast BEFORE seeing
        errors.append(rmse(mean, f[h]))
        server.update("sensor", t[sl], Y[sl])  # filter forward
    print(f"streamed {n - half} points in {len(errors)} chunks; "
          f"{horizon}-point-ahead forecast RMSE "
          f"first={errors[0]:.3f} median={sorted(errors)[len(errors) // 2]:.3f} "
          f"last={errors[-1]:.3f}")

    # every forecast is made at the filter frontier, so the error sits near
    # the noise floor (0.1) throughout — it does not degrade as the series
    # grows, and no step ever touches more than one chunk of data
    assert max(errors) < 0.35, errors
    assert sorted(errors)[len(errors) // 2] < 0.2, errors
    n_final = int(server.state("sensor").n)
    assert n_final == n, (n_final, n)
    server.close()
    print("temporal quickstart OK")


if __name__ == "__main__":
    main()
