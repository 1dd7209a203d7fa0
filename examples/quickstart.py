"""Quickstart: sparse GP regression through the `repro.gp` facade.

    PYTHONPATH=src python examples/quickstart.py [--steps 300]

Fits a sparse GP (Titsias bound, the paper's eq. (2)-(3)) to 1-D data via the
same distributed code path used on a pod (here the mesh is 1 CPU device —
the code is identical), then prints test RMSE and calibration. The facade
owns the wiring this example used to hand-roll across five modules.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core.distributed import make_gp_mesh
from repro.gp import SparseGPRegression, get


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--backend", choices=("jnp", "pallas", "fused"),
                    default="jnp",
                    help="statistics path; 'fused' trains through the fused "
                         "suffstats kernel pair (fwd + hand-derived reverse, "
                         "exact statistics via S -> 0)")
    ap.add_argument("--pallas", action="store_true",
                    help="deprecated alias for --backend pallas")
    ap.add_argument("--max-rmse", type=float, default=0.1,
                    help="accuracy bar (smoke sizes/steps warrant a looser one)")
    args = ap.parse_args()
    compile_cache.enable()
    if args.pallas and args.backend != "jnp":
        ap.error("--pallas is an alias for --backend pallas; don't pass both")
    backend = "pallas" if args.pallas else args.backend

    key = jax.random.PRNGKey(0)
    N, M = args.n, 32
    X = jnp.sort(jax.random.uniform(key, (N, 1), minval=-3.0, maxval=3.0), axis=0)
    f = jnp.sin(2.0 * X[:, 0]) + 0.3 * jnp.cos(5.0 * X[:, 0])
    Y = (f + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (N,)))[:, None]

    # --- the whole model setup: kernel by name, mesh + backend from the ctor
    gp = SparseGPRegression(kernel=get("rbf")(1), M=M, mesh=make_gp_mesh(),
                            backend=backend)
    loss0 = -gp.fit(X, Y, steps=0).elbo() / N  # initial nlml/point (0 steps)
    print(f"initial nlml/point: {loss0:.4f}")
    gp.fit(X, Y, steps=args.steps, lr=3e-2)
    print(f"final   nlml/point: {-gp.elbo() / N:.4f}")

    # --- prediction through the facade
    Xt = jnp.linspace(-3, 3, 200)[:, None]
    mean, var = gp.predict(Xt)
    f_true = jnp.sin(2.0 * Xt[:, 0]) + 0.3 * jnp.cos(5.0 * Xt[:, 0])
    rmse = float(jnp.sqrt(jnp.mean((mean[:, 0] - f_true) ** 2)))
    inside = float(jnp.mean((jnp.abs(mean[:, 0] - f_true) < 2 * jnp.sqrt(var))))
    print(f"test RMSE {rmse:.4f}; {inside*100:.0f}% of truth inside 2-sigma")
    kern_cls = type(gp.kernel)
    print(f"learned lengthscale {float(kern_cls.lengthscale(gp.params['kern'])[0]):.3f}, "
          f"noise std {float(jnp.exp(gp.params['log_beta']) ** -0.5):.3f}")
    assert rmse < args.max_rmse, (rmse, args.max_rmse)
    print("quickstart OK")


if __name__ == "__main__":
    main()
