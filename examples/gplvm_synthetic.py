"""The paper's §4 experiment: Bayesian GP-LVM dimensionality reduction on
synthetic data — recover the 1-D latent line from 3-D observations.

    PYTHONPATH=src python examples/gplvm_synthetic.py [--n 2048] [--pallas]

Setup mirrors the paper: Q=1 latent dim, M=100 inducing points, data sampled
through an RBF-kernel function. Optimizes the distributed bound with Adam
(use --lbfgs for the paper's optimizer) through the `repro.gp.BayesianGPLVM`
facade and reports the latent-recovery correlation (up to sign/scale, the
invariances of the model).
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro import compile_cache
from repro.core.distributed import make_gp_mesh
from repro.data.synthetic import gplvm_synthetic
from repro.gp import BayesianGPLVM, get


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--m", type=int, default=100)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--lbfgs", action="store_true", help="paper's optimizer")
    ap.add_argument("--backend", choices=("jnp", "pallas", "fused"),
                    default="jnp",
                    help="psi-stats path; 'fused' trains through the fused "
                         "suffstats kernel pair (fwd + hand-derived reverse)")
    ap.add_argument("--pallas", action="store_true",
                    help="deprecated alias for --backend pallas")
    ap.add_argument("--min-corr", type=float, default=0.95,
                    help="latent-recovery bar (smoke-mode CI relaxes it: the "
                         "recovery quality depends on the data draw and N)")
    args = ap.parse_args()
    compile_cache.enable()

    key = jax.random.PRNGKey(0)
    X_true, Y = gplvm_synthetic(key, N=args.n, D=3, Q=1)
    print(f"data: N={args.n} 3-D points from a 1-D latent (paper §4)")

    if args.pallas and args.backend != "jnp":
        ap.error("--pallas is an alias for --backend pallas; don't pass both")
    backend = "pallas" if args.pallas else args.backend
    lvm = BayesianGPLVM(kernel=get("rbf")(1), M=args.m, mesh=make_gp_mesh(),
                        backend=backend)

    t0 = time.time()
    lvm.fit(Y, optimizer="lbfgs" if args.lbfgs else "adam", steps=args.steps,
            lr=2e-2, log_every=0 if args.lbfgs else max(args.steps // 8, 1), key=key)
    dt = time.time() - t0
    print(f"optimized {args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.1f} ms/iter) final loss {lvm.history[-1]:.4f}")

    # latent recovery: correlation of q_mu with the true latent (sign/scale free)
    mu, _ = lvm.latent()
    corr = abs(np.corrcoef(np.asarray(mu[:, 0]), np.asarray(X_true[:, 0]))[0, 1])
    print(f"|corr(latent, truth)| = {corr:.3f}")
    assert corr > args.min_corr, f"latent line not recovered: {corr:.3f} <= {args.min_corr}"
    print("recovered the 1-D latent structure — paper reproduction OK")


if __name__ == "__main__":
    main()
