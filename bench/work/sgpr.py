"""Sparse GP regression: exact statistics of n rows, one Adam step.

Forward (statistics of n rows):
  distances X Z^T        2 n M Q
  exponent and scale     4 n M        (+ n M exponentials)
  Psi2 = Kfu^T Kfu       2 n M^2
  PsiY = Kfu^T Y         2 n M D
Reverse, with cotangents G2 (M x M) and GY (M x D), Kfu recomputed as the
forward does (storing it would cost n M float32 of memory traffic):
  recompute Kfu          2 n M Q + 4 n M   (+ n M exponentials)
  dKfu = Kfu (G2 + G2^T) + Y GY^T        2 n M^2 + 2 n M D
  through the exponential                2 n M
  dZ and dlengthscale, two contractions over n with the inputs   4 n M Q
Bytes: X and Y read once in each direction, the statistics written.
"""
from __future__ import annotations

from bench.work import common


def statistics(n: int, M: int, Q: int, D: int) -> dict:
    fwd = 2 * n * M * Q + 4 * n * M + 2 * n * M * M + 2 * n * M * D
    bwd = (2 * n * M * Q + 4 * n * M + 2 * n * M * M + 2 * n * M * D
           + 2 * n * M + 4 * n * M * Q)
    byts = common.F32 * (2 * n * (Q + D) + 2 * (M * M + M * D + M * Q))
    return {"flops": fwd + bwd, "fwd_flops": fwd, "bwd_flops": bwd,
            "bytes": byts, "transcendentals": 2 * n * M}


def step(cfg: dict, n: int) -> dict:
    """Work of one training step over n rows (one chip's share)."""
    M, Q, D = cfg["M"], cfg["Q"], cfg["D"]
    return common.total(psi=statistics(n, M, Q, D),
                        epilogue=common.epilogue(M, Q, D),
                        optimizer=common.adam(M * Q + Q + 2))
