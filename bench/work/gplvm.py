"""Bayesian GP-LVM: expected statistics of n rows under q(X), one Adam step.

Per-row weights a = 1 / (l^2 + S), b = 1 / (l^2 + 2 S) make each squared
distance sum_q w (mu - z)^2 two contractions over Q (of w mu and of w
against z and z^2), so each costs 4 Q per (row, column).

Forward (statistics of n rows):
  weights, log-normalizers and KL   8 n Q      (+ 3 n Q transcendentals)
  Psi1 exponent                     4 n M Q + 4 n M   (+ n M exponentials)
  PsiY = Psi1^T Y                   2 n M D
  Psi2 exponent over the pairs      4 n M^2 Q
  scale and sum over n              2 n M^2   (+ n M^2 exponentials)
  the pairs' own factor             3 M^2 Q + 2 M^2   (+ M^2 exponentials)
Reverse, with cotangents G2 (M x M) and GY (M x D), Psi1 and Psi2's terms
recomputed as the forward does (storing n M^2 of them is out of reach):
  recompute Psi1                    4 n M Q + 4 n M   (+ n M exponentials)
  dPsi1 = Y GY^T, times Psi1        2 n M D + n M
  dmu, dS from Psi1                 4 n M Q
  dZ from Psi1                      4 n M Q
  recompute Psi2                    4 n M^2 Q + 2 n M^2   (+ n M^2 exponentials)
  weight by G2                      n M^2
  dmu, dS from Psi2                 4 n M^2 Q
  dZ from Psi2                      4 n M^2 Q
  KL and the weights                8 n Q
Bytes: mu, S and Y read once in each direction, dmu and dS written, the
statistics and their cotangents written and read.
"""
from __future__ import annotations

from bench.work import common


def statistics(n: int, M: int, Q: int, D: int) -> dict:
    fwd = (8 * n * Q + 4 * n * M * Q + 4 * n * M + 2 * n * M * D
           + 4 * n * M * M * Q + 2 * n * M * M + 3 * M * M * Q + 2 * M * M)
    bwd = (4 * n * M * Q + 4 * n * M + 2 * n * M * D + n * M + 8 * n * M * Q
           + 4 * n * M * M * Q + 3 * n * M * M + 8 * n * M * M * Q + 8 * n * Q)
    byts = common.F32 * (2 * n * (2 * Q + D) + 2 * n * Q
                         + 2 * (M * M + M * D + M * Q))
    return {"flops": fwd + bwd, "fwd_flops": fwd, "bwd_flops": bwd,
            "bytes": byts,
            "transcendentals": 3 * n * Q + 2 * n * M + 2 * n * M * M + M * M}


def step(cfg: dict, n: int) -> dict:
    """Work of one training step over n rows (one chip's share): the global
    parameters and this chip's rows of q(X)."""
    M, Q, D = cfg["M"], cfg["Q"], cfg["D"]
    return common.total(psi=statistics(n, M, Q, D),
                        epilogue=common.epilogue(M, Q, D),
                        optimizer=common.adam(n * 2 * Q + M * Q + Q + 2))
