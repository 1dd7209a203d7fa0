"""The numbers that decide `correct`, each read against the reference.

Training (the set-up steps and the timed window, against the reference's):
  loss_gap           worst |L - L_ref| / |L_ref| over the set-up steps.
  change_gap         worst leaf's | |p_t - p_0| - |p_t,ref - p_0| | over
                     max(|p_t,ref - p_0| of that leaf, the median leaf's),
                     after the set-up steps. Leaves whose first reference
                     gradient is under a thousandth of the median leaf's are
                     left out: Adam moves them by round-off alone.
  window_loss_gap    the same as loss_gap, of the window's last step.
  window_change_gap  the same as change_gap, from the start point to the
                     parameters the window leaves.
"""
from __future__ import annotations

import jax
import numpy as np

GRAD_FLOOR = 1e-3


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
            for path, leaf in flat}


def loss_gap(losses, ref_losses) -> float:
    if len(losses) != len(ref_losses) or not losses:
        return float("inf")
    return max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))


def change_gap(p0, p_prog, p_ref, ref_grad) -> tuple:
    """(gap, worst leaf, leaves compared)."""
    gaps = leaf_change_gaps(p0, p_prog, p_ref, ref_grad)
    if not all(np.isfinite(v) for v in gaps.values()):
        return float("inf"), "non-finite", list(gaps)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, list(gaps)


def leaf_change_gaps(p0, p_prog, p_ref, ref_grad) -> dict:
    """Each compared leaf's change gap, as change_gap reads it."""
    start, prog, ref = _leaves(p0), _leaves(p_prog), _leaves(p_ref)
    grad = {k: float(np.linalg.norm(v)) for k, v in _leaves(ref_grad).items()}
    floor = GRAD_FLOOR * float(np.median(list(grad.values())))
    keep = [k for k in start if grad[k] >= floor]
    d_ref = {k: float(np.linalg.norm(ref[k] - start[k])) for k in keep}
    med = float(np.median(list(d_ref.values())))
    return {k: abs(float(np.linalg.norm(prog[k] - start[k])) - d_ref[k])
            / max(d_ref[k], med, 1e-30) for k in keep}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(all within limits, {name: {"value", "limit"}}) for the result line."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        ok = ok and bool(np.isfinite(value)) and value <= limit
        out[name] = {"value": value, "limit": limit}
    return ok, out
