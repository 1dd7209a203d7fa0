"""Work of the parts every collapsed-bound model shares."""
from __future__ import annotations

F32 = 4


def epilogue(M: int, Q: int, D: int) -> dict:
    """O(M^3) epilogue, forward and reverse (reverse counted as twice the
    forward): Kuu (2 M^2 Q), two Cholesky factors (M^3 / 3 each), the two
    triangular solves of the trace term (M^3 each) and c = LA^-1 PsiY
    (M^2 D). Bytes: about ten M x M float32 matrices read or written."""
    fwd = 2 * M ** 3 / 3 + 2 * M ** 3 + M * M * D + 2 * M * M * Q
    return {"flops": 3 * fwd, "bytes": 3 * 10 * M * M * F32,
            "transcendentals": 3 * 2 * M}


def adam(n_params: int) -> dict:
    """Adam: ~10 FLOPs and one square root per parameter; reads parameter,
    gradient and both moments, writes parameter and moments."""
    return {"flops": 10 * n_params, "bytes": 7 * F32 * n_params,
            "transcendentals": n_params}


def total(**parts) -> dict:
    out = {f"{name}_{k}": v for name, part in parts.items()
           for k, v in part.items()}
    for k in ("flops", "bytes", "transcendentals"):
        out[k] = sum(part[k] for part in parts.values())
    return out
