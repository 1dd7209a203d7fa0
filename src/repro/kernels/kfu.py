"""Pallas TPU kernel: RBF cross-covariance K_fu (paper §3, the sparse-GP /
GP-head hot loop).

TPU adaptation (vs the paper's CUDA Table 1): instead of a thread per
datapoint, the squared distance is rewritten as

    d2[n,m] = |x_n/l|^2 + |z_m/l|^2 - 2 (x/l) @ (z/l)^T

so the O(N M Q) inner product runs on the 128x128 MXU, and the row/col norms
are VPU row reductions. Each grid step owns one (TILE_N, TILE_M) output tile
in VMEM; BlockSpec index maps make every output tile written exactly once
(no global-memory write contention to manage, unlike CUDA cc-2.0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.suffstats import _dot, _vma

TILE_N = 256
TILE_M = 128


def _kfu_kernel(xs_ref, zs_ref, o_ref, *, ct=jnp.float32):
    """xs/zs are pre-scaled by 1/lengthscale in the wrapper (one pass,
    instead of once per tile)."""
    xs = xs_ref[...].astype(ct)  # (TILE_N, Q)
    zs = zs_ref[...].astype(ct)  # (TILE_M, Q)
    xn = jnp.sum(xs * xs, axis=-1, keepdims=True)  # (TILE_N, 1)
    zn = jnp.sum(zs * zs, axis=-1)[None, :]  # (1, TILE_M)
    cross = _dot(xs, zs, ((1,), (1,)), ct)  # MXU: (TILE_N, TILE_M)
    d2 = jnp.maximum(xn + zn - 2.0 * cross, 0.0)
    o_ref[...] = jnp.exp(-0.5 * d2).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def kfu_pallas(
    X: jax.Array,
    Z: jax.Array,
    variance: jax.Array,
    lengthscale: jax.Array,
    *,
    interpret: bool = False,
    block: tuple | None = None,
) -> jax.Array:
    """K_fu = variance * exp(-0.5 ||(x-z)/l||^2), tiled (tile_n, tile_m).

    Compiled (TPU) execution computes in float32 — the hardware dtype the
    tiles are chosen for. Interpret mode computes in the input dtype promoted
    to at least f32 (same policy as the fused suffstats kernel): it exists to
    validate the kernel body, and under x64 that makes f64 parity checks
    meaningful.

    `block=(tile_n, tile_m)` overrides the module-constant tiles — the knob
    the `repro.tune` autotuner turns; None keeps (TILE_N, TILE_M). The
    wrapper pads to whatever multiple the block demands, so any measured
    winner is numerically identical to the defaults.
    """
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = X.shape
    M = Z.shape[0]
    dtype = X.dtype
    ct = jnp.promote_types(dtype, jnp.float32) if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    Xs = jnp.pad((X / lengthscale).astype(ct), ((0, pad_n), (0, 0)))
    Zs = jnp.pad((Z / lengthscale).astype(ct), ((0, pad_m), (0, 0)))

    grid = (Xs.shape[0] // tile_n, Zs.shape[0] // tile_m)
    vma = _vma(Xs, Zs)
    out = pl.pallas_call(
        functools.partial(_kfu_kernel, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, tile_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Xs.shape[0], Zs.shape[0]), ct, vma=vma),
        interpret=interpret,
    )(Xs, Zs)
    return (variance * out[:N, :M]).astype(dtype)
