"""Pallas TPU kernel: Psi1 statistic of the Bayesian GP-LVM (paper §3).

    Psi1[n,m] = sigma^2 prod_q (1 + S_nq/l_q^2)^(-1/2)
                exp(-0.5 (mu_nq - z_mq)^2 / (l_q^2 + S_nq))

TPU adaptation — the CUDA version (paper Table 1) loops a thread over
(n, m, q). Here the n-dependent denominator d_nq = l_q^2 + S_nq is factored
so the whole exponent becomes MXU matmuls over the Q contraction:

    (mu-z)^2 / d  =  mu^2/d  -  2 (mu/d) z  +  (1/d) z^2
    expo[n,m]     =  c_n  -  2 (mu*b)[n,:] @ Z^T[:,m]  +  b[n,:] @ (Z^2)^T[:,m]

with b = 1/d, c_n = sum_q mu^2 b. No (TILE_N, TILE_M, Q) broadcast tensor
ever exists — the kernel is two (TILE_N, Q) x (Q, TILE_M) MXU contractions
plus VPU row terms, which is also what makes large-Q GP heads viable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.suffstats import _psi1_tile, _vma

TILE_N = 256
TILE_M = 128


def _psi1_kernel(mu_ref, s_ref, z_ref, l2_ref, o_ref, *, ct=jnp.float32):
    mu = mu_ref[...].astype(ct)  # (TILE_N, Q)
    S = s_ref[...].astype(ct)  # (TILE_N, Q)
    Z = z_ref[...].astype(ct)  # (TILE_M, Q)
    l2 = l2_ref[...].astype(ct)  # (1, Q)

    # the shared tile helper of the fused forward/reverse kernels — the
    # single-statistic op evaluates the identical expression, so the psi1
    # formula exists in exactly one place
    _, blk = _psi1_tile(mu, S, Z, l2, ct=ct)
    o_ref[...] = blk.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def psi1_pallas(
    mu: jax.Array,
    S: jax.Array,
    Z: jax.Array,
    variance: jax.Array,
    lengthscale: jax.Array,
    *,
    interpret: bool = False,
    block: tuple | None = None,
) -> jax.Array:
    # `block=(tile_n, tile_m)` overrides the module-constant tiles (the
    # repro.tune knob); the wrapper pads to the block's multiple, so every
    # candidate is numerically identical to the defaults.
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = mu.shape
    M = Z.shape[0]
    dtype = mu.dtype
    # compiled TPU execution computes in float32; interpret mode computes in
    # the input dtype promoted to at least f32 (same policy as the fused
    # suffstats kernel) so f64 parity tests exercise the kernel body itself
    ct = jnp.promote_types(dtype, jnp.float32) if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    mu_p = jnp.pad(mu.astype(ct), ((0, pad_n), (0, 0)))
    # pad S with 1.0: any positive value keeps log1p/division well-defined
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]  # (1, Q)

    grid = (mu_p.shape[0] // tile_n, Z_p.shape[0] // tile_m)
    vma = _vma(mu_p, S_p, Z_p, l2)
    out = pl.pallas_call(
        functools.partial(_psi1_kernel, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, Q), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j: (j, 0)),
            pl.BlockSpec((1, Q), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, tile_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mu_p.shape[0], Z_p.shape[0]), ct, vma=vma),
        interpret=interpret,
    )(mu_p, S_p, Z_p, l2)
    return (variance * out[:N, :M]).astype(dtype)
