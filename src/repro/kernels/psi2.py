"""Pallas TPU kernel: Psi2 statistic (paper §3 Table 1, "Phi" accumulation).

    Psi2[m,m'] = sum_n sigma^4 prod_q (1+2 S_nq/l_q^2)^(-1/2)
        exp(-(z_mq - z_m'q)^2/(4 l_q^2) - (mu_nq - zbar_q)^2/(l_q^2 + 2 S_nq))

TPU adaptation of the CUDA design (block per (m1,m2) pair, threads over n,
shared-memory reduction):

  * grid = (M/TM, M/TM, N/TN); the N axis is the *innermost* grid dimension,
    so for a fixed (m1, m2) tile the kernel revisits the same VMEM output
    block sequentially and accumulates in place — a race-free replacement for
    CUDA's shared-memory tree reduction (TPU grid steps are sequential per
    core, so no synchronization exists or is needed).
  * the (mu - zbar)^2 / d_nq exponent is expanded so the n<->m coupling
    becomes MXU matmuls (two halfterms A1, A2 and the rank-Q cross term as
    one (TN*TM, Q) x (Q, TM) contraction); the final weighted reduction over
    the datapoint tile is itself an MXU contraction  w(1,TN) @ E(TN, TM*TM).
  * padded datapoints carry weight 0 (exact masking — they contribute nothing
    to the sum, matching the paper's "sum over exactly N points").

The n-independent factor sigma^4 exp(-(z-z')^2/(4 l^2)) is applied outside
the kernel (O(M^2), negligible) — keeping the kernel a pure streaming
reduction over datapoints.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.suffstats import _dot, _psi2_prefactor, _psi2_tile, _vma

TILE_N = 32
TILE_M = 128


def _psi2_kernel(mu_ref, s_ref, w_ref, z1_ref, z2_ref, l2_ref, o_ref, *,
                 ct=jnp.float32):
    k = pl.program_id(2)

    mu = mu_ref[...].astype(ct)  # (TN, Q)
    S = s_ref[...].astype(ct)  # (TN, Q)
    w = w_ref[...].astype(ct)  # (TN, 1)
    z1 = z1_ref[...].astype(ct)  # (TM, Q)
    z2 = z2_ref[...].astype(ct)  # (TM, Q)
    l2 = l2_ref[...].astype(ct)  # (1, Q)

    tn = mu.shape[0]
    tm = z1.shape[0]

    # the shared tile helper of the fused forward/reverse kernels: the
    # per-point factor E (MXU halfterms + rank-Q cross term) is evaluated in
    # exactly one place, so the single-statistic and fused formulas can't drift
    _, E = _psi2_tile(mu, S, z1, z2, l2, ct=ct)  # (TN, TM, TM)

    # weighted datapoint reduction on the MXU: (1,TN) @ (TN, TM*TM)
    contrib = _dot(w.T, E.reshape(tn, tm * tm), ((1,), (0,)), ct
                   ).reshape(tm, tm)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def psi2_pallas(
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
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    w = jnp.pad(jnp.ones((N, 1), ct), ((0, pad_n), (0, 0)))
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]

    Mp = Z_p.shape[0]
    grid = (Mp // tile_m, Mp // tile_m, mu_p.shape[0] // tile_n)
    vma = _vma(mu_p, S_p, w, Z_p, l2)
    acc = pl.pallas_call(
        functools.partial(_psi2_kernel, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda i, j, k: (k, 0)),
            pl.BlockSpec((tile_n, Q), lambda i, j, k: (k, 0)),
            pl.BlockSpec((tile_n, 1), lambda i, j, k: (k, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j, k: (i, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, Q), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Mp), ct, vma=vma),
        interpret=interpret,
    )(mu_p, S_p, w, Z_p, Z_p, l2)

    # n-independent prefactor: sigma^4 exp(-(z - z')^2 / (4 l^2))
    pref = _psi2_prefactor(Z, variance, lengthscale, ct)
    return (pref * acc[:M, :M]).astype(dtype)
