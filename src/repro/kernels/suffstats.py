"""Fused suffstats kernel: ALL sufficient statistics in one pass over N
(beyond-paper optimization C3, EXPERIMENTS.md §Perf) — forward AND reverse
Pallas TPU kernels, streaming jnp twins of both, and the hand-derived
reverse-pass algebra they all implement.

The paper computes Psi1 and Psi2 in separate GPU kernels (Table 1); the
bound only ever consumes psiY = Psi1^T Y and Psi2, so this kernel streams
each datapoint once and accumulates BOTH:

    psiY[m, :]   += psi1[n, m] * y[n, :]
    acc2[m, m']  += exp(lognorm2_n + muterm_n,m,m')

Removing the second pass halves HBM reads of (mu, S) and never materializes
the (N, M) Psi1 matrix.

The REVERSE pass has the same structure (paper Table 2 generalized to the
fused outputs): given cotangents (g2, gY) of (psi2, psiY), every input
cotangent is a weighted streaming reduction over the same per-point factors
the forward computes — so the backward reuses the forward's tile scheme.
The full algebra, with the equation numbers cited throughout this file,
lives in docs/derivations/suffstats_vjp.md.

Main entry points (wired into differentiable ops by `repro.kernels.ops`):

  * `suffstats_pallas`      — forward Pallas kernel (compiled on TPU,
                              interpret elsewhere). Grid (i, j, kn) with the
                              N axis innermost: each (M-tile, M-tile) output
                              block accumulates datapoint tiles in place.
  * `suffstats_bwd_pallas`  — reverse Pallas kernel. Grid (kn, i, j) with
                              the N axis OUTERMOST: the per-datapoint
                              cotangent blocks (dmu, dS, dY) accumulate the
                              (i, j) inducing tiles in place, while the
                              global cotangents (dZ, dvariance,
                              dlengthscale) live in whole-array output
                              blocks whose index never changes (they stay
                              resident in VMEM for the entire grid).
  * `suffstats_fused_jnp`   — numerically-matching streaming `lax.scan`
                              over N chunks; the off-TPU large-N forward.
  * `suffstats_vjp_jnp`     — the same reverse algebra as a streaming jnp
                              scan; the off-TPU large-N backward.

The single-statistic ops' reverse passes live here too — `kfu_bwd_pallas` /
`psi1_bwd_pallas` / `psi2_bwd_pallas` and their streaming jnp twins
(`kfu_vjp_jnp` / `psi1_vjp_jnp` / `psi2_vjp_jnp`) — as specializations of
the fused rules on the same tile scheme.

The Pallas forward and reverse kernels share the `_psi1_tile` / `_psi2_tile`
block helpers below, and every reverse pass (fused or single-statistic,
Pallas or jnp) shares the `_psi1_bwd_tile` / `_psi2_bwd_tile` cotangent
helpers, so the exponential a reverse pass differentiates is the
exponential the forward evaluates and the cotangent algebra exists in
exactly one place — forward and reverse formulas cannot drift. The jnp
forward pair shares `_psi1_weighted` / `_psi2_weighted` the same way (and
`_psi1_weighted` is itself a wrapper over `_psi1_tile`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 32
TILE_M = 128


# ---------------------------------------------------------------------------
# shared tile helpers (used by BOTH the forward and reverse Pallas kernels)
# ---------------------------------------------------------------------------

def _dot(a, b, dims, ct):
    # HIGHEST: the psi exponents expand (mu - z)^2 into differences of
    # these products, so a single bf16 MXU pass (the TPU default for f32
    # operands) would cancel away most of their significant bits
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=ct)


def _vma(*operands):
    """The mesh axes a pallas_call's outputs vary over: every axis any
    operand varies over. Inside `jax.shard_map` (the data-parallel losses)
    the output avals must say so; outside it this is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _psi2_prefactor(Z, variance, lengthscale, ct):
    """The (m, m')-only psi2 factor v^2 exp(-|z_m - z_m'|^2 / (4 l^2)),
    applied outside the kernels (O(M^2)). Shared by every psi2 forward and
    reverse wrapper."""
    zs = Z.astype(ct) / lengthscale.astype(ct)
    zn = jnp.sum(zs * zs, -1)
    d2 = jnp.maximum(zn[:, None] + zn[None, :]
                     - 2.0 * _dot(zs, zs, ((1,), (1,)), ct), 0.0)
    return variance.astype(ct) ** 2 * jnp.exp(-0.25 * d2)


def _psi1_tile(mu, S, z, l2, *, ct):
    """psi1 block / (v * w) for one (TN, TM) tile via the MXU factorization
    (suffstats_vjp.md eq. (1)-(2)): returns (b (TN, Q), blk (TN, TM)).

    Shared by the forward kernel, the reverse kernel, and (through
    `_psi1_weighted`) the streaming jnp twin + hand-derived VJP — every
    consumer evaluates the identical expression.
    """
    b = 1.0 / (l2 + S)
    lognorm1 = -0.5 * jnp.sum(jnp.log1p(S / l2), axis=-1, keepdims=True)
    c1 = jnp.sum(mu * mu * b, axis=-1, keepdims=True)
    mub_zt = _dot(mu * b, z, ((1,), (1,)), ct)
    b_z2t = _dot(b, z * z, ((1,), (1,)), ct)
    return b, jnp.exp(lognorm1 - 0.5 * (c1 - 2.0 * mub_zt + b_z2t))


def _psi2_tile(mu, S, z1, z2, l2, *, ct):
    """Per-point psi2 factor E (without the v^2 exp(zterm) prefactor or pad
    weight) for one (TN, TM, TM) tile (suffstats_vjp.md eq. (4)-(6)):
    returns (r (TN, Q), E (TN, TM, TM)).

    The (mu - zbar)^2 exponent is expanded so the n<->m coupling becomes
    MXU matmuls: two halfterms (A1, A2) and the rank-Q cross term. Shared by
    the forward and reverse kernels (see `_psi1_tile`).
    """
    tn, q_dim = mu.shape
    tm = z1.shape[0]
    r = 1.0 / (l2 + 2.0 * S)
    lognorm2 = -0.5 * jnp.sum(jnp.log1p(2.0 * S / l2), axis=-1, keepdims=True)
    c2 = jnp.sum(mu * mu * r, axis=-1, keepdims=True)
    mur = mu * r

    def halfterm(z):
        a = _dot(mur, z, ((1,), (1,)), ct)
        b = _dot(r, z * z, ((1,), (1,)), ct)
        return a - 0.25 * b

    A1 = halfterm(z1)
    A2 = halfterm(z2)
    # cross[n, a, b] = sum_q r[n, q] z1[a, q] z2[b, q]: one (TN*TM, Q) x
    # (Q, TM) MXU contraction, so no per-q (TN, TM, TM) temporary is live
    rz1 = (r[:, None, :] * z1[None, :, :]).reshape(tn * tm, q_dim)
    cross = _dot(rz1, z2, ((1,), (1,)), ct).reshape(tn, tm, tm)
    E = jnp.exp((lognorm2 - c2)[:, :, None] + A1[:, :, None] + A2[:, None, :]
                - 0.5 * cross)
    return r, E


# ---------------------------------------------------------------------------
# shared reverse-pass tile helpers
# ---------------------------------------------------------------------------
#
# Every input cotangent of every psi-statistic op is linear in a per-point
# branch weight — W1 (eq. (8), the psi1/psiY branch) or T (eq. (9), the psi2
# branch) — so the whole reverse pass factors into the two tile helpers
# below. The fused reverse kernel, the single-statistic reverse kernels
# (kfu/psi1/psi2), and the streaming jnp twins all call these, the same way
# every forward shares `_psi1_tile`/`_psi2_tile`: the ops differ only in how
# they build their branch weight, never in the cotangent algebra.

def _psi1_bwd_tile(mu, S, z1, l2, W1, *, ct):
    """Cotangent contributions of one (TN, TM) psi1-branch tile given branch
    weight W1 (eq. (8)): returns (dmu (TN, Q), dS (TN, Q), dz (TM, Q),
    dvraw scalar, dl (1, Q)) per eq. (10)-(14).

    `dvraw` is the raw weight total sum W1 — the caller divides by v
    (eq. (13)), which keeps v out of the tile entirely.
    """
    b = 1.0 / (l2 + S)
    ls = jnp.sqrt(l2)
    s1 = jnp.sum(W1, axis=1, keepdims=True)  # (TN, 1)
    W1Z = _dot(W1, z1, ((1,), (0,)), ct)  # (TN, Q)
    sq1 = mu * mu * s1 - 2.0 * mu * W1Z + _dot(W1, z1 * z1, ((1,), (0,)), ct)
    dmu = -b * (mu * s1 - W1Z)  # eq. (10)
    dS = -0.5 * b * s1 + 0.5 * b * b * sq1  # eq. (11)
    dz = (_dot(W1, mu * b, ((0,), (0,)), ct)
          - z1 * _dot(W1, b, ((0,), (0,)), ct))  # eq. (12)
    dvraw = jnp.sum(s1)  # eq. (13); the 1/v rides outside
    dl = jnp.sum((S * b / ls) * s1 + ls * b * b * sq1,
                 axis=0, keepdims=True)  # eq. (14)
    return dmu, dS, dz, dvraw, dl


def _psi2_bwd_tile(mu, S, z1, z2, l2, T, *, ct):
    """Cotangent contributions of one (TN, TM, TM) psi2-branch tile given
    branch weight T (eq. (9)): returns (dmu (TN, Q), dS (TN, Q),
    dz_i (TM, Q) — slot-a rows, dz_j (TM, Q) — slot-b rows, dvraw scalar,
    dl (1, Q)) per eq. (15)-(20).

    All T moments reduce to MXU contractions against z / z^2; nothing larger
    than T itself is ever live. `dvraw` is the raw weight total 2 sum T
    (eq. (19) without the 1/v, divided out by the caller).
    """
    tn, q_dim = mu.shape
    tm = z1.shape[0]
    ls = jnp.sqrt(l2)
    z1sq = z1 * z1
    z2sq = z2 * z2
    r = 1.0 / (l2 + 2.0 * S)
    row = jnp.sum(T, axis=2)  # (TN, TM)  sum over m' (slot b)
    col = jnp.sum(T, axis=1)  # (TN, TM)  sum over m  (slot a)
    t = jnp.sum(row, axis=1, keepdims=True)  # (TN, 1)
    # zbar moments (eq. (15)): u = sum_ab T zbar, w2 = sum_ab T zbar^2
    TZ2 = _dot(T.reshape(tn * tm, tm), z2, ((1,), (0,)), ct
               ).reshape(tn, tm, q_dim)
    TtZ1 = _dot(jnp.swapaxes(T, 1, 2).reshape(tn * tm, tm), z1,
                ((1,), (0,)), ct).reshape(tn, tm, q_dim)
    u = 0.5 * (_dot(row, z1, ((1,), (0,)), ct) + _dot(col, z2, ((1,), (0,)), ct))
    B = jnp.sum(z1[None, :, :] * TZ2, axis=1)  # (TN, Q) bilinear z^T T z
    w2 = 0.25 * (_dot(row, z1sq, ((1,), (0,)), ct)
                 + _dot(col, z2sq, ((1,), (0,)), ct)) + 0.5 * B
    V = mu * mu * t - 2.0 * mu * u + w2  # sum_ab T (mu - zbar)^2
    dmu = -2.0 * r * (mu * t - u)  # eq. (16)
    dS = -r * t + 2.0 * r * r * V  # eq. (17)
    dvraw = 2.0 * jnp.sum(t)  # eq. (19); the 1/v rides outside
    # eq. (20): dlengthscale — lognorm2 + exponent-r terms + the zterm term
    P = jnp.sum(T, axis=0)  # (TM, TM)
    Pr = jnp.sum(P, axis=1, keepdims=True)  # (TM, 1) row sums
    Pc = jnp.sum(P, axis=0, keepdims=True).T  # (TM, 1) column sums
    PZ2 = _dot(P, z2, ((1,), (0,)), ct)  # (TM, Q)
    PtZ1 = _dot(P, z1, ((0,), (0,)), ct)  # (TM, Q)
    # sum_ab P (z1_a - z2_b)^2 per q, factored through the P moments
    zd2 = (jnp.sum(Pr * z1sq, axis=0, keepdims=True)
           + jnp.sum(Pc * z2sq, axis=0, keepdims=True)
           - 2.0 * jnp.sum(z1 * PZ2, axis=0, keepdims=True))  # (1, Q)
    dl = ((2.0 / ls) * jnp.sum(S * r * t, axis=0, keepdims=True)
          + 2.0 * ls * jnp.sum(r * r * V, axis=0, keepdims=True)
          + zd2 / (2.0 * ls * l2))
    # eq. (18): dZ — slot-a rows (tile i) and slot-b rows (tile j)
    r_mu = r * mu
    dz_i = (_dot(row, r_mu, ((0,), (0,)), ct)
            - 0.5 * z1 * _dot(row, r, ((0,), (0,)), ct)
            - 0.5 * jnp.sum(r[:, None, :] * TZ2, axis=0)
            + (PZ2 - z1 * Pr) / (2.0 * l2))
    dz_j = (_dot(col, r_mu, ((0,), (0,)), ct)
            - 0.5 * z2 * _dot(col, r, ((0,), (0,)), ct)
            - 0.5 * jnp.sum(r[:, None, :] * TtZ1, axis=0)
            + (PtZ1 - z2 * Pc) / (2.0 * l2))
    return dmu, dS, dz_i, dz_j, dvraw, dl


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _suffstats_kernel(mu_ref, s_ref, y_ref, w_ref, z1_ref, z2_ref, l2_ref,
                      psi2_ref, psiy_ref, *, ct=jnp.float32):
    j = pl.program_id(1)
    kn = pl.program_id(2)

    mu = mu_ref[...].astype(ct)  # (TN, Q)
    S = s_ref[...].astype(ct)
    y = y_ref[...].astype(ct)  # (TN, D)
    w = w_ref[...].astype(ct)  # (TN, 1)
    z1 = z1_ref[...].astype(ct)  # (TM, Q)
    z2 = z2_ref[...].astype(ct)
    l2 = l2_ref[...].astype(ct)  # (1, Q)

    tn = mu.shape[0]
    tm = z1.shape[0]

    # ---------------- psi2 tile (shared helper; eq. (6)-(7)) -------------
    _, E = _psi2_tile(mu, S, z1, z2, l2, ct=ct)
    # weighted datapoint reduction on the MXU: (1, TN) @ (TN, TM*TM)
    contrib2 = _dot(w.T, E.reshape(tn, tm * tm), ((1,), (0,)), ct
                    ).reshape(tm, tm)

    @pl.when(kn == 0)
    def _():
        psi2_ref[...] = contrib2

    @pl.when(kn > 0)
    def _():
        psi2_ref[...] += contrib2

    # ---------------- psiY tile (shared helper; eq. (2)-(3)) -------------
    @pl.when(j == 0)
    def _():
        _, blk = _psi1_tile(mu, S, z1, l2, ct=ct)
        psi1_blk = blk * w  # (TN, TM)
        contribY = _dot(psi1_blk, y, ((0,), (0,)), ct)  # (TM, D)

        @pl.when(kn == 0)
        def _():
            psiy_ref[...] = contribY

        @pl.when(kn > 0)
        def _():
            psiy_ref[...] += contribY


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def suffstats_pallas(mu, S, Y, Z, variance, lengthscale, *,
                     interpret: bool = False, block: tuple | None = None):
    """Returns (psi2 (M, M), psiY (M, D)) accumulated over all N.

    Compiled (TPU) execution computes in float32 — the hardware dtype the
    tile sizes are chosen for. Interpret mode keeps the input dtype instead:
    it exists to validate the kernel body, and under x64 that makes parity
    checks meaningful rather than epilogue-conditioning-limited.

    `block=(tile_n, tile_m)` overrides the module-constant tiles (the
    repro.tune knob); the wrapper pads to the block's multiple, so every
    candidate is numerically identical to the defaults.
    """
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = mu.shape
    M = Z.shape[0]
    D = Y.shape[1]
    ct = mu.dtype if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    mu_p = jnp.pad(mu.astype(ct), ((0, pad_n), (0, 0)))
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    Y_p = jnp.pad(Y.astype(ct), ((0, pad_n), (0, 0)))
    w = jnp.pad(jnp.ones((N, 1), ct), ((0, pad_n), (0, 0)))
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]
    Mp = Z_p.shape[0]

    grid = (Mp // tile_m, Mp // tile_m, mu_p.shape[0] // tile_n)
    vma = _vma(mu_p, S_p, Y_p, w, Z_p, l2)
    acc2, accY = pl.pallas_call(
        functools.partial(_suffstats_kernel, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda i, j, kn: (kn, 0)),
            pl.BlockSpec((tile_n, Q), lambda i, j, kn: (kn, 0)),
            pl.BlockSpec((tile_n, D), lambda i, j, kn: (kn, 0)),
            pl.BlockSpec((tile_n, 1), lambda i, j, kn: (kn, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j, kn: (i, 0)),
            pl.BlockSpec((tile_m, Q), lambda i, j, kn: (j, 0)),
            pl.BlockSpec((1, Q), lambda i, j, kn: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, tile_m), lambda i, j, kn: (i, j)),
            pl.BlockSpec((tile_m, D), lambda i, j, kn: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Mp), ct, vma=vma),
            jax.ShapeDtypeStruct((Mp, D), ct, vma=vma),
        ],
        interpret=interpret,
    )(mu_p, S_p, Y_p, w, Z_p, Z_p, l2)

    psi2 = _psi2_prefactor(Z, variance, lengthscale, ct) * acc2[:M, :M]
    psiY = variance.astype(ct) * accY[:M]
    return psi2, psiY


# ---------------------------------------------------------------------------
# reverse kernel: same tile structure, N axis outermost
# ---------------------------------------------------------------------------
#
# Grid (kn, i, j). For a fixed datapoint tile kn, the kernel sweeps every
# (i, j) pair of inducing tiles and accumulates the per-datapoint cotangent
# blocks (dmu, dS, dY — out index kn) in place; the global cotangents
# (dZ, dvariance, dlengthscale) are single whole-array output blocks (index
# constant across the grid) updated every iteration — the grid is sequential
# per core, so no synchronization exists or is needed (same argument as the
# forward's in-place psi2 accumulation).
#
# Equation numbers reference docs/derivations/suffstats_vjp.md. The branch
# weights are W1 (eq. (8), psi1/psiY branch) and T (eq. (9), psi2 branch);
# every cotangent is linear in them, so per-tile contributions simply add.

def _suffstats_bwd_kernel(mu_ref, s_ref, y_ref, w_ref, z1_ref, z2_ref,
                          l2_ref, g2p_ref, gyv_ref,
                          dmu_ref, ds_ref, dy_ref, dz_ref, dvraw_ref, dl_ref,
                          *, tile_m, ct=jnp.float32):
    kn = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    first_mm = jnp.logical_and(i == 0, j == 0)

    mu = mu_ref[...].astype(ct)  # (TN, Q)
    S = s_ref[...].astype(ct)
    w = w_ref[...].astype(ct)  # (TN, 1)
    z1 = z1_ref[...].astype(ct)  # (TM, Q)
    z2 = z2_ref[...].astype(ct)
    l2 = l2_ref[...].astype(ct)  # (1, Q)
    g2p = g2p_ref[...].astype(ct)  # (TM, TM) = g2 * v^2 exp(zterm), padded 0

    # ---------------- psi2 branch: T = g2p . E . w  (eq. (9)) ------------
    _, E = _psi2_tile(mu, S, z1, z2, l2, ct=ct)
    T = g2p[None, :, :] * E * w[:, :, None]  # (TN, TM, TM)
    dmu_c, ds_c, dz_i, dz_j, dvraw_c, dl_c = _psi2_bwd_tile(
        mu, S, z1, z2, l2, T, ct=ct)

    # ---------------- accumulate: per-datapoint blocks -------------------
    @pl.when(first_mm)
    def _():
        dmu_ref[...] = dmu_c
        ds_ref[...] = ds_c

    @pl.when(jnp.logical_not(first_mm))
    def _():
        dmu_ref[...] += dmu_c
        ds_ref[...] += ds_c

    # ---------------- accumulate: global blocks --------------------------
    @pl.when(jnp.logical_and(kn == 0, first_mm))
    def _():
        dz_ref[...] = jnp.zeros(dz_ref.shape, ct)
        dvraw_ref[...] = jnp.zeros(dvraw_ref.shape, ct)
        dl_ref[...] = jnp.zeros(dl_ref.shape, ct)

    dz_ref[pl.dslice(i * tile_m, tile_m), :] += dz_i
    dz_ref[pl.dslice(j * tile_m, tile_m), :] += dz_j
    dvraw_ref[...] += dvraw_c
    dl_ref[...] += dl_c

    # ---------------- psi1/psiY branch (once per (kn, i); eq. (10)-(14)) -
    @pl.when(j == 0)
    def _():
        y = y_ref[...].astype(ct)  # (TN, D)
        gyv = gyv_ref[...].astype(ct)  # (TM, D) = v * gY, padded 0
        _, blk = _psi1_tile(mu, S, z1, l2, ct=ct)
        blk = blk * w  # psi1 / v, pad-masked
        W1 = _dot(y, gyv, ((1,), (1,)), ct) * blk  # (TN, TM)  eq. (8)
        dmu1, ds1, dz1, dvraw1, dl1 = _psi1_bwd_tile(mu, S, z1, l2, W1, ct=ct)
        dmu_ref[...] += dmu1
        ds_ref[...] += ds1
        dvraw_ref[...] += dvraw1
        dl_ref[...] += dl1
        dz_ref[pl.dslice(i * tile_m, tile_m), :] += dz1
        dy_c = _dot(blk, gyv, ((1,), (0,)), ct)  # (TN, D)

        @pl.when(i == 0)
        def _():
            dy_ref[...] = dy_c

        @pl.when(i > 0)
        def _():
            dy_ref[...] += dy_c


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def suffstats_bwd_pallas(mu, S, Y, Z, variance, lengthscale, g2, gY, *,
                         interpret: bool = False, block: tuple | None = None):
    """Pallas reverse pass of ``(psi2, psiY) = suffstats(...)``.

    Returns cotangents ``(dmu, dS, dY, dZ, dvariance, dlengthscale)`` given
    output cotangents ``g2 (M, M)`` and ``gY (M, D)``. Same dtype policy as
    the forward: compiled TPU execution computes in float32, interpret mode
    keeps the input dtype so f64 parity tests check the kernel body itself.

    The (m, m')-only psi2 prefactor v^2 exp(zterm) is folded into the
    cotangent outside the kernel (eq. (9)): the kernel sees
    G2p = g2 * v^2 exp(zterm), padded with zeros so padded inducing rows
    contribute nothing; gY is pre-scaled by v the same way. The variance
    cotangent leaves the kernel as the raw branch weight total
    sum W1 + 2 sum T (eq. (13)+(19)) and is divided by v here.

    `block=(tile_n, tile_m)` overrides the module-constant tiles (the
    repro.tune knob); padding makes any block choice numerically identical.
    """
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = mu.shape
    M = Z.shape[0]
    D = Y.shape[1]
    ct = mu.dtype if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    mu_p = jnp.pad(mu.astype(ct), ((0, pad_n), (0, 0)))
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    Y_p = jnp.pad(Y.astype(ct), ((0, pad_n), (0, 0)))
    w = jnp.pad(jnp.ones((N, 1), ct), ((0, pad_n), (0, 0)))
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]
    v = variance.astype(ct)

    g2p = jnp.pad(g2.astype(ct) * _psi2_prefactor(Z, variance, lengthscale, ct),
                  ((0, pad_m), (0, pad_m)))
    gyv = jnp.pad(v * gY.astype(ct), ((0, pad_m), (0, 0)))

    Np = mu_p.shape[0]
    Mp = Z_p.shape[0]
    grid = (Np // tile_n, Mp // tile_m, Mp // tile_m)
    vma = _vma(mu_p, S_p, Y_p, w, Z_p, l2, g2p, gyv)
    dmu, dS, dY, dZ, dvraw, dl = pl.pallas_call(
        functools.partial(_suffstats_bwd_kernel, tile_m=tile_m, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # mu
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # S
            pl.BlockSpec((tile_n, D), lambda kn, i, j: (kn, 0)),  # Y
            pl.BlockSpec((tile_n, 1), lambda kn, i, j: (kn, 0)),  # w
            pl.BlockSpec((tile_m, Q), lambda kn, i, j: (i, 0)),  # Z (slot a)
            pl.BlockSpec((tile_m, Q), lambda kn, i, j: (j, 0)),  # Z (slot b)
            pl.BlockSpec((1, Q), lambda kn, i, j: (0, 0)),  # l^2
            pl.BlockSpec((tile_m, tile_m), lambda kn, i, j: (i, j)),  # G2p
            pl.BlockSpec((tile_m, D), lambda kn, i, j: (i, 0)),  # v * gY
        ],
        out_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # dmu
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # dS
            pl.BlockSpec((tile_n, D), lambda kn, i, j: (kn, 0)),  # dY
            pl.BlockSpec((Mp, Q), lambda kn, i, j: (0, 0)),  # dZ (resident)
            pl.BlockSpec((1, 1), lambda kn, i, j: (0, 0)),  # dv_raw
            pl.BlockSpec((1, Q), lambda kn, i, j: (0, 0)),  # dl
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Np, D), ct, vma=vma),
            jax.ShapeDtypeStruct((Mp, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((1, 1), ct, vma=vma),
            jax.ShapeDtypeStruct((1, Q), ct, vma=vma),
        ],
        interpret=interpret,
    )(mu_p, S_p, Y_p, w, Z_p, Z_p, l2, g2p, gyv)
    return (dmu[:N].astype(mu.dtype), dS[:N].astype(S.dtype),
            dY[:N].astype(Y.dtype), dZ[:M].astype(Z.dtype),
            (dvraw[0, 0] / v).astype(variance.dtype),
            dl[0].astype(lengthscale.dtype))


# ---------------------------------------------------------------------------
# single-statistic reverse kernels (kfu / psi1 / psi2)
# ---------------------------------------------------------------------------
#
# The single-statistic ops' reverse passes are specializations of the fused
# rules — the cotangent algebra is identical, only the branch weight changes
# (docs/derivations/suffstats_vjp.md §"Single-statistic specializations"):
#
#   psi1 op:  W1[n,m] = g1[n,m] · psi1[n,m]   (the output cotangent itself
#             weights psi1, where the fused op weights by gY·Y)
#   kfu op:   psi1 at S = 0 (psi1 IS the S-smoothed K_fu), dS discarded
#   psi2 op:  T exactly as the fused psi2 branch (eq. (9))
#
# so the kernels below are the fused reverse kernel with one branch removed,
# on the same tile helpers and the same grid/accumulation scheme.

def _psi1_bwd_kernel(mu_ref, s_ref, z_ref, l2_ref, gv_ref,
                     dmu_ref, ds_ref, dz_ref, dvraw_ref, dl_ref,
                     *, tile_m, ct=jnp.float32):
    kn = pl.program_id(0)
    i = pl.program_id(1)

    mu = mu_ref[...].astype(ct)  # (TN, Q)
    S = s_ref[...].astype(ct)
    z = z_ref[...].astype(ct)  # (TM, Q)
    l2 = l2_ref[...].astype(ct)  # (1, Q)
    gv = gv_ref[...].astype(ct)  # (TN, TM) = v * g, zero-padded both axes

    # shared forward tile: blk = psi1 / v; zero-padded gv rows/cols kill
    # every padded contribution, so no separate pad-weight input is needed
    _, blk = _psi1_tile(mu, S, z, l2, ct=ct)
    W1 = gv * blk  # eq. (8) specialized: W1 = g1 . psi1
    dmu_c, ds_c, dz_c, dvraw_c, dl_c = _psi1_bwd_tile(mu, S, z, l2, W1, ct=ct)

    @pl.when(i == 0)
    def _():
        dmu_ref[...] = dmu_c
        ds_ref[...] = ds_c

    @pl.when(i > 0)
    def _():
        dmu_ref[...] += dmu_c
        ds_ref[...] += ds_c

    @pl.when(jnp.logical_and(kn == 0, i == 0))
    def _():
        dz_ref[...] = jnp.zeros(dz_ref.shape, ct)
        dvraw_ref[...] = jnp.zeros(dvraw_ref.shape, ct)
        dl_ref[...] = jnp.zeros(dl_ref.shape, ct)

    dz_ref[pl.dslice(i * tile_m, tile_m), :] += dz_c
    dvraw_ref[...] += dvraw_c
    dl_ref[...] += dl_c


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def psi1_bwd_pallas(mu, S, Z, variance, lengthscale, g, *,
                    interpret: bool = False, block: tuple | None = None):
    """Pallas reverse pass of ``psi1 = psi1_pallas(...)``.

    Returns cotangents ``(dmu, dS, dZ, dvariance, dlengthscale)`` given the
    output cotangent ``g (N, M)``. Grid (kn, i): per-datapoint blocks
    (dmu, dS) accumulate the inducing tiles in place, the global cotangents
    (dZ, dvariance, dlengthscale) live in constant-index VMEM-resident
    blocks — the fused reverse kernel's scheme with the psi2 branch removed.
    v is folded into the cotangent (gv = v * g) so it never enters the
    kernel; the raw variance weight sum W1 is divided by v here (eq. (13)).
    Interpret-mode dtype policy matches the single-statistic forwards:
    computes in the input dtype promoted to at least f32.

    `block=(tile_n, tile_m)` overrides the module-constant tiles (the
    repro.tune knob); padding makes any block choice numerically identical.
    """
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = mu.shape
    M = Z.shape[0]
    ct = jnp.promote_types(mu.dtype, jnp.float32) if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    mu_p = jnp.pad(mu.astype(ct), ((0, pad_n), (0, 0)))
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]
    v = variance.astype(ct)
    gv = jnp.pad(v * g.astype(ct), ((0, pad_n), (0, pad_m)))

    Np = mu_p.shape[0]
    Mp = Z_p.shape[0]
    grid = (Np // tile_n, Mp // tile_m)
    vma = _vma(mu_p, S_p, Z_p, l2, gv)
    dmu, dS, dZ, dvraw, dl = pl.pallas_call(
        functools.partial(_psi1_bwd_kernel, tile_m=tile_m, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i: (kn, 0)),  # mu
            pl.BlockSpec((tile_n, Q), lambda kn, i: (kn, 0)),  # S
            pl.BlockSpec((tile_m, Q), lambda kn, i: (i, 0)),  # Z
            pl.BlockSpec((1, Q), lambda kn, i: (0, 0)),  # l^2
            pl.BlockSpec((tile_n, tile_m), lambda kn, i: (kn, i)),  # v * g
        ],
        out_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i: (kn, 0)),  # dmu
            pl.BlockSpec((tile_n, Q), lambda kn, i: (kn, 0)),  # dS
            pl.BlockSpec((Mp, Q), lambda kn, i: (0, 0)),  # dZ (resident)
            pl.BlockSpec((1, 1), lambda kn, i: (0, 0)),  # dv_raw
            pl.BlockSpec((1, Q), lambda kn, i: (0, 0)),  # dl
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Mp, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((1, 1), ct, vma=vma),
            jax.ShapeDtypeStruct((1, Q), ct, vma=vma),
        ],
        interpret=interpret,
    )(mu_p, S_p, Z_p, l2, gv)
    return (dmu[:N].astype(mu.dtype), dS[:N].astype(S.dtype),
            dZ[:M].astype(Z.dtype), (dvraw[0, 0] / v).astype(variance.dtype),
            dl[0].astype(lengthscale.dtype))


def kfu_bwd_pallas(X, Z, variance, lengthscale, g, *, interpret: bool = False,
                   block: tuple | None = None):
    """Pallas reverse pass of ``Kfu = kfu_pallas(...)``: the S -> 0
    specialization of the psi1 reverse kernel (K_fu is psi1 with zero
    latent variance; suffstats_vjp.md §"Exact statistics"). Returns
    ``(dX, dZ, dvariance, dlengthscale)``."""
    dX, _, dZ, dv, dl = psi1_bwd_pallas(X, jnp.zeros_like(X), Z, variance,
                                        lengthscale, g, interpret=interpret,
                                        block=block)
    return dX, dZ, dv, dl


def _psi2_bwd_kernel(mu_ref, s_ref, w_ref, z1_ref, z2_ref, l2_ref, g2p_ref,
                     dmu_ref, ds_ref, dz_ref, dvraw_ref, dl_ref,
                     *, tile_m, ct=jnp.float32):
    kn = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    first_mm = jnp.logical_and(i == 0, j == 0)

    mu = mu_ref[...].astype(ct)  # (TN, Q)
    S = s_ref[...].astype(ct)
    w = w_ref[...].astype(ct)  # (TN, 1)
    z1 = z1_ref[...].astype(ct)  # (TM, Q)
    z2 = z2_ref[...].astype(ct)
    l2 = l2_ref[...].astype(ct)  # (1, Q)
    g2p = g2p_ref[...].astype(ct)  # (TM, TM) = g2 * v^2 exp(zterm), padded 0

    # the fused kernel's psi2 branch, verbatim: same shared helpers
    _, E = _psi2_tile(mu, S, z1, z2, l2, ct=ct)
    T = g2p[None, :, :] * E * w[:, :, None]  # (TN, TM, TM)  eq. (9)
    dmu_c, ds_c, dz_i, dz_j, dvraw_c, dl_c = _psi2_bwd_tile(
        mu, S, z1, z2, l2, T, ct=ct)

    @pl.when(first_mm)
    def _():
        dmu_ref[...] = dmu_c
        ds_ref[...] = ds_c

    @pl.when(jnp.logical_not(first_mm))
    def _():
        dmu_ref[...] += dmu_c
        ds_ref[...] += ds_c

    @pl.when(jnp.logical_and(kn == 0, first_mm))
    def _():
        dz_ref[...] = jnp.zeros(dz_ref.shape, ct)
        dvraw_ref[...] = jnp.zeros(dvraw_ref.shape, ct)
        dl_ref[...] = jnp.zeros(dl_ref.shape, ct)

    dz_ref[pl.dslice(i * tile_m, tile_m), :] += dz_i
    dz_ref[pl.dslice(j * tile_m, tile_m), :] += dz_j
    dvraw_ref[...] += dvraw_c
    dl_ref[...] += dl_c


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def psi2_bwd_pallas(mu, S, Z, variance, lengthscale, g2, *,
                    interpret: bool = False, block: tuple | None = None):
    """Pallas reverse pass of ``psi2 = psi2_pallas(...)``.

    Returns cotangents ``(dmu, dS, dZ, dvariance, dlengthscale)`` given the
    output cotangent ``g2 (M, M)``. This is `suffstats_bwd_pallas` with the
    psi1/psiY branch removed: same grid (kn, i, j), same per-datapoint /
    VMEM-resident output split, same folded prefactor
    G2p = g2 * v^2 exp(zterm) (eq. (9)) padded with zeros. Interpret-mode
    dtype policy matches the single-statistic forwards.

    `block=(tile_n, tile_m)` overrides the module-constant tiles (the
    repro.tune knob); padding makes any block choice numerically identical.
    """
    tile_n, tile_m = block if block is not None else (TILE_N, TILE_M)
    N, Q = mu.shape
    M = Z.shape[0]
    ct = jnp.promote_types(mu.dtype, jnp.float32) if interpret else jnp.float32
    pad_n = (-N) % tile_n
    pad_m = (-M) % tile_m
    mu_p = jnp.pad(mu.astype(ct), ((0, pad_n), (0, 0)))
    S_p = jnp.pad(S.astype(ct), ((0, pad_n), (0, 0)), constant_values=1.0)
    w = jnp.pad(jnp.ones((N, 1), ct), ((0, pad_n), (0, 0)))
    Z_p = jnp.pad(Z.astype(ct), ((0, pad_m), (0, 0)))
    l2 = (lengthscale.astype(ct) ** 2)[None, :]
    v = variance.astype(ct)

    g2p = jnp.pad(g2.astype(ct) * _psi2_prefactor(Z, variance, lengthscale, ct),
                  ((0, pad_m), (0, pad_m)))

    Np = mu_p.shape[0]
    Mp = Z_p.shape[0]
    grid = (Np // tile_n, Mp // tile_m, Mp // tile_m)
    vma = _vma(mu_p, S_p, w, Z_p, l2, g2p)
    dmu, dS, dZ, dvraw, dl = pl.pallas_call(
        functools.partial(_psi2_bwd_kernel, tile_m=tile_m, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # mu
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # S
            pl.BlockSpec((tile_n, 1), lambda kn, i, j: (kn, 0)),  # w
            pl.BlockSpec((tile_m, Q), lambda kn, i, j: (i, 0)),  # Z (slot a)
            pl.BlockSpec((tile_m, Q), lambda kn, i, j: (j, 0)),  # Z (slot b)
            pl.BlockSpec((1, Q), lambda kn, i, j: (0, 0)),  # l^2
            pl.BlockSpec((tile_m, tile_m), lambda kn, i, j: (i, j)),  # G2p
        ],
        out_specs=[
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # dmu
            pl.BlockSpec((tile_n, Q), lambda kn, i, j: (kn, 0)),  # dS
            pl.BlockSpec((Mp, Q), lambda kn, i, j: (0, 0)),  # dZ (resident)
            pl.BlockSpec((1, 1), lambda kn, i, j: (0, 0)),  # dv_raw
            pl.BlockSpec((1, Q), lambda kn, i, j: (0, 0)),  # dl
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Np, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((Mp, Q), ct, vma=vma),
            jax.ShapeDtypeStruct((1, 1), ct, vma=vma),
            jax.ShapeDtypeStruct((1, Q), ct, vma=vma),
        ],
        interpret=interpret,
    )(mu_p, S_p, w, Z_p, Z_p, l2, g2p)
    return (dmu[:N].astype(mu.dtype), dS[:N].astype(S.dtype),
            dZ[:M].astype(Z.dtype), (dvraw[0, 0] / v).astype(variance.dtype),
            dl[0].astype(lengthscale.dtype))


# ---------------------------------------------------------------------------
# streaming jnp twin of the forward kernel (off-TPU large-N path)
# ---------------------------------------------------------------------------

def _pad_stream(mu, S, Y, chunk):
    """Pad the N axis to a chunk multiple; returns per-chunk xs + weights."""
    N, Q = mu.shape
    D = Y.shape[1]
    pad = (-N) % chunk
    mu_p = jnp.pad(mu, ((0, pad), (0, 0)))
    # pad S with ones (any positive value) and mask via weight w
    S_p = jnp.pad(S, ((0, pad), (0, 0)), constant_values=1.0)
    Y_p = jnp.pad(Y, ((0, pad), (0, 0)))
    w = jnp.pad(jnp.ones((N,), mu.dtype), ((0, pad),))
    k = (N + pad) // chunk
    return (mu_p.reshape(k, chunk, Q), S_p.reshape(k, chunk, Q),
            Y_p.reshape(k, chunk, D), w.reshape(k, chunk))


def _psi1_weighted(mu_i, S_i, w_i, Z, l2):
    """psi1 block / variance with pad weights folded in: returns
    (b (chunk, Q), blk (chunk, M)).

    A wrapper over the shared `_psi1_tile` — the streaming forward, the
    hand-derived VJP, and the Pallas kernels all evaluate the identical
    expression, or the registered gradient would be wrong.
    """
    b, blk = _psi1_tile(mu_i, S_i, Z, l2[None, :], ct=mu_i.dtype)
    return b, blk * w_i[:, None]


def _psi2_weighted(mu_i, S_i, w_i, zbar, l2):
    """Per-point psi2 factor exp(lognorm2 + e2) (without the v^2 exp(zterm)
    prefactor), pad weights folded in: returns (r (chunk, Q), E (chunk, M, M)).
    Shared by the streaming forward and the hand-derived VJP (see above)."""
    Q = mu_i.shape[1]
    M = zbar.shape[0]
    r = 1.0 / (l2[None, :] + 2.0 * S_i)
    lognorm2 = -0.5 * jnp.sum(jnp.log1p(2.0 * S_i / l2[None, :]), axis=-1)
    expo = jnp.zeros((mu_i.shape[0], M, M), mu_i.dtype)
    for q in range(Q):  # Q is small (latent dim); unrolled
        dq = mu_i[:, None, None, q] - zbar[None, :, :, q]
        expo = expo - dq * dq * r[:, None, None, q]
    return r, jnp.exp(lognorm2[:, None, None] + expo) * w_i[:, None, None]


def suffstats_fused_jnp(mu, S, Y, Z, variance, lengthscale, *, chunk: int = 1024):
    """(psi2 (M, M), psiY (M, D)) by one streaming jnp pass over N — the same
    math and accumulation order as `suffstats_pallas`, O(chunk * M^2) live."""
    N, Q = mu.shape
    M = Z.shape[0]
    D = Y.shape[1]
    l2 = lengthscale**2
    zdiff = Z[:, None, :] - Z[None, :, :]
    zterm = -jnp.sum(zdiff**2 / (4.0 * l2), axis=-1)  # (M, M)
    zbar = 0.5 * (Z[:, None, :] + Z[None, :, :])

    xs = _pad_stream(mu, S, Y, chunk)

    def body(acc, x):
        mu_i, S_i, Y_i, w_i = x
        acc2, accY = acc
        _, psi1_blk = _psi1_weighted(mu_i, S_i, w_i, Z, l2)  # (chunk, M)
        accY = accY + variance * psi1_blk.T @ Y_i
        _, E = _psi2_weighted(mu_i, S_i, w_i, zbar, l2)  # (chunk, M, M)
        acc2 = acc2 + jnp.sum(E, axis=0)
        return (acc2, accY), None

    # `+ 0 * mu[0, 0]` inherits mu's varying-manual-axes type so the scan
    # carry is well-typed when this runs inside shard_map (see shard_map-vma).
    vma = 0.0 * mu[0, 0]
    acc0 = (jnp.zeros((M, M), mu.dtype) + vma, jnp.zeros((M, D), mu.dtype) + vma)
    (acc2, accY), _ = jax.lax.scan(body, acc0, xs)
    return variance**2 * jnp.exp(zterm) * acc2, accY


# ---------------------------------------------------------------------------
# hand-derived reverse pass as a streaming jnp scan over N
# ---------------------------------------------------------------------------
#
# Same algebra as the Pallas reverse kernel above (equation numbers from
# docs/derivations/suffstats_vjp.md), expressed as a second streaming kernel
# over N: per-datapoint cotangents (dmu, dS, dY) leave chunk by chunk,
# global cotangents (dZ, dvariance, dlengthscale) ride the scan carry. Peak
# live memory is O(chunk * M^2), matching the forward. Since z1 == z2 == Z
# here, the two dZ slot contributions of eq. (18) are evaluated in their
# symmetrized form (T + T^T).

def suffstats_vjp_jnp(mu, S, Y, Z, variance, lengthscale, g2, gY, *,
                      chunk: int = 512):
    """Hand-derived VJP of ``(psi2, psiY) = suffstats(...)``.

    Returns cotangents ``(dmu, dS, dY, dZ, dvariance, dlengthscale)``.
    Validated against jax.grad of the jnp reference formulas in
    tests/test_streaming.py and tests/test_suffstats_bwd.py.
    """
    N, Q = mu.shape
    M = Z.shape[0]
    dt = mu.dtype
    v = variance.astype(dt)
    ls = lengthscale.astype(dt)
    l2 = ls**2
    g2 = g2.astype(dt)
    gY = gY.astype(dt)
    zdiff = Z[:, None, :] - Z[None, :, :]  # (M, M, Q)
    zterm = -jnp.sum(zdiff**2 / (4.0 * l2), axis=-1)
    zbar = 0.5 * (Z[:, None, :] + Z[None, :, :])
    # fold the (m, m')-only psi2 prefactor v^2 exp(zterm) into the cotangent
    G2p = g2 * v**2 * jnp.exp(zterm)  # (M, M)  — eq. (9)
    Z2 = Z * Z

    xs = _pad_stream(mu, S, Y, chunk)

    def body(carry, x):
        dZ_a, dv_a, dl_a = carry
        mu_i, S_i, Y_i, w_i = x
        # ---------------- psi1 branch (eq. (8), (10)-(14)) ----------------
        b, blk = _psi1_weighted(mu_i, S_i, w_i, Z, l2)  # (c, Q), (c, M)
        psi1w = v * blk  # (c, M)
        W1 = (Y_i @ gY.T) * psi1w  # (c, M)  — eq. (8)
        dY_i = psi1w @ gY  # (c, D)
        s1 = jnp.sum(W1, axis=1)  # (c,)
        W1Z = W1 @ Z  # (c, Q)
        # sum_m W1 (mu - z_m)^2, factored through Z moments
        sq1 = mu_i**2 * s1[:, None] - 2.0 * mu_i * W1Z + W1 @ Z2
        dmu_i = -b * (mu_i * s1[:, None] - W1Z)  # eq. (10)
        dS_i = -0.5 * b * s1[:, None] + 0.5 * b * b * sq1  # eq. (11)
        dZ_c = W1.T @ (mu_i * b) - Z * (W1.T @ b)  # (M, Q)  — eq. (12)
        dv_c = jnp.sum(s1) / v  # eq. (13)
        dl_c = jnp.sum((S_i * b / ls) * s1[:, None] + ls * b * b * sq1,
                       axis=0)  # eq. (14)
        # ---------------- psi2 branch (eq. (9), (15)-(20)) ----------------
        r, E = _psi2_weighted(mu_i, S_i, w_i, zbar, l2)  # (c, Q), (c, M, M)
        T = G2p[None, :, :] * E  # (c, M, M)  — eq. (9)
        t = jnp.sum(T, axis=(1, 2))  # (c,)
        rc = jnp.sum(T, axis=2) + jnp.sum(T, axis=1)  # (c, M) row + col sums
        u = 0.5 * rc @ Z  # (c, Q): sum_mm' T zbar        — eq. (15)
        B = jnp.einsum("nab,aq,bq->nq", T, Z, Z)  # (c, Q) bilinear z^T T z
        w2 = 0.25 * (rc @ Z2) + 0.5 * B  # sum_mm' T zbar^2
        V = mu_i**2 * t[:, None] - 2.0 * mu_i * u + w2  # sum_mm' T (mu-zbar)^2
        dmu_i = dmu_i - 2.0 * r * (mu_i * t[:, None] - u)  # eq. (16)
        dS_i = dS_i - r * t[:, None] + 2.0 * r * r * V  # eq. (17)
        # eq. (18), symmetrized: zbar appears in both slots — symmetrize T
        # once, then the two slot sums collapse to a single contraction
        # (psi2_n is m<->m' even).
        Ts = T + jnp.swapaxes(T, 1, 2)
        Ps = jnp.sum(Ts, axis=0)  # (M, M)
        dZ_c = dZ_c - (Z * jnp.sum(Ps, axis=1)[:, None] - Ps @ Z) / (2.0 * l2)
        dZ_c = dZ_c + jnp.einsum("nk,nq->kq", rc, r * mu_i) \
            - 0.5 * Z * jnp.einsum("nk,nq->kq", rc, r) \
            - 0.5 * jnp.einsum("nkm,mq,nq->kq", Ts, Z, r)
        dv_c = dv_c + 2.0 * jnp.sum(t) / v  # eq. (19)
        dl_c = dl_c + (2.0 / ls) * jnp.sum((S_i * r) * t[:, None], axis=0) \
            + 2.0 * ls * jnp.sum(r * r * V, axis=0) \
            + jnp.einsum("ab,abq->q", jnp.sum(T, axis=0), zdiff**2) \
            / (2.0 * ls**3)  # eq. (20)
        return (dZ_a + dZ_c, dv_a + dv_c, dl_a + dl_c), (dmu_i, dS_i, dY_i)

    vma = 0.0 * mu[0, 0]
    # dvariance rides the carry as (1,): rank-0 scan carries trip this jax
    # version's shard_map transpose spec check (see gp/stats.py)
    carry0 = (jnp.zeros((M, Q), dt) + vma, jnp.zeros((1,), dt) + vma,
              jnp.zeros((Q,), dt) + vma)
    (dZ, dv, dl), (dmu_s, dS_s, dY_s) = jax.lax.scan(body, carry0, xs)
    dmu = dmu_s.reshape(-1, Q)[:N]
    dS = dS_s.reshape(-1, Q)[:N]
    dY = dY_s.reshape(-1, Y.shape[1])[:N]
    return (dmu.astype(mu.dtype), dS.astype(S.dtype), dY.astype(Y.dtype),
            dZ.astype(Z.dtype), dv[0].astype(variance.dtype),
            dl.astype(lengthscale.dtype))


# ---------------------------------------------------------------------------
# streaming jnp twins of the single-statistic reverse passes
# ---------------------------------------------------------------------------
#
# The off-TPU large-N backward of the kfu/psi1/psi2 ops: the same tile
# helpers the Pallas reverse kernels call, driven by a lax.scan over N
# chunks instead of a grid. Per-datapoint cotangents (dmu, dS) leave chunk
# by chunk; global cotangents (dZ, dvariance, dlengthscale) ride the carry.
# Peak live memory is O(chunk * M) for psi1/kfu and O(chunk * M^2) for
# psi2 — never an (N, M, Q) reference-formula residual.

def psi1_vjp_jnp(mu, S, Z, variance, lengthscale, g, *, chunk: int = 512):
    """Hand-derived VJP of ``psi1 = psi1_rbf(...)`` as a streaming scan.

    Returns cotangents ``(dmu, dS, dZ, dvariance, dlengthscale)`` given the
    output cotangent ``g (N, M)``.
    """
    N, Q = mu.shape
    M = Z.shape[0]
    dt = jnp.promote_types(mu.dtype, jnp.float32)
    v = variance.astype(dt)
    ls = lengthscale.astype(dt)
    l2 = (ls**2)[None, :]
    Zc = Z.astype(dt)
    pad = (-N) % chunk
    mu_p = jnp.pad(mu.astype(dt), ((0, pad), (0, 0)))
    S_p = jnp.pad(S.astype(dt), ((0, pad), (0, 0)), constant_values=1.0)
    # zero-padded cotangent rows kill every padded contribution (eq. (8))
    gv_p = jnp.pad(v * g.astype(dt), ((0, pad), (0, 0)))
    k = (N + pad) // chunk
    xs = (mu_p.reshape(k, chunk, Q), S_p.reshape(k, chunk, Q),
          gv_p.reshape(k, chunk, M))

    def body(carry, x):
        dZ_a, dv_a, dl_a = carry
        mu_i, S_i, gv_i = x
        _, blk = _psi1_tile(mu_i, S_i, Zc, l2, ct=dt)  # psi1 / v
        W1 = gv_i * blk  # eq. (8) specialized: W1 = g1 . psi1
        dmu_i, dS_i, dz_c, dvraw_c, dl_c = _psi1_bwd_tile(
            mu_i, S_i, Zc, l2, W1, ct=dt)
        return (dZ_a + dz_c, dv_a + dvraw_c[None], dl_a + dl_c[0]), \
            (dmu_i, dS_i)

    vma = 0.0 * mu_p[0, 0]
    # dvariance rides the carry as (1,) — see suffstats_vjp_jnp
    carry0 = (jnp.zeros((M, Q), dt) + vma, jnp.zeros((1,), dt) + vma,
              jnp.zeros((Q,), dt) + vma)
    (dZ, dvraw, dl), (dmu_s, dS_s) = jax.lax.scan(body, carry0, xs)
    return (dmu_s.reshape(-1, Q)[:N].astype(mu.dtype),
            dS_s.reshape(-1, Q)[:N].astype(S.dtype),
            dZ.astype(Z.dtype), (dvraw[0] / v).astype(variance.dtype),
            dl.astype(lengthscale.dtype))


def kfu_vjp_jnp(X, Z, variance, lengthscale, g, *, chunk: int = 512):
    """Hand-derived VJP of ``Kfu = kfu_rbf(...)``: the S -> 0 specialization
    of the psi1 twin. Returns ``(dX, dZ, dvariance, dlengthscale)``."""
    dX, _, dZ, dv, dl = psi1_vjp_jnp(X, jnp.zeros_like(X), Z, variance,
                                     lengthscale, g, chunk=chunk)
    return dX, dZ, dv, dl


def psi2_vjp_jnp(mu, S, Z, variance, lengthscale, g2, *, chunk: int = 512):
    """Hand-derived VJP of ``psi2 = psi2_rbf(...)`` as a streaming scan.

    Returns cotangents ``(dmu, dS, dZ, dvariance, dlengthscale)`` given the
    output cotangent ``g2 (M, M)``. Since z1 == z2 == Z, the two dZ slot
    contributions of eq. (18) are summed.
    """
    N, Q = mu.shape
    M = Z.shape[0]
    dt = jnp.promote_types(mu.dtype, jnp.float32)
    v = variance.astype(dt)
    ls = lengthscale.astype(dt)
    l2 = (ls**2)[None, :]
    Zc = Z.astype(dt)
    # fold the (m, m')-only prefactor v^2 exp(zterm) into the cotangent
    G2p = g2.astype(dt) * _psi2_prefactor(Zc, v, ls, dt)  # (M, M) — eq. (9)

    pad = (-N) % chunk
    mu_p = jnp.pad(mu.astype(dt), ((0, pad), (0, 0)))
    S_p = jnp.pad(S.astype(dt), ((0, pad), (0, 0)), constant_values=1.0)
    w = jnp.pad(jnp.ones((N,), dt), ((0, pad),))
    k = (N + pad) // chunk
    xs = (mu_p.reshape(k, chunk, Q), S_p.reshape(k, chunk, Q),
          w.reshape(k, chunk))

    def body(carry, x):
        dZ_a, dv_a, dl_a = carry
        mu_i, S_i, w_i = x
        _, E = _psi2_tile(mu_i, S_i, Zc, Zc, l2, ct=dt)  # (c, M, M)
        T = G2p[None, :, :] * E * w_i[:, None, None]  # eq. (9)
        dmu_i, dS_i, dz_i, dz_j, dvraw_c, dl_c = _psi2_bwd_tile(
            mu_i, S_i, Zc, Zc, l2, T, ct=dt)
        return (dZ_a + dz_i + dz_j, dv_a + dvraw_c[None], dl_a + dl_c[0]), \
            (dmu_i, dS_i)

    vma = 0.0 * mu_p[0, 0]
    carry0 = (jnp.zeros((M, Q), dt) + vma, jnp.zeros((1,), dt) + vma,
              jnp.zeros((Q,), dt) + vma)
    (dZ, dvraw, dl), (dmu_s, dS_s) = jax.lax.scan(body, carry0, xs)
    return (dmu_s.reshape(-1, Q)[:N].astype(mu.dtype),
            dS_s.reshape(-1, Q)[:N].astype(S.dtype),
            dZ.astype(Z.dtype), (dvraw[0] / v).astype(variance.dtype),
            dl.astype(lengthscale.dtype))
