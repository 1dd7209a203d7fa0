"""Differentiable jit'd wrappers around the Pallas psi-statistic kernels.

Forward = Pallas kernel (interpret-mode on CPU, compiled on TPU). Backward =
the HAND-DERIVED reverse passes (kernels/suffstats.py, the paper's Table-2
gradient loops expressed as closed-form reverse rules) for the fused
`suffstats` op AND the single-statistic ops (`kfu`/`psi1`/`psi2` specialize
the fused rules — see docs/derivations/suffstats_vjp.md). Every op's
reverse-pass implementation is selected by a static `bwd_backend` knob:

  * ``"auto"``   (default) — mirror the forward's three-way dispatch: the
    Pallas reverse kernel compiled on TPU, the same kernel body in interpret
    mode off-TPU for small N, and the streaming-jnp reverse scan off-TPU for
    large N. This is the only knob value callers normally need.
  * ``"pallas"`` — force the Pallas reverse kernel (interpret off-TPU even
    at large N: slow, for validation).
  * ``"jnp"``    — force the streaming-jnp reverse scan everywhere.

Tile selection: every entry point resolves its forward and reverse block
configuration through the `repro.tune` autotuner (`tune.best_blocks`) unless
the caller pins `block=`/`bwd_block=` explicitly. With tuning disabled and a
cold cache that resolution returns None — the kernels' module-constant tiles
— at dict-lookup cost; with a tuned cache the measured winner is baked into
the (bounded, per-knob) cached custom_vjp op.

`interpret_mode()` flips automatically: True off-TPU so the whole test/bench
suite exercises the real kernel bodies on CPU. It reads the backend at call
time (import-time freezing would mis-dispatch after a test fixture or
`jax.config` forces a platform post-import); `_INTERPRET_OVERRIDE` is the
test-visible override. Because interpret mode pays a Python-level cost per
grid point, the reverse dispatch only runs the kernel bodies off-TPU up to
`fused_interpret_max_n()` datapoints; beyond that it switches to the
numerically-matching streaming-jnp twins.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.kfu import kfu_pallas
from repro.kernels.psi1 import psi1_pallas
from repro.kernels.psi2 import psi2_pallas
from repro.kernels.suffstats import (
    kfu_bwd_pallas,
    kfu_vjp_jnp,
    psi1_bwd_pallas,
    psi1_vjp_jnp,
    psi2_bwd_pallas,
    psi2_vjp_jnp,
    suffstats_bwd_pallas,
    suffstats_fused_jnp,
    suffstats_pallas,
    suffstats_vjp_jnp,
    _vma,
)

# Test-visible override for `interpret_mode()`: None = detect from the
# backend at call time; True/False force a path (restore to None after).
_INTERPRET_OVERRIDE: bool | None = None


def interpret_mode() -> bool:
    """Whether the Pallas kernel bodies should run in interpret mode.

    Read at CALL time, not import time: `jax.default_backend()` is itself
    cached by jax and invalidated when the platform config changes, so a
    test fixture (or `jax.config.update("jax_platform_name", ...)`) that
    forces a backend after this module imports still dispatches the right
    kernel path.
    """
    if _INTERPRET_OVERRIDE is not None:
        return bool(_INTERPRET_OVERRIDE)
    return jax.default_backend() != "tpu"


# off-TPU, run the real kernel bodies (interpret mode) only for problems
# small enough that per-grid-point interpretation stays cheap. The shipped
# default; a per-host measured value can override it through the tune cache
# (key ``interpret_max_n|<backend>``), and `_INTERPRET_MAX_N_OVERRIDE` is
# the test hook that wins over both.
DEFAULT_FUSED_INTERPRET_MAX_N = 1024

_INTERPRET_MAX_N_OVERRIDE: int | None = None


def fused_interpret_max_n() -> int:
    """The off-accelerator interpret-vs-streaming dispatch threshold, read
    at CALL time: test override > tune-cache entry > shipped default."""
    if _INTERPRET_MAX_N_OVERRIDE is not None:
        return int(_INTERPRET_MAX_N_OVERRIDE)
    from repro import tune

    cached = tune.cached_interpret_max_n()
    if cached is not None:
        return int(cached)
    return DEFAULT_FUSED_INTERPRET_MAX_N


def __getattr__(name: str):
    # back-compat: both used to be import-time module constants; keep the
    # attributes readable but always call-time fresh
    if name == "INTERPRET":
        return interpret_mode()
    if name == "FUSED_INTERPRET_MAX_N":
        return fused_interpret_max_n()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


BWD_BACKENDS = ("auto", "pallas", "jnp")


def _check_bwd_backend(bwd_backend: str) -> None:
    if bwd_backend not in BWD_BACKENDS:
        raise ValueError(
            f"bwd_backend must be one of {BWD_BACKENDS}, got {bwd_backend!r}")


def _bwd_dispatch(bwd_backend, n, pallas_fn, jnp_fn):
    """The shared three-way reverse dispatch (mirrors the forward's split):
    `pallas_fn(interpret)` runs a Pallas reverse kernel, `jnp_fn()` the
    streaming-jnp twin. Every op's custom_vjp backward routes through here.
    """
    if bwd_backend == "jnp":
        return jnp_fn()
    if bwd_backend == "pallas":
        return pallas_fn(interpret_mode())
    if not interpret_mode():
        return pallas_fn(False)
    if n <= fused_interpret_max_n():
        return pallas_fn(True)
    return jnp_fn()


def _match_vma(*args):
    """Cast every argument to vary over the same mesh axes.

    Inside `jax.shard_map` a custom_vjp's cotangents must vary over the
    same axes as its primal inputs. The reverse kernels return per-shard
    partial cotangents for the global inputs (Z, variance, lengthscale), so
    those inputs are cast to varying here; the transpose of the cast psums
    the partials. Outside shard_map nothing varies and this is the
    identity."""
    vma = _vma(*args)
    return tuple(
        a if jax.typeof(a).vma == vma
        else jax.lax.pcast(a, tuple(sorted(vma - jax.typeof(a).vma)),
                           to="varying")
        for a in args)


# ---------------------------------------------------------------------------
# tuned-block resolution + op-factory cache policy
# ---------------------------------------------------------------------------

# Each (bwd_backend, block, bwd_block) knob combination owns one cached
# custom_vjp op (the knobs must be static at trace time). Bounded: an
# autotuner exploring many block candidates through these entry points must
# not grow an unbounded op population — LRU keeps the working set.
_OP_CACHE_SIZE = 32


def _tuned_block(kernel_name: str, dtype, m: int, q: int,
                 ) -> Optional[Tuple[int, int]]:
    """`tune.best_blocks` for one direction of one op; None = module
    defaults. Lazy import: `repro.tune` imports the kernel wrappers (and,
    transitively, this module) for measurement."""
    from repro import tune

    return tune.best_blocks(kernel_name, dtype=dtype, m=int(m), q=int(q))


def cache_info():
    """Debug hook: lru_cache statistics of every op factory, keyed by op
    name — how many knob combinations are live vs evicted."""
    return {
        "kfu": _make_kfu_op.cache_info(),
        "psi1": _make_psi1_op.cache_info(),
        "psi2": _make_psi2_op.cache_info(),
        "suffstats": _make_suffstats_op.cache_info(),
    }


# ---------------------------------------------------------------------------
# kfu
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def _make_kfu_op(bwd_backend: str, block, bwd_block):
    @jax.custom_vjp
    def op(X, Z, variance, lengthscale):
        return kfu_pallas(X, Z, variance, lengthscale,
                          interpret=interpret_mode(), block=block)

    def fwd(X, Z, variance, lengthscale):
        return op(X, Z, variance, lengthscale), (X, Z, variance, lengthscale)

    def bwd(res, g):
        X, Z, variance, lengthscale = res
        return _bwd_dispatch(
            bwd_backend, X.shape[0],
            lambda interp: kfu_bwd_pallas(X, Z, variance, lengthscale, g,
                                          interpret=interp, block=bwd_block),
            lambda: kfu_vjp_jnp(X, Z, variance, lengthscale, g))

    op.defvjp(fwd, bwd)
    return op


def kfu(X, Z, variance, lengthscale, *, bwd_backend: str = "auto",
        block: Optional[Tuple[int, int]] = None,
        bwd_block: Optional[Tuple[int, int]] = None):
    """RBF cross-covariance K_fu (N, M) with a hand-derived, kernelized
    reverse pass (the S -> 0 specialization of the psi1 rules). `block` /
    `bwd_block` pin the forward/reverse tiles; None consults the autotuner
    (the reverse delegates to the psi1 reverse kernel, so its tune key is
    `psi1_bwd_pallas`)."""
    _check_bwd_backend(bwd_backend)
    if block is None:
        block = _tuned_block("kfu_pallas", X.dtype, Z.shape[0], X.shape[1])
    if bwd_block is None:
        bwd_block = _tuned_block("psi1_bwd_pallas", X.dtype, Z.shape[0],
                                 X.shape[1])
    return _make_kfu_op(bwd_backend, block, bwd_block)(
        *_match_vma(X, Z, variance, lengthscale))


# ---------------------------------------------------------------------------
# psi1
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def _make_psi1_op(bwd_backend: str, block, bwd_block):
    @jax.custom_vjp
    def op(mu, S, Z, variance, lengthscale):
        return psi1_pallas(mu, S, Z, variance, lengthscale,
                           interpret=interpret_mode(), block=block)

    def fwd(mu, S, Z, variance, lengthscale):
        return op(mu, S, Z, variance, lengthscale), \
            (mu, S, Z, variance, lengthscale)

    def bwd(res, g):
        return _bwd_dispatch(
            bwd_backend, res[0].shape[0],
            lambda interp: psi1_bwd_pallas(*res, g, interpret=interp,
                                           block=bwd_block),
            lambda: psi1_vjp_jnp(*res, g))

    op.defvjp(fwd, bwd)
    return op


def psi1(mu, S, Z, variance, lengthscale, *, bwd_backend: str = "auto",
         block: Optional[Tuple[int, int]] = None,
         bwd_block: Optional[Tuple[int, int]] = None):
    """Psi1 statistic (N, M) with a hand-derived, kernelized reverse pass
    (eq. (10)-(14) of the derivation, branch weight W1 = g . psi1).
    `block`/`bwd_block` pin the tiles; None consults the autotuner."""
    _check_bwd_backend(bwd_backend)
    if block is None:
        block = _tuned_block("psi1_pallas", mu.dtype, Z.shape[0], mu.shape[1])
    if bwd_block is None:
        bwd_block = _tuned_block("psi1_bwd_pallas", mu.dtype, Z.shape[0],
                                 mu.shape[1])
    return _make_psi1_op(bwd_backend, block, bwd_block)(
        *_match_vma(mu, S, Z, variance, lengthscale))


# ---------------------------------------------------------------------------
# psi2
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def _make_psi2_op(bwd_backend: str, block, bwd_block):
    @jax.custom_vjp
    def op(mu, S, Z, variance, lengthscale):
        return psi2_pallas(mu, S, Z, variance, lengthscale,
                           interpret=interpret_mode(), block=block)

    def fwd(mu, S, Z, variance, lengthscale):
        return op(mu, S, Z, variance, lengthscale), \
            (mu, S, Z, variance, lengthscale)

    def bwd(res, g2):
        return _bwd_dispatch(
            bwd_backend, res[0].shape[0],
            lambda interp: psi2_bwd_pallas(*res, g2, interpret=interp,
                                           block=bwd_block),
            lambda: psi2_vjp_jnp(*res, g2))

    op.defvjp(fwd, bwd)
    return op


def psi2(mu, S, Z, variance, lengthscale, *, bwd_backend: str = "auto",
         block: Optional[Tuple[int, int]] = None,
         bwd_block: Optional[Tuple[int, int]] = None):
    """Psi2 statistic (M, M) with a hand-derived, kernelized reverse pass
    (the fused op's psi2 branch alone: eq. (9), (15)-(20)).
    `block`/`bwd_block` pin the tiles; None consults the autotuner."""
    _check_bwd_backend(bwd_backend)
    if block is None:
        block = _tuned_block("psi2_pallas", mu.dtype, Z.shape[0], mu.shape[1])
    if bwd_block is None:
        bwd_block = _tuned_block("psi2_bwd_pallas", mu.dtype, Z.shape[0],
                                 mu.shape[1])
    return _make_psi2_op(bwd_backend, block, bwd_block)(
        *_match_vma(mu, S, Z, variance, lengthscale))


# ---------------------------------------------------------------------------
# fused suffstats (psi2 + psiY in one pass over N)
# ---------------------------------------------------------------------------

def _suffstats_impl(mu, S, Y, Z, variance, lengthscale, block=None):
    if not interpret_mode():
        return suffstats_pallas(mu, S, Y, Z, variance, lengthscale,
                                interpret=False, block=block)
    if mu.shape[0] <= fused_interpret_max_n():
        return suffstats_pallas(mu, S, Y, Z, variance, lengthscale,
                                interpret=True, block=block)
    return suffstats_fused_jnp(mu, S, Y, Z, variance, lengthscale)


@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def _make_suffstats_op(bwd_backend: str, block, bwd_block):
    """One custom_vjp op per knob combination (the knobs must be static at
    trace time, so they select among cached op instances rather than riding
    the traced arguments)."""

    @jax.custom_vjp
    def op(mu, S, Y, Z, variance, lengthscale):
        return _suffstats_impl(mu, S, Y, Z, variance, lengthscale,
                               block=block)

    def fwd(mu, S, Y, Z, variance, lengthscale):
        out = op(mu, S, Y, Z, variance, lengthscale)
        return out, (mu, S, Y, Z, variance, lengthscale)

    def bwd(res, g):
        g2, gY = g
        return _bwd_dispatch(
            bwd_backend, res[0].shape[0],
            lambda interp: suffstats_bwd_pallas(*res, g2, gY,
                                                interpret=interp,
                                                block=bwd_block),
            lambda: suffstats_vjp_jnp(*res, g2, gY))

    op.defvjp(fwd, bwd)
    return op


def suffstats(mu, S, Y, Z, variance, lengthscale, *,
              bwd_backend: str = "auto",
              block: Optional[Tuple[int, int]] = None,
              bwd_block: Optional[Tuple[int, int]] = None):
    """Fused (psi2 (M, M), psiY (M, D)) with a hand-derived O(chunk * M^2)
    reverse pass — usable under jax.grad inside training steps.

    `bwd_backend` selects the reverse-pass implementation ("auto" | "pallas"
    | "jnp", see module docstring); the forward dispatch is unaffected.
    `block`/`bwd_block` pin the forward/reverse Pallas tiles; None consults
    the autotuner.
    """
    _check_bwd_backend(bwd_backend)
    if block is None:
        block = _tuned_block("suffstats_pallas", mu.dtype, Z.shape[0],
                             mu.shape[1])
    if bwd_block is None:
        bwd_block = _tuned_block("suffstats_bwd_pallas", mu.dtype,
                                 Z.shape[0], mu.shape[1])
    return _make_suffstats_op(bwd_backend, block, bwd_block)(
        *_match_vma(mu, S, Y, Z, variance, lengthscale))
