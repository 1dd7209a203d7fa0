"""Compiled-vs-interpret kernel parity — the hardware lane.

Everything else in the suite validates the Pallas kernel BODIES in interpret
mode on CPU; what interpret mode cannot validate is the compiled artifact
itself (Mosaic lowering, MXU accumulation, the tiled memory movement). These
tests run each registered kernel twice — compiled on the accelerator and in
interpret mode — and demand agreement, in both differentiation directions
(the registry's forward kernels AND the hand-derived reverse kernels are
separate entries, so all seven get their own row).

Marked `compiled` and skipped cleanly on CPU-only hosts; CI runs
``pytest -m compiled`` as a hardware-gated lane (scripts/ci.sh).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.pallas_audit import KERNELS, Problem, registry_entry
from repro.kernels import ops

pytestmark = pytest.mark.compiled


@pytest.fixture(autouse=True)
def accelerator():
    """Asks for the backend when a test runs, not while the module is
    imported: importing must not start a TPU runtime in every worker."""
    if jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm"):
        pytest.skip("compiled-parity lane needs a TPU/GPU backend")

# multi-tile in N and M at the default blocks, small enough to compile fast
PROBLEM = Problem(N=512, M=256, Q=3, D=2)

# compiled path computes in f32 either way; MXU-vs-VPU accumulation order
# differences bound the agreement
RTOL = 5e-5
ATOL = 1e-5


def _concrete(shapes, seed=0):
    """Positive, O(1)-magnitude inputs for every operand: valid variances /
    lengthscales / latent S, non-degenerate exponents, usable cotangents."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [
        jax.random.uniform(k, s.shape, jnp.float32, minval=0.5, maxval=1.5)
        for k, s in zip(keys, shapes)
    ]


@pytest.mark.parametrize("kernel_name", KERNELS)
def test_compiled_matches_interpret(kernel_name):
    fn, build = registry_entry(kernel_name)
    args = _concrete(build(PROBLEM, jnp.float32))
    compiled = fn(*args, interpret=False)
    interp = fn(*args, interpret=True)
    for c, i in zip(jax.tree.leaves(compiled), jax.tree.leaves(interp)):
        np.testing.assert_allclose(np.asarray(c), np.asarray(i),
                                   rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("kernel_name", KERNELS)
def test_compiled_matches_interpret_at_tuned_candidate(kernel_name):
    """A non-default admissible block must be numerically invisible in the
    compiled artifact too — the autotuner's core safety property on real
    hardware."""
    from repro import tune

    fn, build = registry_entry(kernel_name)
    args = _concrete(build(PROBLEM, jnp.float32), seed=1)
    cands = tune.candidate_blocks(kernel_name, problem=PROBLEM, limit=2)
    alt = next((c for c in cands
                if c != tune.default_blocks(kernel_name)), None)
    if alt is None:
        pytest.skip("no admissible non-default candidate at this problem")
    base = fn(*args, interpret=False)
    tuned = fn(*args, interpret=False, block=alt)
    for b, t in zip(jax.tree.leaves(base), jax.tree.leaves(tuned)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(t),
                                   rtol=RTOL, atol=ATOL)


def test_ops_grad_compiled_matches_interpret(monkeypatch):
    """End-to-end: value+grad of the fused op, compiled vs forced-interpret
    through the public `ops.suffstats` entry point."""
    shapes = registry_entry("suffstats_pallas")[1](PROBLEM, jnp.float32)
    mu, S, Y, Z, var, ls = _concrete(shapes, seed=2)

    def loss(mu, S, Y, Z, var, ls):
        psi2, psiY = ops.suffstats(mu, S, Y, Z, var, ls)
        return psi2.sum() + psiY.sum()

    compiled = jax.value_and_grad(loss, argnums=(0, 1, 4, 5))(
        mu, S, Y, Z, var, ls)
    monkeypatch.setattr(ops, "_INTERPRET_OVERRIDE", True)
    interp = jax.value_and_grad(loss, argnums=(0, 1, 4, 5))(
        mu, S, Y, Z, var, ls)
    for c, i in zip(jax.tree.leaves(compiled), jax.tree.leaves(interp)):
        np.testing.assert_allclose(np.asarray(c), np.asarray(i),
                                   rtol=RTOL, atol=ATOL)
