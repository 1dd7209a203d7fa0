"""Data-parallel sparse-GP inference (paper §2) on a JAX device mesh.

The paper's MPI scheme, translated:

  * every device owns a contiguous shard of (Y, q_mu, q_logS) [GP-LVM] or
    (X, Y) [sparse GP regression];
  * each device computes its local `SuffStats` (the only O(N) work);
  * one `jax.lax.psum` over the data axes combines them — this is the paper's
    single Allreduce of {phi, Phi, Psi, yy};
  * the O(M^3) epilogue (Cholesky, logdet, quadratic form) is evaluated
    replicated on every device — cheaper than broadcasting its result, and it
    keeps the whole step SPMD;
  * jax.grad through the psum reproduces the reverse path of paper Table 2:
    dL/dPhi etc. are *replicated* cotangents that each shard contracts against
    its local kernel-derivative terms. Global-parameter gradients (theta, Z,
    beta) emerge psum'd; local-parameter gradients (mu_n, S_n) stay sharded.

No parameter server, no gradient gathering to rank 0: the optimizer step is
SPMD too (the paper notes its rank-0 L-BFGS collector is a stopgap).

Both losses are kernel-generic: pass any `repro.gp.kernels.Kernel` (default
RBF, the paper's choice); `backend=` / `bwd_backend=` / `chunk=` thread
through to the statistics engine unchanged, so each shard's kernelized
statistics backward through their hand-derived reverse kernels (or the
streaming jnp twins) under the shard_map transpose. Shard_map in/out specs
derive from the declarative
`PARAM_ROLES` table below instead of per-model hand-written spec dicts —
kernel parameter trees of any shape ride on the `P()` pytree prefix.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gplvm, svgp
from repro.gp.kernels import Kernel, default_rbf
from repro.gp.stats import ExactBatch, suff_stats

Params = Dict[str, jax.Array]

# ---------------------------------------------------------------------------
# declarative parameter-spec table (the paper's local/global split)
# ---------------------------------------------------------------------------
# "local"  — per-datapoint parameters, sharded over the data axes;
# "global" — model parameters, replicated (grads emerge psum'd).
# A single P() / P(axes) acts as a pytree *prefix*, so arbitrarily-shaped
# kernel parameter trees need no per-leaf spec.
PARAM_ROLES: Dict[str, str] = {
    "kern": "global",
    "Z": "global",
    "log_beta": "global",
    "q_mu": "local",
    "q_logS": "local",
}

SGPR_PARAM_NAMES = ("kern", "Z", "log_beta")
GPLVM_PARAM_NAMES = SGPR_PARAM_NAMES + ("q_mu", "q_logS")


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes used for data parallelism (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_param_specs(names: Iterable[str], mesh: Mesh) -> Dict[str, P]:
    """in_specs for a param dict, derived from PARAM_ROLES."""
    axes = _data_axes(mesh)
    return {n: P(axes) if PARAM_ROLES[n] == "local" else P() for n in names}


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh):
    return NamedSharding(mesh, P(_data_axes(mesh)))


def shard_gp_params(params: Params, mesh: Mesh) -> Params:
    """Device placement mirroring PARAM_ROLES: locals on the data axes,
    globals replicated."""
    out = {}
    for k, v in params.items():
        if PARAM_ROLES.get(k) == "local":
            out[k] = jax.device_put(v, data_sharded(mesh))
        else:
            out[k] = jax.device_put(v, jax.tree.map(lambda _: replicated(mesh), v)
                                     if isinstance(v, dict) else replicated(mesh))
    return out


# back-compat alias (pre-facade name)
shard_gplvm_params = shard_gp_params


def gplvm_loss_dist(mesh: Mesh, *, kernel: Optional[Kernel] = None,
                    backend: str = "jnp", chunk: Optional[int] = None,
                    bwd_backend: str = "auto"):
    """Distributed GP-LVM negative-ELBO: shard_map over the data axes.

    Returns loss(params, Y) with Y and q(X) sharded over the data axes and a
    replicated scalar output. Differentiable; grads of global params are
    automatically psum'd by the shard_map transpose. `chunk=` streams each
    shard's datapoints (per-shard scan, then the one psum).
    """
    axes = _data_axes(mesh)
    local_spec = P(axes)
    gspec = make_param_specs(GPLVM_PARAM_NAMES, mesh)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(gspec, local_spec),
        out_specs=P(),
    )
    def loss(params: Params, Y_local: jax.Array) -> jax.Array:
        D = Y_local.shape[1]
        stats = gplvm.local_stats(params, Y_local, kernel=kernel,
                                  backend=backend, chunk=chunk,
                                  bwd_backend=bwd_backend)
        kl = gplvm.kl_qp(params["q_mu"], params["q_logS"])
        # --- the paper's single collective: combine sufficient statistics ---
        stats = jax.tree.map(lambda x: jax.lax.psum(x, axes), stats)
        kl = jax.lax.psum(kl, axes)
        # --- indistributable epilogue, replicated ---
        bound = gplvm.bound_from_stats(params, stats, kl, D, kernel=kernel)
        return -bound / stats.n

    return loss


def sgpr_loss_dist(mesh: Mesh, *, kernel: Optional[Kernel] = None,
                   backend: str = "jnp", chunk: Optional[int] = None,
                   bwd_backend: str = "auto"):
    """Distributed sparse-GP-regression negative log-bound (deterministic X)."""
    axes = _data_axes(mesh)
    local_spec = P(axes)
    gspec = make_param_specs(SGPR_PARAM_NAMES, mesh)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(gspec, local_spec, local_spec),
        out_specs=P(),
    )
    def loss(params: Params, X_local: jax.Array, Y_local: jax.Array) -> jax.Array:
        D = Y_local.shape[1]
        kern = default_rbf(kernel, params["Z"].shape[1])
        stats = suff_stats(kern, params["kern"],
                           ExactBatch(X_local, Y_local, params["Z"]),
                           backend=backend, chunk=chunk,
                           bwd_backend=bwd_backend)
        stats = jax.tree.map(lambda x: jax.lax.psum(x, axes), stats)
        Kuu = kern.K(params["kern"], params["Z"])
        terms = svgp.collapsed_bound(Kuu, stats, jnp.exp(params["log_beta"]), D)
        return -terms.bound / stats.n

    return loss


# ---------------------------------------------------------------------------
# predict-time statistics (same decomposition, no epilogue)
# ---------------------------------------------------------------------------

def sgpr_stats_dist(mesh: Mesh, *, kernel: Optional[Kernel] = None,
                    backend: str = "jnp", chunk: Optional[int] = None,
                    bwd_backend: str = "auto"):
    """Distributed O(N M^2) statistics pass for SGPR posterior/prediction.

    `posterior()` needs the same psum'd `SuffStats` the training loss
    consumes, so prediction shards the pass identically: per-device (and
    optionally per-chunk) statistics, one psum, replicated output.
    """
    axes = _data_axes(mesh)
    local_spec = P(axes)
    gspec = make_param_specs(SGPR_PARAM_NAMES, mesh)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(gspec, local_spec, local_spec),
        out_specs=P(),
    )
    def stats_fn(params: Params, X_local: jax.Array, Y_local: jax.Array):
        kern = default_rbf(kernel, params["Z"].shape[1])
        stats = suff_stats(kern, params["kern"],
                           ExactBatch(X_local, Y_local, params["Z"]),
                           backend=backend, chunk=chunk,
                           bwd_backend=bwd_backend)
        return jax.tree.map(lambda x: jax.lax.psum(x, axes), stats)

    return stats_fn


def gplvm_stats_dist(mesh: Mesh, *, kernel: Optional[Kernel] = None,
                     backend: str = "jnp", chunk: Optional[int] = None,
                     bwd_backend: str = "auto"):
    """Distributed statistics pass for the GP-LVM posterior (see above)."""
    axes = _data_axes(mesh)
    local_spec = P(axes)
    gspec = make_param_specs(GPLVM_PARAM_NAMES, mesh)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(gspec, local_spec),
        out_specs=P(),
    )
    def stats_fn(params: Params, Y_local: jax.Array):
        stats = gplvm.local_stats(params, Y_local, kernel=kernel,
                                  backend=backend, chunk=chunk,
                                  bwd_backend=bwd_backend)
        return jax.tree.map(lambda x: jax.lax.psum(x, axes), stats)

    return stats_fn


def make_gp_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """1-D data mesh over however many devices exist (1 on this CPU box,
    hundreds of chips in production — the code path is identical)."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return jax.make_mesh((len(devs),), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devs)
