"""Optimization drivers for the GP models.

Two paths, mirroring the paper:
  * `fit_lbfgs`  — scipy L-BFGS-B on the (negative) bound, gradients from JAX.
                   This is the paper's optimizer (§2 end). Parameters are
                   gathered/flattened to the host — fine at GP scale, and it
                   reproduces the paper's experiment exactly.
  * `fit_adam`   — SPMD Adam on the distributed bound: no collector node, the
                   production path. Works with any loss(params, *batch).

Host spans on the profiler's clock (no-ops unless a profiler trace is being
taken): `gp.fit` around a facade's whole `fit` (`fit_span`), and
`gp.adam.step` around each step call of `fit_adam`. Both carry the `fit` id
of their call and, on exit, what the call built (`compile_cache.snapshot`
deltas): non-zero only where a step retraced or read its program back.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.optim import AdamConfig, adam_init, adam_update

PyTree = Any

_fit_ids = itertools.count()
_fit_id = contextvars.ContextVar("gp_fit_id", default=None)


def _built_since(before: dict) -> dict:
    now = compile_cache.snapshot()
    return {k: now[k] - before[k] for k in now}


@contextlib.contextmanager
def fit_span(facade: str, optimizer: str, steps: int, rows: int):
    """The `gp.fit` span of one facade `fit` call: its id and arguments at
    entry, what it built at exit."""
    fit_id = next(_fit_ids)
    token = _fit_id.set(fit_id)
    before = compile_cache.snapshot()
    with jax.profiler.TraceAnnotation(
            "gp.fit", fit=fit_id, facade=facade, optimizer=optimizer,
            steps=steps, rows=rows) as span:
        try:
            yield
        finally:
            span.set_metadata(**_built_since(before))
            _fit_id.reset(token)


def fit_adam(
    loss_fn: Callable[..., jax.Array],
    params: PyTree,
    data: tuple,
    *,
    steps: int = 200,
    lr: float = 1e-2,
    log_every: int = 0,
    donate: bool = True,
) -> tuple[PyTree, list[float]]:
    """SPMD Adam driver. `donate=` donates the (params, state) buffers to the
    jitted step so each iteration updates in place instead of holding two
    copies of the model state (the caller's pytrees are copied once up
    front, so references the caller keeps stay valid). The returned history
    ends with the loss the final step computed (at its pre-update
    parameters) — no extra full statistics pass is spent on logging; with
    `steps=0` no loss is ever evaluated and the history is empty.
    """
    config = AdamConfig(lr=lr, clip_norm=None, weight_decay=0.0)
    state = adam_init(params, config)

    # the CPU backend does not implement buffer donation (XLA would warn and
    # copy anyway), so only request it where it is real
    donate_argnums = (0, 1) if donate and jax.default_backend() != "cpu" else ()
    if donate_argnums:
        # the first step would otherwise donate the CALLER's buffers — copy
        # once up front so only loop-internal state is recycled
        params = jax.tree.map(jnp.array, params)
        state = jax.tree.map(jnp.array, state)

    @functools.partial(jax.jit, donate_argnums=donate_argnums)
    def step(params, state, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        with jax.named_scope("gp.adam.update"):
            params, state, _ = adam_update(grads, state, params, config)
        return params, state, loss

    fit_id = _fit_id.get()
    ids = {} if fit_id is None else {"fit": fit_id}
    history = []
    loss = None
    for i in range(steps):
        before = compile_cache.snapshot()
        with jax.profiler.StepTraceAnnotation("gp.adam.step", step_num=i,
                                              **ids) as span:
            params, state, loss = step(params, state, *data)
            span.set_metadata(**_built_since(before))
        if log_every and i % log_every == 0:
            history.append(float(loss))
            print(f"  step {i:5d}  loss {float(loss):.4f}")
    if loss is not None and not (log_every and (steps - 1) % log_every == 0):
        history.append(float(loss))
    return params, history


def fit_lbfgs(
    loss_fn: Callable[..., jax.Array],
    params: PyTree,
    data: tuple,
    *,
    maxiter: int = 200,
) -> tuple[PyTree, float]:
    """scipy L-BFGS-B driver (the paper's optimizer)."""
    from scipy.optimize import minimize

    flat, treedef = jax.tree.flatten(params)
    shapes = [p.shape for p in flat]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dtypes = [p.dtype for p in flat]

    def pack(tree_leaves) -> np.ndarray:
        return np.concatenate([np.asarray(p, np.float64).reshape(-1) for p in tree_leaves])

    def unpack(x: np.ndarray) -> PyTree:
        out, off = [], 0
        for s, n, dt in zip(shapes, sizes, dtypes):
            out.append(jnp.asarray(x[off : off + n].reshape(s), dt))
            off += n
        return treedef.unflatten(out)

    vg = jax.jit(jax.value_and_grad(loss_fn))

    def objective(x: np.ndarray):
        p = unpack(x)
        val, grads = vg(p, *data)
        return float(val), pack(treedef.flatten_up_to(grads))

    res = minimize(objective, pack(flat), jac=True, method="L-BFGS-B",
                   options={"maxiter": maxiter})
    return unpack(res.x), float(res.fun)
