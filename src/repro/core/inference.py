"""Optimization drivers for the GP models.

Two paths, mirroring the paper:
  * `fit_lbfgs`  — scipy L-BFGS-B on the (negative) bound, gradients from JAX.
                   This is the paper's optimizer (§2 end). Parameters are
                   gathered/flattened to the host — fine at GP scale, and it
                   reproduces the paper's experiment exactly.
  * `fit_adam`   — SPMD Adam on the distributed bound: no collector node, the
                   production path. Works with any loss(params, *batch).

Both keep their jitted program across calls: one per (loss function,
optimizer settings), up to `_PROGRAM_CACHE_SIZE` of each, so a repeated
`fit` with the same loss and settings traces, lowers and compiles nothing
(`cache_info()`). A caller that builds a new loss closure on every call
builds a new program every time, as it always has.

Host spans on the profiler's clock (no-ops unless a profiler trace is being
taken): `gp.fit` around a facade's whole `fit` (`fit_span`), and
`gp.adam.step` around each step call of `fit_adam`. Both carry the `fit` id
of their call and, on exit, what the call built (`compile_cache.snapshot`
deltas): non-zero only where a step retraced or read its program back.
`gp.fit` also records `step_kept`: 1 where its program was a kept one.
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
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.optim import AdamConfig, AdamState, adam_init, adam_update

PyTree = Any

# programs kept per driver: a caller that makes a new loss closure on every
# call (`serve.online.refit`) misses each time and pins at most this many
_PROGRAM_CACHE_SIZE = 8

_fit_ids = itertools.count()
# the id and the exit stats of the open `gp.fit` span
_fit = contextvars.ContextVar("gp_fit", default=None)


def _built_since(before: dict) -> dict:
    now = compile_cache.snapshot()
    return {k: now[k] - before[k] for k in now}


@contextlib.contextmanager
def fit_span(facade: str, optimizer: str, steps: int, rows: int):
    """The `gp.fit` span of one facade `fit` call: its id and arguments at
    entry; at exit what it built and whether its program was kept."""
    fit = {"fit": next(_fit_ids), "step_kept": 0}
    token = _fit.set(fit)
    before = compile_cache.snapshot()
    with jax.profiler.TraceAnnotation(
            "gp.fit", fit=fit["fit"], facade=facade, optimizer=optimizer,
            steps=steps, rows=rows) as span:
        try:
            yield
        finally:
            span.set_metadata(**_built_since(before),
                              step_kept=fit["step_kept"])
            _fit.reset(token)


def _kept(factory, *key):
    """`factory(*key)`, noting on the open `gp.fit` span whether it came
    from the kept programs (`step_kept` 1) or was built (0). Fits running
    on other threads at the same moment can blur the note, never the
    program."""
    misses = factory.cache_info().misses
    program = factory(*key)
    fit = _fit.get()
    if fit is not None:
        fit["step_kept"] = int(factory.cache_info().misses == misses)
    return program


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _make_adam_step(loss_fn: Callable[..., jax.Array], config: AdamConfig,
                    donate_argnums: tuple):
    """The jitted Adam step of one (loss, settings, donation), kept across
    `fit_adam` calls so that jit's own cache holds its executables."""

    @functools.partial(jax.jit, donate_argnums=donate_argnums)
    def step(params, state, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        with jax.named_scope("gp.adam.update"):
            params, state, _ = adam_update(grads, state, params, config)
        return params, state, loss

    return step


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _make_value_and_grad(loss_fn: Callable[..., jax.Array]):
    """The jitted value and gradient of one loss, kept across `fit_lbfgs`
    calls."""
    return jax.jit(jax.value_and_grad(loss_fn))


def cache_info():
    """Debug hook: lru_cache statistics of the kept programs, keyed by
    driver — how many are live vs evicted."""
    return {"adam_step": _make_adam_step.cache_info(),
            "lbfgs_value_and_grad": _make_value_and_grad.cache_info()}


def _placed_as_returned(params: PyTree, config: AdamConfig):
    """Step 0's params and Adam state, typed and placed as the step returns
    them, so that every step call shares one specialization: jax arrays of
    strong type; with no committed parameter all uncommitted, as the
    outputs then are; otherwise each parameter and its moments with the
    parameter's sharding, and what is uncommitted (the step count, any
    uncommitted parameter) replicated over the same devices."""
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.result_type(x)), params)
    state = adam_init(params, config)
    shardings = [x.sharding for x in jax.tree.leaves(params) if x.committed]
    if not shardings:
        return params, state
    s = shardings[0]  # on a mesh, or else on one device
    replicated = NamedSharding(s.mesh, P()) if isinstance(s, NamedSharding) else s
    like = jax.tree.map(lambda x: x.sharding if x.committed else replicated,
                        params)
    return jax.device_put((params, state),
                          (like, AdamState(replicated, like, like)))


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
    params, state = _placed_as_returned(params, config)

    # the CPU backend does not implement buffer donation (XLA would warn and
    # copy anyway), so only request it where it is real
    donate_argnums = (0, 1) if donate and jax.default_backend() != "cpu" else ()
    if donate_argnums:
        # the first step would otherwise donate the CALLER's buffers — copy
        # once up front so only loop-internal state is recycled
        params = jax.tree.map(jnp.array, params)
        state = jax.tree.map(jnp.array, state)

    step = _kept(_make_adam_step, loss_fn, config, donate_argnums)

    fit = _fit.get()
    ids = {} if fit is None else {"fit": fit["fit"]}
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

    vg = _kept(_make_value_and_grad, loss_fn)

    def objective(x: np.ndarray):
        p = unpack(x)
        val, grads = vg(p, *data)
        return float(val), pack(treedef.flatten_up_to(grads))

    res = minimize(objective, pack(flat), jac=True, method="L-BFGS-B",
                   options={"maxiter": maxiter})
    return unpack(res.x), float(res.fun)
