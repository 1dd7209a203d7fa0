"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; tests and
benches see the real single CPU device).

Production target: TPU v5e pods, 256 chips each, mesh (data=16, model=16)
per pod; multi-pod adds a leading "pod" axis over the (slow) DCN links —
used for data parallelism (optionally pipeline stages, parallel/pipeline.py).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


POD_SHAPE = (16, 16)
N_PODS = 2


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (N_PODS, *POD_SHAPE) if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (see launch/dryrun.py)"
        )
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh() -> Mesh:
    """Whatever devices exist (1 CPU here): for tests/examples; same code path."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
