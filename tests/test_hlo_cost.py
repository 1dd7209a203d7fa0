"""The trip-count-aware HLO cost model vs XLA's own analysis and analytics."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import hlo_cost


def test_matches_xla_on_scan_free_program():
    def f(a, b):
        return jnp.sum(jax.nn.relu(a @ b))

    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    ours = hlo_cost.analyze(compiled.as_text())
    xla = compiled.cost_analysis()
    assert abs(ours.flops - xla["flops"]) / xla["flops"] < 0.01
    assert abs(ours.bytes - xla["bytes accessed"]) / xla["bytes accessed"] < 0.05


def test_scan_bodies_multiplied_by_trip_count():
    def body(c, _):
        return c @ c, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    ours = hlo_cost.analyze(compiled.as_text())
    expect = 10 * 2 * 128**3
    assert abs(ours.flops - expect) / expect < 0.02
    # XLA's own count misses the multiplier — that's why hlo_cost exists
    assert compiled.cost_analysis()["flops"] < expect / 5


def test_nested_scans():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None

            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    ours = hlo_cost.analyze(compiled.as_text())
    expect = 20 * 2 * 128**3
    assert abs(ours.flops - expect) / expect < 0.02


def test_sliced_loop_params_not_counted_full():
    """A scan that reads one slice of a big stacked array per step must not
    charge the whole array per step."""
    big = jax.ShapeDtypeStruct((64, 256, 256), jnp.float32)  # 16 MiB

    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    compiled = jax.jit(f).lower(big, x).compile()
    ours = hlo_cost.analyze(compiled.as_text())
    full_per_step = 64 * (64 * 256 * 256 * 4)  # trips x whole array
    assert ours.bytes < full_per_step / 4, ours.bytes


def test_collectives_scale_with_trip_count():
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_cost
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
def f(w, x):
    def body(c, _):
        h = c @ w  # contraction over the sharded dim => all-reduce per step
        return jax.lax.with_sharding_constraint(jnp.tanh(h), NamedSharding(mesh, P(None, "model"))), None
    y, _ = jax.lax.scan(body, x, None, length=7)
    return jnp.sum(y)
w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
x = jax.ShapeDtypeStruct((8, 512), jnp.float32)
with mesh:
    c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("model", None)), NamedSharding(mesh, P(None, "model")))).lower(w, x).compile()
cost = hlo_cost.analyze(c.as_text())
n = sum(cost.coll_counts.values())
print("NCOLL", n)
assert n >= 7, cost.coll_counts
print("COLL-OK")
"""
    import repro

    src = repro.__file__.rsplit("/repro/", 1)[0]
    out = subprocess.run([sys.executable, "-c", script % src], capture_output=True,
                         text=True, timeout=300)
    assert "COLL-OK" in out.stdout, out.stdout + out.stderr
