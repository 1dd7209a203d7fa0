"""Compile-only guards: every Pallas kernel, lowered by Mosaic and compiled
for a described TPU v5e chip at the main path's widths, with no chip
attached.

Interpret mode validates the kernel bodies; only the chip's compiler sees
VMEM use, tile alignment and Mosaic lowering. Q=8 with M in {256, 1024} are
the sparse-GP smoke shapes (`chip_smoke.py`), and the fused forward/reverse
pair also runs at D=128 (the GP-LVM's output width).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.pallas_audit import KERNELS, Problem, registry_entry

N = 8192
Q = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else libtpu logs under /tmp
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(name, problem, sharding):
    """Compile one registered kernel at float32 shapes with x64 off, as on
    the chip: conftest turns x64 on, and Mosaic refuses 64-bit grid
    indices."""
    fn, build = registry_entry(name)
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=sharding)
                for a in build(problem, jnp.float32)]
        return jax.jit(functools.partial(fn, interpret=False)).lower(
            *args).compile()


@pytest.mark.parametrize("m", [256, 1024])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, m):
    compiled = _compile(name, Problem(N=N, M=m, Q=Q, D=1), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["suffstats_pallas", "suffstats_bwd_pallas"])
def test_fused_pair_compiles_at_d128(one_chip, name):
    compiled = _compile(name, Problem(N=N, M=256, Q=Q, D=128), one_chip)
    assert "tpu_custom_call" in compiled.as_text()
