"""Checkpoint manager: roundtrip, retention, atomicity, async, train-loop
resume, elastic reshard across device counts (subprocess)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(key, (8, 4), jnp.float32),
        "nested": {"b": jnp.arange(6, dtype=jnp.int32), "c": jnp.ones((3,), jnp.bfloat16)},
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    t = _tree()
    mgr.save(3, t, extra={"step": 3, "data": {"seed": 0, "step": 7}})
    restored, extra = mgr.restore(jax.tree.map(lambda x: jnp.zeros_like(x), t))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert extra == {"step": 3, "data": {"seed": 0, "step": 7}}


def test_retention_keeps_newest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in range(5):
        mgr.save(s, _tree(s))
    assert mgr.steps() == [3, 4]


def test_keep_every_survives_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=1, keep_every=2)
    for s in range(5):
        mgr.save(s, _tree(s))
    assert set(mgr.steps()) == {0, 2, 4}


def test_async_save_then_wait(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _tree(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 1
    # no tmp dirs left behind
    assert not list(Path(tmp_path).glob("*.tmp"))


def test_restore_rejects_shape_mismatch(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _tree())
    bad = {"a": jnp.zeros((2, 2)), "nested": {"b": jnp.zeros((6,), jnp.int32),
                                              "c": jnp.zeros((3,), jnp.bfloat16)}}
    with pytest.raises(ValueError):
        mgr.restore(bad)


def test_train_loop_resume(tmp_path):
    """Interrupt a loop, restart it, confirm it continues from the step and
    data position (exactly the node-failure recovery path)."""
    from repro.configs.base import ShapeCell, get_smoke_config
    from repro.data.synthetic import TokenStream
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import default_adam, make_train_step
    from repro.models.model_zoo import build
    from repro.optim import adam_init
    from repro.runtime.train_loop import LoopConfig, TrainLoop

    cfg = get_smoke_config("smollm-360m")
    shape = ShapeCell("t", 32, 2, "train")
    mesh = make_host_mesh()
    with mesh:
        bundle = make_train_step(cfg, shape, mesh, batch=2)
        step_fn = bundle.jitted()
        params = build(cfg).init(jax.random.PRNGKey(0))
        opt = adam_init(params, default_adam(cfg))
        lc = LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=2, log_every=0, async_save=False)

        loop1 = TrainLoop(step_fn, params, opt, TokenStream(cfg, shape, batch=2), lc)
        loop1.run(3)
        assert loop1.step == 3

        loop2 = TrainLoop(step_fn, params, opt, TokenStream(cfg, shape, batch=2), lc)
        loop2.run(5)
        assert loop2.step == 5
        # data stream resumed from saved position, not from scratch
        assert loop2.data.state.step >= 5


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys
sys.path.insert(0, "{src}")
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint.manager import CheckpointManager
from repro.parallel import sharding as shd
from repro.runtime.elastic import reshard_for_mesh
from repro.configs.base import get_smoke_config
from repro.models.model_zoo import build

cfg = get_smoke_config("smollm-360m")
params = build(cfg).init(jax.random.PRNGKey(7))
mesh = jax.make_mesh(({dshape}), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
if "{phase}" == "save":
    sharded = jax.device_put(params, shd.to_shardings(shd.param_specs(params, mesh), mesh))
    CheckpointManager("{dir}").save(11, {{"params": sharded}}, extra={{"step": 11}})
    print("SAVED", float(jax.tree.leaves(sharded)[0].sum()))
else:
    restored, extra = reshard_for_mesh("{dir}", jax.eval_shape(lambda: params), mesh)
    assert extra["step"] == 11
    a = jax.tree.leaves(params); b = jax.tree.leaves(restored)
    ok = all(np.allclose(np.asarray(x, np.float32), np.asarray(y, np.float32)) for x, y in zip(a, b))
    print("RESTORED-OK" if ok else "MISMATCH")
"""


@pytest.mark.slow
def test_elastic_reshard_across_meshes(tmp_path):
    """Save on a (2,2) mesh, restore on (4,2) — elastic scale-up resumes
    bit-exactly."""
    import repro

    src = repro.__file__.rsplit("/repro/", 1)[0]
    save = ELASTIC_SCRIPT.format(ndev=4, dshape="2, 2", phase="save", dir=tmp_path, src=src)
    out = subprocess.run([sys.executable, "-c", save], capture_output=True, text=True,
                         timeout=600)
    assert "SAVED" in out.stdout, out.stdout + out.stderr
    load = ELASTIC_SCRIPT.format(ndev=8, dshape="4, 2", phase="load", dir=tmp_path, src=src)
    out = subprocess.run([sys.executable, "-c", load], capture_output=True, text=True,
                         timeout=600)
    assert "RESTORED-OK" in out.stdout, out.stdout + out.stderr
