"""`fit_adam` and `fit_lbfgs` keep their jitted program across calls
(`repro.core.inference.cache_info`): a repeated `fit` with the same loss
and settings builds nothing, and a fresh one builds its step once, on one
device and on a mesh."""
import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_cache
from repro.core import inference
from repro.gp import SparseGPRegression, get

ROOT = Path(__file__).resolve().parents[1]


def _regression(n=128, q=2):
    X = jax.random.normal(jax.random.PRNGKey(0), (n, q))
    return X, jnp.sin(X[:, :1])


def _sgpr(**kw):
    return SparseGPRegression(kernel=get("rbf")(2), M=8, **kw)


def _compiles(fn) -> int:
    before = compile_cache.snapshot()["compiles"]
    fn()
    return compile_cache.snapshot()["compiles"] - before


def _fit_spans(log_dir) -> list:
    """The stats of the `gp.fit` host events, in order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.start_ns, dict(e.stats)) for line in plane.lines
                    for e in line.events if e.name == "gp.fit"]
    return [stats for _, stats in sorted(out, key=lambda s: s[0])]


def _same(a, b) -> bool:
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


@pytest.mark.parametrize("optimizer", ["adam", "lbfgs"])
def test_a_repeat_fit_builds_nothing(optimizer):
    X, Y = _regression()
    gp = _sgpr().fit(X, Y, optimizer=optimizer, steps=3)
    assert _compiles(lambda: gp.fit(X, Y, optimizer=optimizer,
                                    params=gp.params, steps=3)) == 0


def test_a_fresh_fit_from_host_params_builds_its_step_once():
    X, Y = _regression()
    # numpy leaves, as a warm start read back from the host or a file has
    start = jax.device_get(_sgpr().fit(X, Y, steps=2).params)
    _sgpr().fit(X, Y, params=start, steps=3)  # warms the eager operations
    assert _compiles(lambda: _sgpr().fit(X, Y, params=start, steps=3)) == 1


def test_the_fit_span_says_whether_its_step_was_kept(tmp_path):
    X, Y = _regression()
    gp = _sgpr()
    with jax.profiler.trace(str(tmp_path)):
        gp.fit(X, Y, steps=2)
        gp.fit(X, Y, params=gp.params, steps=2)
        _sgpr().fit(X, Y, steps=2)
    fits = _fit_spans(tmp_path)
    assert [f["step_kept"] for f in fits] == [0, 1, 0]
    assert fits[1]["compiles"] == 0


def test_a_kept_program_lands_where_a_fresh_one_does():
    X, Y = _regression()
    gp = _sgpr().fit(X, Y, steps=3)
    start = gp.params
    assert _compiles(lambda: gp.fit(X, Y, params=start, steps=4)) == 0
    fresh = _sgpr().fit(X, Y, params=start, steps=4)
    assert _same(gp.params, fresh.params)
    assert gp.history == fresh.history


def test_a_new_lr_builds_a_new_program():
    X, Y = _regression()
    gp = _sgpr().fit(X, Y, steps=3, lr=3e-2)
    start = gp.params
    assert _compiles(lambda: gp.fit(X, Y, params=start, steps=3,
                                    lr=1e-2)) == 1
    assert _same(gp.params,
                 _sgpr().fit(X, Y, params=start, steps=3, lr=1e-2).params)
    assert not _same(gp.params,
                     _sgpr().fit(X, Y, params=start, steps=3, lr=3e-2).params)


def test_the_kept_programs_are_bounded():
    size = inference._PROGRAM_CACHE_SIZE
    w0 = jnp.arange(3.0)
    for i in range(size + 3):
        # a new closure on every call, as `serve.online.refit` makes
        loss = lambda w, c=float(i): jnp.sum((w - c) ** 2)
        inference.fit_adam(loss, w0, (), steps=1)
        inference.fit_lbfgs(loss, w0, (), maxiter=1)
    for info in inference.cache_info().values():
        assert info.currsize == info.maxsize == size


def test_the_callers_params_survive_a_donating_program(monkeypatch):
    # the CPU honours donation; the driver asks for it off the CPU only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    w0 = {"w": jnp.arange(3.0), "b": jnp.asarray(1.0)}
    loss = lambda p: jnp.sum((p["w"] - 2.0) ** 2) + p["b"] ** 2
    first, _ = inference.fit_adam(loss, w0, (), steps=3)
    again, _ = inference.fit_adam(loss, w0, (), steps=3)  # the kept program
    assert not any(x.is_deleted() for x in jax.tree.leaves(w0))
    np.testing.assert_array_equal(w0["w"], np.arange(3.0))
    assert _same(first, again)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from repro import compile_cache
from repro.core.distributed import make_gp_mesh
from repro.gp import BayesianGPLVM, SparseGPRegression, get

def compiles(fn):
    before = compile_cache.snapshot()["compiles"]
    fn()
    return compile_cache.snapshot()["compiles"] - before

mesh = make_gp_mesh()
X = jax.random.normal(jax.random.PRNGKey(0), (256, 2))
Y = jnp.sin(X[:, :1])
if {facade!r} == "SparseGPRegression":
    new = lambda: SparseGPRegression(kernel=get("rbf")(2), M=8, mesh=mesh)
    data = (X, Y)
else:
    new = lambda: BayesianGPLVM(kernel=get("rbf")(2), M=6, mesh=mesh)
    data = (np.asarray(jnp.concatenate([Y, X], axis=1)),)
new().fit(*data, steps=3)  # warms the eager operations of `fit`
gp = new()
out = {{"devices": mesh.devices.size,
        "fresh": compiles(lambda: gp.fit(*data, steps=3))}}
out["repeat"] = compiles(lambda: gp.fit(*data, params=gp.params, steps=3))
print(json.dumps(out))
"""


@pytest.mark.parametrize("facade", ["SparseGPRegression", "BayesianGPLVM"])
def test_on_a_mesh_a_fresh_fit_builds_its_step_once_and_a_repeat_none(facade):
    code = MESH_SCRIPT.format(src=str(ROOT / "src"), facade=facade)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr
    out = json.loads(run.stdout.strip().splitlines()[-1])
    assert out == {"devices": 2, "fresh": 1, "repeat": 0}
