"""The training path's host spans (`gp.fit`, `gp.adam.step`) and the
process's compile count (`repro.compile_cache.snapshot`) they carry."""
import glob
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_cache
from repro.gp import BayesianGPLVM, SparseGPRegression, get

ROOT = Path(__file__).resolve().parents[1]
COUNTS = ("compiles", "cache_hits", "cache_misses", "build_s")


def _spans(log_dir) -> list:
    """(name, start_ns, end_ns, stats) of the `gp.*` host events, in order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("gp."):
                    stats = {k: v for k, v in e.stats}
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, stats))
    return sorted(out, key=lambda s: s[1])


def _regression(n=128, q=2):
    X = jax.random.normal(jax.random.PRNGKey(0), (n, q))
    return X, jnp.sin(X[:, :1])


def _delta(before: dict) -> dict:
    now = compile_cache.snapshot()
    return {k: now[k] - before[k] for k in COUNTS}


def test_fit_span_holds_its_step_spans(tmp_path):
    X, Y = _regression()
    with jax.profiler.trace(str(tmp_path)):
        SparseGPRegression(kernel=get("rbf")(2), M=8).fit(X, Y, steps=3)
    spans = _spans(tmp_path)
    fits = [s for s in spans if s[0] == "gp.fit"]
    steps = [s for s in spans if s[0] == "gp.adam.step"]
    assert len(fits) == 1 and len(steps) == 3
    (_, f0, f1, fit) = fits[0]
    assert fit["facade"] == "SparseGPRegression"
    assert fit["optimizer"] == "adam"
    assert fit["steps"] == 3 and fit["rows"] == 128
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2]
    assert {s[3]["fit"] for s in steps} == {fit["fit"]}
    assert all(f0 <= s0 and s1 <= f1 for _, s0, s1, _ in steps)
    for _, _, _, stats in fits + steps:
        assert set(COUNTS) <= set(stats)


def test_step_builds_add_up_to_the_fit_and_the_counter(tmp_path):
    X, Y = _regression()
    # warm the eager operations of `fit` so that only the step program builds
    SparseGPRegression(kernel=get("rbf")(2), M=8).fit(X, Y, steps=1)
    gp = SparseGPRegression(kernel=get("rbf")(2), M=8)
    before = compile_cache.snapshot()
    with jax.profiler.trace(str(tmp_path)):
        gp.fit(X, Y, steps=3)
    delta = _delta(before)
    spans = _spans(tmp_path)
    (fit,) = [s[3] for s in spans if s[0] == "gp.fit"]
    steps = [s[3] for s in spans if s[0] == "gp.adam.step"]
    assert delta["compiles"] >= 1
    assert sum(s["compiles"] for s in steps) == fit["compiles"]
    assert fit["compiles"] == delta["compiles"]
    assert steps[0]["compiles"] >= 1 and steps[0]["build_s"] > 0
    assert steps[-1]["compiles"] == 0 and steps[-1]["build_s"] == 0
    assert sum(s["build_s"] for s in steps) == pytest.approx(fit["build_s"])


def test_a_fresh_jit_counts_one_compile_and_its_rerun_none():
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    x = jnp.arange(7.0)
    before = compile_cache.snapshot()
    f(x).block_until_ready()
    first = _delta(before)
    assert first["compiles"] == 1 and first["build_s"] > 0
    before = compile_cache.snapshot()
    f(x).block_until_ready()
    assert _delta(before) == {k: 0 for k in COUNTS}


def test_nested_builds_are_counted_once():
    def inner(x):
        time.sleep(0.2)  # runs while the outer function is being traced
        return jnp.sin(x) + 1.0

    outer = jax.jit(lambda x: jax.jit(inner)(x) * 2.0)
    x = jnp.arange(5.0)
    before = compile_cache.snapshot()
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    d = _delta(before)
    assert d["compiles"] == 1
    # the inner trace lies inside the outer one: counted once, the build
    # fits in the call's wall time
    assert 0.2 <= d["build_s"] <= wall


def test_importing_registers_no_listener():
    code = (
        "from jax._src import monitoring as m\n"
        "lists = ('_event_listeners', '_event_duration_secs_listeners',"
        " '_scalar_listeners')\n"
        "n = lambda: [len(getattr(m, k)) for k in lists]\n"
        "before = n()\n"
        "import repro.compile_cache, repro.core.inference, repro.gp\n"
        "assert n() == before, (before, n())\n"
        "repro.compile_cache.snapshot(); repro.compile_cache.snapshot()\n"
        "assert n() == [b + 1 for b in before], (before, n())\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr
    assert run.stdout.strip() == "ok"


@pytest.mark.parametrize("facade,optimizer", [
    ("BayesianGPLVM", "adam"),
    ("SparseGPRegression", "lbfgs"),
    ("BayesianGPLVM", "lbfgs"),
])
def test_every_facade_and_optimizer_writes_its_fit_span(tmp_path, facade,
                                                        optimizer):
    X, Y = _regression(n=64)
    with jax.profiler.trace(str(tmp_path)):
        if facade == "BayesianGPLVM":
            Ylvm = np.asarray(jnp.concatenate([Y, X], axis=1))
            BayesianGPLVM(kernel=get("rbf")(2), M=6).fit(
                Ylvm, optimizer=optimizer, steps=2)
        else:
            SparseGPRegression(kernel=get("rbf")(2), M=6).fit(
                X, Y, optimizer=optimizer, steps=2)
    spans = _spans(tmp_path)
    fits = [s[3] for s in spans if s[0] == "gp.fit"]
    assert len(fits) == 1
    assert fits[0]["facade"] == facade and fits[0]["optimizer"] == optimizer
    assert fits[0]["rows"] == 64
    assert set(COUNTS) <= set(fits[0])
    n_steps = sum(s[0] == "gp.adam.step" for s in spans)
    assert n_steps == (2 if optimizer == "adam" else 0)
