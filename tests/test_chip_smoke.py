"""The chip smoke refuses to run off the chip, and the compile-cache helper
that entry points call keeps the cache at one fixed place."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_exits_nonzero_on_cpu_before_model_work(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=300)
    assert run.returncode != 0
    assert "'cpu'" in run.stderr
    assert run.stdout == ""  # no phase ran and no result was printed


def test_compile_cache_env_var_wins(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own read


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable() == path  # the same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(path) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
