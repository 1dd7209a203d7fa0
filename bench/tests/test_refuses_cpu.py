"""Off the chip, bench/run.py exits non-zero and prints no result line."""
import os
import shutil
import subprocess
import sys

from bench.harness import cell as cells

ROOT = cells.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sgpr-airline.train",
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_refused_without_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_unknown_workload_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_only_benchmark_files_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
