"""Each cell's CPU sizes, and ways to run a cell on the CPU with the look
for a chip skipped.

A cell's sizes are in `bench/tests/cells/<cell>.json`: `rehearsal`, the
overrides of its configuration at which it runs end to end here, and
`control`, those at which its control is read here. Above 1,024 rows per
device the program's statistics run their jnp twins off the TPU.

A one-chip cell runs in the test's own process, on its one CPU device. A
four-chip cell runs in a child process that sees four host devices
(`--xla_force_host_platform_device_count`): this module run as a script,
which prints its result as its last line. Each hook, a `module:function`,
is called with a monkeypatch before the run, such as a fault planted in the
program, as `test_faults.py` plants them in process.

    python -m bench.tests.tiny <rehearsal|control> <cell> [module:function ...]
"""
import importlib
import io
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from bench.harness import cell as cells

SIZES = Path(__file__).resolve().parent / "cells"
SEED = 2 ** 33 + 17
CONTROL_SEED = 2 ** 34 + 3
CHILD_TIMEOUT_S = 600


def sizes_file(name: str) -> Path:
    return SIZES / f"{name}.json"


def sizes(name: str) -> dict:
    return cells.load_json(sizes_file(name))


def run_cell(name: str, *, seconds: float = 1.0, seed: int = SEED,
             trace: bool = False) -> dict:
    """Run `name` at its rehearsal size through bench/run.py's emit();
    returns the parsed last stdout line."""
    import jax

    from bench import run
    from bench.harness.compile_log import CompileLog

    out = io.StringIO()
    cell = cells.Cell.load(name, seed=seed, seconds=seconds, trace=trace,
                           t0=time.monotonic(), devices=jax.devices(),
                           compiles=CompileLog(),
                           config_overrides=sizes(name)["rehearsal"], out=out)
    assert run.emit(cell) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control_readings(name: str, seed: int, on_chip: bool) -> tuple:
    """(limits, readings) of the program and of the control, at the cell's
    own size on the chip, else at its `control` size."""
    import jax

    from bench import calibrate
    from bench.harness.compile_log import CompileLog

    seconds = cells.spec()["run_seconds"] if on_chip else 2.0
    cell = cells.Cell.load(
        name, seed=seed, seconds=seconds, trace=False, t0=time.monotonic(),
        devices=jax.devices(), compiles=CompileLog(), out=io.StringIO(),
        config_overrides=None if on_chip else sizes(name)["control"])
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    return cell.limits, dict(calibrate.train_readings(cell, control=True))


def on_host_devices(what: str, name: str, chips: int = 0,
                    hooks: tuple = ()) -> dict:
    """Run `what` ("rehearsal" or "control") of `name` in a child process
    on as many CPU devices as the cell asks for chips, or on `chips`, with
    `hooks` applied there first; returns the object the child printed
    last. A child that outlives CHILD_TIMEOUT_S is killed and the call
    raises."""
    chips = chips or cells.workload(name)["chips"]
    flags = (f"{os.environ.get('XLA_FLAGS', '')} "
             f"--xla_force_host_platform_device_count={chips}").strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags,
               PYTHONPATH=os.pathsep.join([str(cells.ROOT),
                                           str(cells.ROOT / "src")]))
    r = subprocess.run([sys.executable, "-m", "bench.tests.tiny", what, name,
                        *hooks], cwd=cells.ROOT, env=env,
                       capture_output=True, text=True,
                       timeout=CHILD_TIMEOUT_S)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def rehearse(name: str) -> dict:
    """The cell end to end at its rehearsal size, on as many CPU devices
    as it asks for chips."""
    if cells.workload(name)["chips"] == 1:
        return run_cell(name)
    return on_host_devices("rehearsal", name)


def cpu_control(name: str) -> tuple:
    """(limits, readings) at the cell's control size, on as many CPU
    devices as it asks for chips."""
    if cells.workload(name)["chips"] == 1:
        return control_readings(name, CONTROL_SEED, on_chip=False)
    d = on_host_devices("control", name)
    return d["limits"], d["readings"]


def main(argv) -> int:
    what, name, hooks = argv[0], argv[1], argv[2:]
    import jax
    import pytest

    patch = pytest.MonkeyPatch()
    for hook in hooks:
        module, function = hook.split(":")
        getattr(importlib.import_module(module), function)(patch)
    chips, n = cells.workload(name)["chips"], len(jax.devices())
    if n != chips:
        print(f"tiny: {name} asks for {chips} devices, JAX found {n}",
              file=sys.stderr)
        return 2
    if what == "rehearsal":
        result = run_cell(name)
    else:
        limits, readings = control_readings(name, CONTROL_SEED, on_chip=False)
        result = {"limits": limits, "readings": readings}
    patch.undo()
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
