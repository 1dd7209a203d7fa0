"""The control: the reference at the next precision below the
configuration's (`high`, three bfloat16 passes, written out as products so
that a CPU computes it as the chip does) put in the program's place.

On a TPU with the cell's chips, at the cell's own size, the control comes
out not correct against the cell's limits and the program comes out
correct; `bench/calibrate.py` reads the same numbers over more seeds, and
PERF.md gives the readings the limits were set from. On the CPU, at a size
a test run can hold, the comparison still tells the two precisions apart:
the control reads at least three times the program on one number.
"""
import io
import time

import jax
import pytest

from bench.harness import cell as cells
from bench.harness.compile_log import CompileLog

CELLS = [w["name"] for w in cells.spec()["workloads"] if w["chips"] == 1]
CPU_SIZES = {"sgpr-airline.train": {"rows_per_chip": 16384, "M": 256}}


def _readings(name: str, seed: int, on_chip: bool) -> dict:
    from bench import calibrate

    seconds = cells.spec()["run_seconds"] if on_chip else 2.0
    cell = cells.Cell.load(
        name, seed=seed, seconds=seconds, trace=False, t0=time.monotonic(),
        devices=jax.devices(), compiles=CompileLog(), out=io.StringIO(),
        config_overrides=None if on_chip else CPU_SIZES.get(name))
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    return cell.limits, dict(calibrate.train_readings(cell, control=True))


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes_on_the_chip(name):
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cells.workload(
            name)["chips"]:
        pytest.skip("needs the cell's TPU chips; the CPU variant below runs")
    limits, r = _readings(name, 2 ** 34 + 3, on_chip=True)
    assert all(r["program"][k] <= v for k, v in limits.items()), r["program"]
    assert any(r["control"][k] > v for k, v in limits.items()), r["control"]


@pytest.mark.parametrize("name", sorted(set(CELLS) & set(CPU_SIZES)))
def test_control_reads_apart_from_the_program_on_cpu(name):
    limits, r = _readings(name, 2 ** 34 + 3, on_chip=False)
    assert all(r["program"][k] <= v for k, v in limits.items()), r["program"]
    assert any(r["control"][k] >= 3 * r["program"][k] for k in limits), r
