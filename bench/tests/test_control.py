"""The control: the reference at the next precision below the
configuration's (`high`, three bfloat16 passes, written out as products so
that a CPU computes it as the chip does) put in the program's place.

On a TPU with the cell's chips, at the cell's own size, the control comes
out not correct against the cell's limits and the program comes out
correct; `bench/calibrate.py` reads the same numbers over more seeds, and
PERF.md gives the readings the limits were set from. On the CPU, at a size
a test run can hold, the comparison still tells the two precisions apart:
the control reads at least three times the program on one number. The
CPU size is the cell's `control` in `bench/tests/cells/<cell>.json`; a
four-chip cell reads it in a child process on four host devices.
"""
import jax
import pytest

from bench.harness import cell as cells
from bench.tests.tiny import CONTROL_SEED, control_readings, cpu_control

CELLS = [w["name"] for w in cells.spec()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes_on_the_chip(name):
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cells.workload(
            name)["chips"]:
        pytest.skip("needs the cell's TPU chips; the CPU variant below runs")
    limits, r = control_readings(name, CONTROL_SEED, on_chip=True)
    assert all(r["program"][k] <= v for k, v in limits.items()), r["program"]
    assert any(r["control"][k] > v for k, v in limits.items()), r["control"]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_apart_from_the_program_on_cpu(name):
    limits, r = cpu_control(name)
    assert all(r["program"][k] <= v for k, v in limits.items()), r["program"]
    assert any(r["control"][k] >= 3 * r["program"][k] for k in limits), r
