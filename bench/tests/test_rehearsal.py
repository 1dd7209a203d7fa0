"""Each cell end to end at its rehearsal size on the CPU, the look for a
chip skipped: a one-chip cell in this process, a four-chip cell in a child
process on four host devices."""
import pytest

from bench.harness import cell as cells
from bench.tests.tiny import on_host_devices, rehearse, run_cell

SPEC = cells.spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]
# a one-chip cell that proves the four-chip path while no four-chip cell
# exists; its rehearsal size gives each device 4,096 rows, above the
# 1,024 under which interpret-mode kernels fail inside shard_map
STAND_IN = "sgpr-airline.train"
AS_FOUR_CHIPS = f"{__name__}:stand_in_as_four_chips"
FOUR_CHIP_CELL = any(w["chips"] == 4 for w in SPEC["workloads"])


def stand_in_as_four_chips(monkeypatch):
    """A hook for the child: the harness sees the stand-in as a cell of
    four chips, so it takes the rows of four."""
    spec = cells.spec()
    cells.workload(STAND_IN, spec)["chips"] = 4
    monkeypatch.setattr(cells, "spec", lambda: spec)


def _assert_sound(name, line, chips):
    assert list(line) == KEYS
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["device"]["count"] == chips
    for v in line["check"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    _assert_sound(name, rehearse(name), cells.workload(name)["chips"])


@pytest.mark.skipif(FOUR_CHIP_CELL,
                    reason="a four-chip cell rehearses on this path itself")
def test_four_chip_rehearsal_on_host_devices():
    """The path a four-chip cell rehearses on, run as a four-chip cell:
    the child sees four devices and the rows of four chips."""
    _assert_sound(STAND_IN, on_host_devices(
        "rehearsal", STAND_IN, 4, hooks=(AS_FOUR_CHIPS,)), 4)


def test_traced_run_reports_layer_metrics(monkeypatch):
    """--trace 1 plumbing: the recorded TPU trace stands in for the
    profiler, whose CPU trace has no device plane."""
    import contextlib

    from bench.harness import trace
    from bench.tests.test_trace import _recorded

    monkeypatch.setattr(trace, "capture", lambda d: contextlib.nullcontext())
    monkeypatch.setattr(trace, "load", lambda d: _recorded())
    monkeypatch.setattr(cells, "device_peaks",
                        lambda kind: {"flops_bf16": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    line = run_cell("sgpr-airline.train", trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "check"]
    assert set(line["metrics"]) == {"idle_pct.train", "step_mfu",
                                    "psi_roofline", "psi_time_pct"}
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) == 10
    assert len(line["breakdown"]["idle_gaps"]) == 10
