"""Each cell end to end at a tiny size on the CPU, the look for a chip
skipped."""
import pytest

from bench.harness import cell as cells
from bench.tests.tiny import run_cell

SPEC = cells.spec()
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_cell_runs_and_is_correct(name):
    line = run_cell(name)
    assert list(line) == KEYS
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    w = cells.workload(name)
    want = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["device"]["count"] == w["chips"]
    for v in line["check"].values():
        assert v["value"] <= v["limit"]


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
