"""One run of one cell: find its files by the names in BENCHMARK.json, run
its traffic's driver, read the per-layer metrics from the trace, and build
the result line.

A cell names a configuration (`bench/configs/<config>.json`) and a traffic
mix (`bench/traffic/<traffic>.json`). The configuration names its model
kind, which selects `bench/models/<model>.py` (the system under test),
`bench/reference/<model>.py` and `bench/work/<model>.py`; the traffic names
its driver, `bench/drivers/<driver>.py`. The limits of the cell's
correctness numbers are in `bench/limits/<cell>.json`, and each per-layer
metric is read by `bench/metrics/<metric>.py`. Adding a cell, a
configuration, a traffic mix or a metric adds files and entries; no file
here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, benchmark: Optional[dict] = None) -> dict:
    for w in (benchmark or spec())["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: str, reported: set) -> bool:
    """Whether a metric is reported in `cell`: listed there, or unlisted and
    the cell reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: dict
    numbers: dict
    memory: int
    trace_dir: Optional[str]
    layer: dict


@dataclasses.dataclass
class Cell:
    name: str
    seed: int
    seconds: float
    trace: bool
    t0: float
    benchmark: dict
    config: dict
    traffic: dict
    limits: dict
    chips: int
    devices: list
    mesh: Any
    compiles: Any
    model: Any
    reference: Any
    work: Any
    out: Any = sys.stdout

    @classmethod
    def load(cls, name: str, *, seed: int, seconds: float, trace: bool,
             t0: float, devices: list, compiles, config_overrides=None,
             out=None) -> "Cell":
        from repro.core.distributed import make_gp_mesh

        benchmark = spec()
        w = workload(name, benchmark)
        cfg = load_json(BENCH / "configs" / f"{w['config']}.json")
        cfg.update(config_overrides or {})
        traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        kind = cfg["model"]
        return cls(
            name=name, seed=seed, seconds=seconds, trace=trace, t0=t0,
            benchmark=benchmark, config=cfg, traffic=traffic,
            limits=load_json(BENCH / "limits" / f"{name}.json"),
            chips=w["chips"], devices=devices,
            mesh=make_gp_mesh(len(devices)), compiles=compiles,
            model=importlib.import_module(f"bench.models.{kind}"),
            reference=importlib.import_module(f"bench.reference.{kind}"),
            work=importlib.import_module(f"bench.work.{kind}"),
            out=out or sys.stdout)

    def log(self, **fields) -> None:
        print(json.dumps(fields, default=float), file=self.out, flush=True)

    @contextlib.contextmanager
    def tracing(self):
        if not self.trace:
            yield None
            return
        from bench.harness import trace

        d = tempfile.mkdtemp(prefix="bench-trace-")
        with trace.capture(d):
            yield d

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


def _layer_metrics(cell: Cell, outcome: Outcome, reported: set):
    from bench.harness import trace

    t = trace.load(outcome.trace_dir)
    ctx = dict(outcome.layer, trace=t, chips=cell.chips,
               peaks=device_peaks(cell.devices[0].device_kind))
    values = {}
    for m in cell.benchmark["per_layer"]:
        if not applies(m, cell.name, reported):
            continue
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"busy_s": t.mean_busy_s(), "window_s": t.window_s}
    breakdown = {"device_ops": t.top_ops(10), "idle_gaps": t.idle_gaps(10)}
    return values, device, breakdown


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in bench/peaks.json")
    return table[kind]


def result(cell: Cell, outcome: Outcome) -> dict:
    """The last line of a run, as the contract orders its keys."""
    from bench.harness import check

    e2e = [m for m in cell.benchmark["end_to_end"]
           if applies(m, cell.name, set(outcome.metrics))]
    reported = {m["name"] for m in e2e}
    ok, numbers = check.verdict(outcome.numbers, cell.limits)
    d0 = cell.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": outcome.memory}
    line = {"correct": ok and outcome.failed == 0,
            "attempted": outcome.attempted, "failed": outcome.failed}
    if cell.trace:
        try:
            metrics, extra, breakdown = _layer_metrics(cell, outcome, reported)
        finally:
            shutil.rmtree(outcome.trace_dir, ignore_errors=True)
        device.update(extra)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        line.update(metrics={m["name"]: {"value": outcome.metrics[m["name"]],
                                         "unit": m["unit"]} for m in e2e},
                    device=device)
    line["check"] = numbers
    return line
