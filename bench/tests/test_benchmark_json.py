"""BENCHMARK.json keeps to the contract, and every name in it finds its files."""
import json
import math
import re

import pytest

from bench.harness import cell as cells
from bench.tests import tiny

ROOT = cells.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TEXT_KEYS = ("why", "layer", "source")
METRIC_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}


def _all_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            yield entry["name"]
    for w in SPEC["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in SPEC["configs"]:
        yield from c["reduced"]


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("name", sorted(set(_all_names())))
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


def test_names_unique_within_each_group():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_command_and_paths():
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for key in TEXT_KEYS:
        if key in metric:
            v = metric[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v
    if metric in SPEC["end_to_end"]:
        assert set(metric) <= METRIC_KEYS
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= LAYER_KEYS
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert (cells.BENCH / "metrics" / f"{metric['name']}.py").is_file()
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_s_is_everywhere_with_bound_quarter():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    assert setup[0]["bound"] == 0.25


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files_and_reports_enough(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    for path in (cells.BENCH / "traffic" / f"{w['traffic']}.json",
                 cells.BENCH / "limits" / f"{w['name']}.json"):
        assert path.is_file(), path
    sizes = tiny.sizes_file(w["name"])
    assert sizes.is_file(), f"{sizes}: the cell's CPU sizes are missing"
    assert {"rehearsal", "control"} <= set(tiny.sizes(w["name"])), (
        f"{sizes}: needs the keys rehearsal and control")
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"] if w["name"] in m["workloads"]]
    assert layer
    for m in layer:  # every per-layer metric's cells report what it moves
        assert m["moves"] in e2e


def test_every_cpu_sizes_file_names_a_cell():
    names = {w["name"] for w in SPEC["workloads"]}
    for path in tiny.SIZES.glob("*.json"):
        assert path.stem in names, f"{path}: no such cell in BENCHMARK.json"


def test_each_pair_of_config_and_traffic_once():
    """Whatever the chips: a four-chip cell of a deployment brings a
    configuration of its own (PERF.md section 7)."""
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_within_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["source"].startswith("https://")
    path = ROOT / c["file"]
    assert path.is_file() and any(c["file"].startswith(p + "/")
                                  for p in SPEC["paths"])
    data = json.loads(path.read_text())
    assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
    assert set(c["reduced"]) <= set(data)
    assert c["name"] in {w["config"] for w in SPEC["workloads"]}
    assert len(c["reduced"]) <= 16
    for key in c["reduced"]:
        assert not re.search(r"(_dim|_rank)$|hidden|intermediate|head", key)
    for module in ("models", "reference", "work"):
        assert (cells.BENCH / module / f"{data['model']}.py").is_file()


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_per_layer_metric_workloads_exist():
    names = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        assert set(m.get("workloads", [])) <= names
    assert not math.isnan(len(names))
