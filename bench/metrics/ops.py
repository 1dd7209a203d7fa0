"""Which device operations belong to which layer, by the names the trace shows.

A TPU trace names each operation by its HLO instruction, "%<name> = ...".
The patterns live in data files, one per rule, `bench/metrics/ops/*.json`:
{"selector": <name>, "instruction": <regex>}. A later change that names
more of the program (a scope, a kernel `name=`) adds a file here and every
reader of that selector follows it.

Today the statistics pass is the Pallas custom calls of kernels/suffstats.py
alone (the program names no scope); XLA ops fused around them are not
counted.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

from bench.harness.trace import instruction


def _rules() -> dict:
    rules: dict = {}
    for path in sorted((Path(__file__).parent / "ops").glob("*.json")):
        r = json.loads(path.read_text())
        rules.setdefault(r["selector"], []).append(re.compile(r["instruction"]))
    return rules


RULES = _rules()


def matches(selector: str, e) -> bool:
    """Whether operation event `e` belongs to `selector`."""
    name = instruction(e.name)
    return any(p.search(name) for p in RULES.get(selector, []))


def is_psi(e) -> bool:
    return matches("psi", e)
