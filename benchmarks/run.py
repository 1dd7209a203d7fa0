"""Benchmark driver: one section per paper table/figure + the roofline table
+ the streaming-engine sweep (BENCH_gp.json) + the serving-latency sweep
(BENCH_serve.json) + the static per-kernel VMEM budget table
(BENCH_vmem.json, from repro.analysis.pallas_audit).

    PYTHONPATH=src python -m benchmarks.run [--fast|--smoke] [--only SECTION] \
        [--out BENCH_gp.json] [--serve-out BENCH_serve.json] \
        [--vmem-out BENCH_vmem.json]

Prints ``name,us_per_call,derived`` CSV rows to stdout. Whenever the
gp_stream / serve sections run (both default; excluded only by ``--only``
with another section), the machine-readable results are written to
``--out`` / ``--serve-out`` so perf PRs have a trajectory to diff against.

Before running anything, every committed BENCH_*.json at the repo root is
validated: it must parse and its meta.schema_version must match
`benchmarks.common.SCHEMA_VERSION` — a row-format change therefore forces
regenerating the committed trajectories.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

SECTIONS = ("gp_scaling", "indistributable", "psi_kernels", "gp_stream",
            "serve", "serve_load", "temporal", "lm_step", "roofline",
            "analysis", "tune")

# every serve_load row must carry these keys (validate_bench_files checks the
# committed BENCH_serve.json against this, so the sustained-load trajectory
# can't silently lose its acceptance columns)
SERVE_LOAD_ROW_KEYS = frozenset({
    "section", "op", "path", "models", "clients", "duration_s",
    "budget_bytes", "requests", "qps", "p50_us", "p99_us", "updates",
    "evictions", "lazy_loads", "peak_resident_bytes", "under_budget",
})


def validate_bench_files(root=None, *, exclude=()) -> list:
    """Check every BENCH_*.json under `root` (default: the repo root)
    parses and carries the current schema version; returns the file names.
    Raises ValueError with the offending file on any mismatch. `exclude`
    names files to skip — the driver passes the outputs the current run is
    about to overwrite, so bumping SCHEMA_VERSION never deadlocks the
    regeneration command on its own stale outputs."""
    from benchmarks.common import SCHEMA_VERSION

    root = pathlib.Path(root) if root is not None else \
        pathlib.Path(__file__).resolve().parents[1]
    names = []
    for path in sorted(root.glob("BENCH_*.json")):
        if path.name in exclude:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception as e:
            raise ValueError(f"{path.name}: does not parse as JSON ({e})") from None
        version = (doc.get("meta") or {}).get("schema_version")
        if version != SCHEMA_VERSION:
            raise ValueError(
                f"{path.name}: meta.schema_version is {version!r}, current is "
                f"{SCHEMA_VERSION} — regenerate with `python -m benchmarks.run`")
        if not isinstance(doc.get("rows"), list) or not doc["rows"]:
            raise ValueError(f"{path.name}: missing or empty rows list")
        if path.name == "BENCH_serve.json":
            load_rows = [r for r in doc["rows"]
                         if isinstance(r, dict) and r.get("section") == "serve_load"]
            if not load_rows:
                raise ValueError(
                    f"{path.name}: no serve_load rows — regenerate with "
                    "`python -m benchmarks.run --only serve_load`")
            for r in load_rows:
                missing = SERVE_LOAD_ROW_KEYS - r.keys()
                if missing:
                    raise ValueError(
                        f"{path.name}: serve_load row missing keys "
                        f"{sorted(missing)}")
                if r.get("budget_bytes") is not None and not r.get("under_budget"):
                    raise ValueError(
                        f"{path.name}: budgeted serve_load row exceeded its "
                        f"budget (peak {r.get('peak_resident_bytes')} > "
                        f"{r.get('budget_bytes')})")
        names.append(path.name)
    return names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", "--smoke", dest="fast", action="store_true",
                    help="smaller sweeps (CI smoke mode)")
    ap.add_argument("--only", choices=SECTIONS, default=None,
                    help="run a single section")
    ap.add_argument("--out", default=None,
                    help="where to write the streaming-engine JSON "
                         "(default: BENCH_gp.json, or BENCH_gp.smoke.json "
                         "under --smoke so the committed full-sweep "
                         "trajectory is never clobbered by a smoke run)")
    ap.add_argument("--serve-out", default=None,
                    help="where to write the serving-latency JSON (default: "
                         "BENCH_serve.json, or BENCH_serve.smoke.json under "
                         "--smoke)")
    ap.add_argument("--vmem-out", default=None,
                    help="where to write the static VMEM budget table "
                         "(default: BENCH_vmem.json, or BENCH_vmem.smoke.json "
                         "under --smoke)")
    ap.add_argument("--tune-out", default=None,
                    help="where to write the autotuner tuned-vs-default "
                         "table (default: BENCH_tune.json, or "
                         "BENCH_tune.smoke.json under --smoke)")
    ap.add_argument("--temporal-out", default=None,
                    help="where to write the temporal-backend parallel-vs-"
                         "sequential scan table (default: "
                         "BENCH_temporal.json, or BENCH_temporal.smoke.json "
                         "under --smoke)")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    if args.out is None:
        args.out = "BENCH_gp.smoke.json" if args.fast else "BENCH_gp.json"
    if args.serve_out is None:
        args.serve_out = "BENCH_serve.smoke.json" if args.fast else "BENCH_serve.json"
    if args.vmem_out is None:
        args.vmem_out = "BENCH_vmem.smoke.json" if args.fast else "BENCH_vmem.json"
    if args.tune_out is None:
        args.tune_out = "BENCH_tune.smoke.json" if args.fast else "BENCH_tune.json"
    if args.temporal_out is None:
        args.temporal_out = ("BENCH_temporal.smoke.json" if args.fast
                             else "BENCH_temporal.json")

    overwriting = {pathlib.Path(args.out).name, pathlib.Path(args.serve_out).name,
                   pathlib.Path(args.vmem_out).name,
                   pathlib.Path(args.tune_out).name,
                   pathlib.Path(args.temporal_out).name}
    committed = validate_bench_files(exclude=overwriting)
    print(f"# committed bench files OK: {', '.join(committed) or '(none)'}",
          file=sys.stderr)

    def wanted(name: str) -> bool:
        return args.only is None or args.only == name

    from benchmarks import (gp_scaling, gp_stream, indistributable, lm_step,
                            psi_kernels, roofline_table)
    from repro.configs.base import ARCH_IDS

    rows = ["name,us_per_call,derived"]
    json_rows = []
    if wanted("gp_scaling"):
        print("# paper Fig 1a - GP-LVM iteration time vs N", file=sys.stderr)
        rows += gp_scaling.run(sizes=(1024, 4096) if args.fast else gp_scaling.SIZES)
    if wanted("indistributable"):
        print("# paper Fig 1b - indistributable fraction", file=sys.stderr)
        rows += indistributable.run(sizes=(1024, 4096) if args.fast else indistributable.SIZES)
    if wanted("psi_kernels"):
        print("# paper S3 - psi-statistic kernels", file=sys.stderr)
        rows += psi_kernels.run()
    if wanted("gp_stream"):
        print("# streaming suffstats engine - time/point + peak memory vs N",
              file=sys.stderr)
        csv, json_rows = gp_stream.run(smoke=args.fast)
        rows += csv
    serve_doc = None
    if wanted("serve"):
        from benchmarks import serve_latency

        print("# serving path - predict latency p50/p95 + update throughput",
              file=sys.stderr)
        csv, serve_doc = serve_latency.run(smoke=args.fast)
        rows += csv
    temporal_doc = None
    if wanted("temporal"):
        from benchmarks import temporal_bench

        print("# temporal backend - parallel associative scan vs sequential "
              "lax.scan (lml + predict)", file=sys.stderr)
        csv, temporal_doc = temporal_bench.run(smoke=args.fast)
        rows += csv
    load_rows = None
    if wanted("serve_load"):
        from benchmarks import serve_load

        print("# serving path - sustained load: QPS, tail latency, eviction "
              "traffic under a byte budget", file=sys.stderr)
        csv, load_rows = serve_load.run(smoke=args.fast)
        rows += csv
    if wanted("lm_step"):
        print("# LM smoke step bench", file=sys.stderr)
        rows += lm_step.run(archs=["smollm-360m", "rwkv6-7b"] if args.fast else ARCH_IDS)
    if wanted("roofline"):
        print("# roofline table (from dry-run artifacts)", file=sys.stderr)
        rows += roofline_table.run()
    vmem_doc = None
    if wanted("analysis"):
        from benchmarks import analysis_vmem

        print("# static analysis - per-kernel VMEM budget table",
              file=sys.stderr)
        csv, vmem_doc = analysis_vmem.run(smoke=args.fast)
        rows += csv
    tune_doc = None
    if wanted("tune"):
        from benchmarks import tune_bench

        print("# autotuner - tuned-vs-default blocks + roofline check",
              file=sys.stderr)
        csv, tune_doc = tune_bench.run(smoke=args.fast)
        rows += csv
    print("\n".join(rows))

    if wanted("gp_stream"):
        import jax

        from benchmarks.common import SCHEMA_VERSION

        doc = {
            "meta": {
                "bench": "gp_stream",
                "schema_version": SCHEMA_VERSION,
                "jax_backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "smoke": bool(args.fast),
                "chunk": gp_stream.CHUNK,
                "M": gp_stream.M,
            },
            "rows": json_rows,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {args.out} ({len(json_rows)} rows)", file=sys.stderr)
    if serve_doc is not None or load_rows is not None:
        # BENCH_serve.json holds both the latency sweep and the sustained-load
        # rows; whichever section didn't run this invocation keeps its rows
        # from the existing file, so `--only serve_load` never clobbers the
        # latency trajectory (and vice versa).
        from benchmarks.common import SCHEMA_VERSION

        existing = {}
        if serve_doc is None or load_rows is None:
            try:
                with open(args.serve_out) as f:
                    existing = json.load(f)
            except (OSError, ValueError):
                existing = {}
        ex_rows = existing.get("rows") or []
        if serve_doc is not None:
            meta, latency_rows = serve_doc["meta"], serve_doc["rows"]
        else:
            meta = existing.get("meta") or {
                "bench": "serve_latency", "schema_version": SCHEMA_VERSION,
                "smoke": bool(args.fast)}
            latency_rows = [r for r in ex_rows
                            if r.get("section") != "serve_load"]
        if load_rows is None:
            load_rows = [r for r in ex_rows
                         if r.get("section") == "serve_load"]
        merged = {"meta": meta, "rows": latency_rows + load_rows}
        with open(args.serve_out, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"# wrote {args.serve_out} ({len(merged['rows'])} rows)",
              file=sys.stderr)
    if vmem_doc is not None:
        with open(args.vmem_out, "w") as f:
            json.dump(vmem_doc, f, indent=1)
        print(f"# wrote {args.vmem_out} ({len(vmem_doc['rows'])} rows)",
              file=sys.stderr)
    if tune_doc is not None:
        with open(args.tune_out, "w") as f:
            json.dump(tune_doc, f, indent=1)
        print(f"# wrote {args.tune_out} ({len(tune_doc['rows'])} rows)",
              file=sys.stderr)
    if temporal_doc is not None:
        with open(args.temporal_out, "w") as f:
            json.dump(temporal_doc, f, indent=1)
        print(f"# wrote {args.temporal_out} ({len(temporal_doc['rows'])} rows)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
