"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. The run makes its
data and start point on the device from --seed, warms up the shapes the
cell's traffic uses (set-up), measures for --seconds, checks what the timed
path produced against a plain reference, and prints one JSON object as the
last line of stdout: `correct`, `attempted`, `failed`, `metrics` (the
cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1), `device`, with --trace 1 `breakdown`, and last `check`, each
compared number beside its limit. Earlier stdout lines are JSON objects of
the run's phases; the compared numbers are also the last lines of stderr.

It needs a TPU with exactly the cell's number of chips: otherwise it exits
with code 2 before any model work and prints no result. JAX's persistent
compilation cache lives in $JAX_COMPILATION_CACHE_DIR if set, else in
.jax_cache/ at the root of the checkout.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _refuse(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell as cells

    try:
        w = cells.workload(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return _refuse(str(e))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        return _refuse(f"needs a TPU, JAX found platform {platform!r} "
                       f"({len(devices)} device(s)); not falling back")
    if len(devices) != w["chips"]:
        return _refuse(f"{args.workload} needs {w['chips']} chip(s), JAX "
                       f"found {len(devices)}")

    # the two lines of repro.compile_cache.enable, kept here
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench.harness.compile_log import CompileLog

    compiles = CompileLog()
    cell = cells.Cell.load(args.workload, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t0=T0, devices=devices,
                           compiles=compiles)
    cell.log(phase="device", platform=platform,
             device_kind=devices[0].device_kind, count=len(devices),
             compile_cache=jax.config.jax_compilation_cache_dir)
    return emit(cell)


def emit(cell) -> int:
    """Run the cell's driver and print the result; used by run.py and by
    the rehearsal tests, which skip the look for a chip."""
    import jax

    from bench.harness import cell as cells

    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    outcome = driver.run(cell)
    line = cells.result(cell, outcome)
    totals = cell.compiles.snapshot()
    totals.pop("names")
    cell.log(phase="compile_totals", **totals)
    for name, v in line["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), file=cell.out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
