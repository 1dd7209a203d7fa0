"""Readings that the limits in bench/limits/<cell>.json are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 30

In one process, on the cell's chips and at its sizes, for each seed: the
numbers that decide `correct` for the program, read as a run reads them
(the set-up steps and the timed `fit` window of --seconds). For each
control seed also:
  control   the reference at the next precision below the configuration's
            ("high", three bfloat16 passes) put in the program's place;
  faults    planted in the reference put in the program's place: a step
            that returns its state unchanged; half of the rows left out,
            the mean taken over the rest; a window that starts from the
            start point instead of where set-up left off.
Prints one JSON line per reading and, last, for each number the largest
program reading (the lower end of its limit) and the smallest control and
fault readings (the upper end). The benchmark's own runs never run this.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def _train_numbers(st, got, ref) -> dict:
    """The compared numbers, and beside them, for the look at their causes,
    each set-up step's loss gap and each leaf's change gap."""
    from bench.drivers import fit
    from bench.harness import check

    nums = fit.numbers(st, got, ref)[0]
    for t, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        nums[f"loss_gap.step{t + 1}"] = abs(a - b) / abs(b)
    nums["change_gap.leaves"] = check.leaf_change_gaps(
        st["p0"], got["params"], ref["params"], ref["grad"])
    nums["window_change_gap.leaves"] = check.leaf_change_gaps(
        st["p0"], got["window_params"], ref["window_params"], ref["grad"])
    return nums


def _as_program(r: dict) -> dict:
    """A reference's readings put in the program's place."""
    return {"losses": r["losses"], "params": r["params"],
            "window_losses": r["window_losses"],
            "window_params": r["window_params"]}


def train_readings(cell, control: bool) -> list:
    from bench.drivers import fit

    st = fit.prepare(cell)
    w = fit.window(cell, st)
    del st["model"]
    gc.collect()
    ref = fit.reference(cell, st)
    out = [("program", _train_numbers(st, fit.program(st, w), ref))]
    if control:
        c = fit.reference(cell, st, "high")
        out.append(("control", _train_numbers(st, _as_program(c), ref)))
        p0, l0 = st["p0"], ref["losses"][0]
        out.append(("fault.unchanged", _train_numbers(
            st, {"losses": [l0] * st["steps0"], "params": p0,
                 "window_losses": [l0], "window_params": p0}, ref)))
        h = fit.reference(cell, st, "highest", slice(0, st["n"] // 2))
        out.append(("fault.half_batch",
                    _train_numbers(st, _as_program(h), ref)))
        r = fit.reference(cell, st, "highest", setup=False)
        out.append(("fault.window_from_start", _train_numbers(
            st, dict(fit.program(st, w), window_losses=r["window_losses"],
                     window_params=r["window_params"]), ref)))
    return out


def summarize(readings: list) -> dict:
    out = {}
    for kind, _, nums in readings:
        for name, v in nums.items():
            if not isinstance(v, float):
                continue
            d = out.setdefault(name, {"lower": None, "upper": {}})
            if kind == "program":
                d["lower"] = v if d["lower"] is None else max(d["lower"], v)
            else:
                prev = d["upper"].get(kind)
                d["upper"][kind] = v if prev is None else min(prev, v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True,
                    help="the cell's run_seconds: it sets the window's steps")
    ap.add_argument("--config", default="{}",
                    help="JSON overrides of the configuration, for a second "
                         "witness such as another statistics backend")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.harness import cell as cells
    from bench.harness.compile_log import CompileLog

    devices = jax.devices()
    w = cells.workload(args.workload)
    if devices[0].platform != "tpu" or len(devices) != w["chips"]:
        print(f"calibrate: {args.workload} needs {w['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform}",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileLog()
    readings = []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        cell = cells.Cell.load(args.workload, seed=seed, seconds=args.seconds,
                               trace=False, t0=T0, devices=devices,
                               compiles=compiles, out=sys.stderr,
                               config_overrides=json.loads(args.config))
        jax.config.update("jax_default_matmul_precision",
                          cell.config["matmul_precision"])
        for kind, nums in train_readings(cell, seed in args.control_seeds):
            if kind == "program" and seed not in args.seeds:
                continue
            readings.append((kind, seed, nums))
            print(json.dumps({"kind": kind, "seed": seed, **nums}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "summary": summarize(readings)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
