import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs (no allocation), print memory/cost analysis, parse
collective traffic, and persist a JSON report per cell under
experiments/dryrun/.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both

The two XLA_FLAGS lines above MUST run before any other import (jax locks the
device count at first init); this module is the only place in the repo that
requests 512 host devices.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import ARCH_IDS, SHAPES, cell_applicable, get_config  # noqa: E402
from repro.launch import hlo_cost, roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import make_step  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             force: bool = False) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    out_path = out_dir / f"{arch}_{shape_name}_{mesh_name}.json"
    if out_path.exists() and not force:
        rec = json.loads(out_path.read_text())
        print(f"[cached] {arch} x {shape_name} x {mesh_name}: {rec.get('status')}")
        return rec

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len, "global_batch": shape.global_batch,
    }
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[skip]   {arch} x {shape_name}: {why}")
        return rec

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.size
        t0 = time.time()
        with mesh:
            bundle = make_step(shape.kind, cfg, shape, mesh)
            lowered = bundle.lower()
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        # trip-count-aware accounting (XLA's cost_analysis counts scan bodies
        # once — see launch/hlo_cost.py); XLA's raw numbers kept for reference
        cost = hlo_cost.analyze(hlo_text)

        flops = float(cost.flops)
        bytes_accessed = float(cost.bytes)
        terms = roofline.roofline_terms(flops, bytes_accessed, cost.coll_traffic)

        params_a = bundle.abstract_args[0]
        n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mf = roofline.model_flops(cfg, params_a, n_tokens)
        if shape.kind != "train":
            # 6ND counts fwd+bwd; prefill/decode are forward-only => 2ND
            mf["model_flops"] /= 3.0
        useful = mf["model_flops"] / (flops * n_chips) if flops else 0.0

        rec.update(
            status="ok",
            n_chips=n_chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_hbm_bytes_est": ma.argument_size_in_bytes
                + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            flops_per_chip=flops,
            bytes_per_chip=bytes_accessed,
            xla_flops_scan_once=float(ca.get("flops", 0.0)),
            xla_bytes_scan_once=float(ca.get("bytes accessed", 0.0)),
            collectives={
                "counts": cost.coll_counts,
                "raw_bytes_per_chip": cost.coll_raw,
                "traffic_bytes_per_chip": cost.coll_traffic,
            },
            roofline=terms,
            model_flops=mf,
            useful_compute_fraction=useful,
            n_params_total=roofline.count_params(params_a),
        )
        hbm_gb = rec["memory"]["peak_hbm_bytes_est"] / 2**30
        print(
            f"[ok]     {arch} x {shape_name} x {mesh_name}: "
            f"compile {t_compile:.1f}s, {hbm_gb:.2f} GiB/chip, "
            f"dominant={terms['dominant']} bound={terms['step_lower_bound_s']*1e3:.2f} ms "
            f"useful={useful:.2f}"
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL]   {arch} x {shape_name} x {mesh_name}: {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape cell or 'all'")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, out_dir, force=args.force)
                n_fail += rec.get("status") == "error"
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
