"""Bring-up smoke test: the sparse-GP train -> serve path on TPU chips.

    python chip_smoke.py              # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4    # four chips: phase (d) alone

One process drives every chip it uses, through the public API, on data
drawn from --seed; all arrays are float32 and every statistic runs on the
fused Pallas kernels (backend="fused"), forward and reverse.

  (a) SparseGPRegression at the shape of the airline regression of Hensman
      et al. 2013 (arXiv:1309.6835): input dim 8, M=1024 inducing points,
      N=1,048,576 rows, D=1. Three Adam steps, then export_state() into a
      GPServer that answers 8 requests of 64 points, half through
      predict() and half through submit() (the micro-batching worker).
  (b) BayesianGPLVM with Q=8, M=256, N=65,536, D=128: three Adam steps.
  (c) At the fitted parameters of (a) and (b), the fused kernels'
      SuffStats against backend="jnp" under
      jax.default_matmul_precision("highest"), on 65,536 rows of each.
  (d) --chips 4: the paper's data-parallel GP-LVM (one psum of the
      statistics, q_mu/q_logS sharded) at N=262,144 on a four-device mesh,
      checked against the same data and parameters on a one-device mesh.

Without a TPU it exits non-zero before any model work and prints no
result. Every phase failure raises, so any failed phase exits non-zero.
Earlier stdout lines are one JSON object per phase (compile seconds, step
seconds, reference errors, served shapes); the last line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Compile seconds (`backend_compile_s`) are `repro.compile_cache`'s build
seconds: tracing, lowering, and compiling or reading the persistent cache.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# (c): max |fused - reference| / max |reference|, per statistic. float32
# carries eps = 2^-23 ~ 1.2e-7. The kernels expand (mu - z)^2 into
# mu^2 - 2 mu z + z^2 before exponentiating, so each exponent is off by
# about eps times its largest term (|x / l|^2 stays below ~100 here), i.e.
# ~1e-5 relative in each exp; summing 65,536 rows in another order adds
# ~sqrt(N) eps ~ 3e-5. 1e-3 leaves ten times that as headroom, while a
# single bf16 MXU pass (2^-8 ~ 4e-3 relative per product, the TPU default
# for float32 matmuls) puts ~0.4 into the exponent and fails it.
REF_TOL = 1e-3

# (d): four-device mesh against one device, both under "highest" matmul
# precision so that only the order of the float32 sums differs (four
# partial sums and a psum against one sequential accumulation).
# Loss: |l4 - l1| / |l1|. Gradients: max |g4 - g1| / max |g1| per leaf,
# q_mu compared row for row. Reordering 65,536-row partial sums moves the
# statistics by ~sqrt(N) eps ~ 3e-5 relative, which the O(M^3) epilogue
# may amplify tenfold in the loss and by the conditioning of
# Kuu + beta Psi2 in the gradients; a lost or doubled shard in the psum
# moves them by 1/4 or more.
LOSS_RTOL = 1e-4
GRAD_RTOL = 1e-2

REGRESSION = dict(n=1_048_576, q=8, m=1024, d=1)
GPLVM = dict(n=65_536, q=8, m=256, d=128)
GPLVM_4CHIP = dict(n=262_144, q=8, m=256, d=128)
REF_ROWS = 65_536
STEPS = 3
REQUESTS, REQUEST_ROWS = 8, 64


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def timed(fn):
    """(result, wall seconds, compile seconds inside them)."""
    from repro import compile_cache

    c0, t0 = compile_cache.snapshot()["build_s"], time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    return out, wall, compile_cache.snapshot()["build_s"] - c0


def kernel_calls(fn, *args) -> int:
    """Pallas TPU kernels in the lowered program of fn(*args)."""
    return jax.jit(fn).lower(*args).as_text().count("@tpu_custom_call")


def all_finite(*arrays) -> bool:
    return all(bool(np.isfinite(np.asarray(a)).all()) for a in arrays)


def fit_phase(name, model, *data, n, **fit_kw):
    """Fit `model` for STEPS Adam steps; logs compile and step seconds."""
    _, wall, comp = timed(lambda: model.fit(*data, steps=STEPS, **fit_kw))
    check(len(model.history) == 1 and all_finite(model.history),
          f"{name}: loss not finite: {model.history}")
    log(phase=name, n=n, steps=STEPS, loss=model.history[-1],
        backend_compile_s=comp, steps_s=wall - comp,
        step_s=(wall - comp) / STEPS,
        note="bring-up observation, not a benchmark number")


def rel_err(a, b) -> float:
    """max |a - b| / max |b|."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def stats_error(kern, params, batch) -> dict:
    """max |fused - jnp@highest| / max |jnp@highest| per statistic."""
    from repro.gp import suff_stats

    fused = jax.jit(functools.partial(suff_stats, kern, backend="fused"))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(suff_stats, kern, backend="jnp"))(
            params, batch)
    return {field: rel_err(a, b) for field, a, b in zip(ref._fields, fused, ref)}


def regression_and_serving(key, *, n, q, m, d):
    """(a): SGPR fit on the fused kernels, then export -> GPServer."""
    from repro.core import distributed
    from repro.core.distributed import make_gp_mesh
    from repro.gp import SparseGPRegression, get
    from repro.serve import GPServer

    kx, kw, kn, kq = jax.random.split(key, 4)
    X = jax.random.normal(kx, (n, q), jnp.float32)
    w = jax.random.normal(kw, (q, d), jnp.float32) / math.sqrt(q)
    Y = jnp.sin(2.0 * X @ w) + 0.1 * jax.random.normal(kn, (n, d), jnp.float32)

    mesh = make_gp_mesh()
    gp = SparseGPRegression(kernel=get("rbf")(q), M=m, mesh=mesh,
                            backend="fused")
    loss = distributed.sgpr_loss_dist(mesh, kernel=gp.kernel, backend="fused")
    calls = kernel_calls(jax.value_and_grad(loss), gp.init_params(X, Y), X, Y)
    check(calls >= 2, f"regression step holds {calls} Pallas TPU kernels, "
          "expected the fused forward and reverse")
    log(phase="a_kernels", tpu_custom_calls=calls)
    fit_phase("a_fit", gp, X, Y, n=n)

    server = GPServer()
    try:
        _, wall, comp = timed(lambda: server.register("airline", gp))
        log(phase="a_export", wall_s=wall, backend_compile_s=comp)
        Xq = jax.random.normal(kq, (REQUESTS, REQUEST_ROWS, q), jnp.float32)
        half = REQUESTS // 2
        direct = [server.predict("airline", Xq[i]) for i in range(half)]
        futures = [server.submit("airline", Xq[i])
                   for i in range(half, REQUESTS)]
        queued = [f.result(timeout=600) for f in futures]
    finally:
        server.close()
    for mean, var in direct + queued:
        check(mean.shape == (REQUEST_ROWS, d) and var.shape == (REQUEST_ROWS,),
              f"served shapes {mean.shape}, {var.shape}")
        check(all_finite(mean, var), "served prediction not finite")
    log(phase="a_serve", requests=REQUESTS, predict=half,
        submit=REQUESTS - half, mean_shape=list(direct[0][0].shape),
        var_shape=list(direct[0][1].shape), finite=True)
    return gp, X, Y


def gplvm_fit(key, *, n, q, m, d, mesh, phase):
    """(b) / (d): BayesianGPLVM fit on the fused kernels."""
    from repro.core import distributed, gplvm
    from repro.data.synthetic import gplvm_synthetic
    from repro.gp import BayesianGPLVM, get

    kd, ki = jax.random.split(key)
    _, Y = gplvm_synthetic(kd, n, D=d, Q=q)
    lvm = BayesianGPLVM(kernel=get("rbf")(q), M=m, mesh=mesh, backend="fused")
    loss = distributed.gplvm_loss_dist(mesh, kernel=lvm.kernel, backend="fused")
    # the parameters fit() starts from: the same init, key and data
    params = distributed.shard_gp_params(
        gplvm.init_params(ki, Y, q, m, kernel=lvm.kernel), mesh)
    calls = kernel_calls(jax.value_and_grad(loss), params, Y)
    check(calls >= 2, f"GP-LVM step holds {calls} Pallas TPU kernels, "
          "expected the fused forward and reverse")
    log(phase=f"{phase}_kernels", tpu_custom_calls=calls)
    fit_phase(f"{phase}_fit", lvm, Y, n=n, key=ki)
    return lvm, Y, params


def reference_check(gp, X, Y, lvm, Ylvm):
    """(c): fused SuffStats against the highest-precision jnp formulas."""
    from repro.gp import ExactBatch, ExpectedBatch

    r = REF_ROWS
    p = gp.params
    errs_a = stats_error(gp.kernel, p["kern"], ExactBatch(X[:r], Y[:r], p["Z"]))
    p = lvm.params
    errs_b = stats_error(lvm.kernel, p["kern"], ExpectedBatch(
        p["q_mu"][:r], jnp.exp(p["q_logS"][:r]), Ylvm[:r], p["Z"]))
    log(phase="c_reference", rows=r, tol=REF_TOL, regression=errs_a,
        gplvm=errs_b)
    for label, errs in (("regression", errs_a), ("gplvm", errs_b)):
        for field, err in errs.items():
            check(err <= REF_TOL, f"{label} {field}: fused vs jnp@highest "
                  f"error {err:.3e} > {REF_TOL:.0e}")


def check_spread(name, arr, n_devices):
    rows = {s.data.shape[0] for s in arr.addressable_shards}
    devices = {s.device for s in arr.addressable_shards}
    check(len(devices) == n_devices and rows == {arr.shape[0] // n_devices},
          f"{name} is not split over {n_devices} devices: "
          f"{len(devices)} devices, shard rows {sorted(rows)}")


def four_chips(key, *, n, q, m, d):
    """(d): data-parallel GP-LVM on four chips against one device."""
    from repro.core import distributed
    from repro.core.distributed import make_gp_mesh

    mesh4, mesh1 = make_gp_mesh(), make_gp_mesh(1)
    check(len(mesh4.devices.flat) == 4, f"mesh has {mesh4.devices.size} devices")
    lvm, Y, params4 = gplvm_fit(key, n=n, q=q, m=m, d=d, mesh=mesh4,
                                phase="d")
    check_spread("Y", lvm._data[0], 4)
    for name in ("q_mu", "q_logS"):
        check_spread(name, lvm.params[name], 4)
        check_spread(f"initial {name}", params4[name], 4)

    out = {}
    for label, mesh in (("4", mesh4), ("1", mesh1)):
        loss = distributed.gplvm_loss_dist(mesh, kernel=lvm.kernel,
                                           backend="fused")
        params = distributed.shard_gp_params(params4, mesh)
        Yd = jax.device_put(Y, distributed.data_sharded(mesh))
        step = jax.jit(jax.value_and_grad(loss))
        with jax.default_matmul_precision("highest"):
            (val, grads), wall, comp = timed(
                lambda: jax.block_until_ready(step(params, Yd)))
        out[label] = (float(val), jax.device_get(grads))
        log(phase=f"d_value_and_grad_{label}dev", loss=float(val),
            backend_compile_s=comp, wall_s=wall - comp,
            note="bring-up observation, not a benchmark number")
    (l4, g4), (l1, g1) = out["4"], out["1"]
    errs = {"loss": abs(l4 - l1) / abs(l1)}
    for name in ("Z", "log_beta", "q_mu", "q_logS"):
        errs[f"d{name}"] = rel_err(g4[name], g1[name])
    for name in sorted(g1["kern"]):
        errs[f"dkern.{name}"] = rel_err(g4["kern"][name], g1["kern"][name])
    log(phase="d_mesh4_vs_mesh1", loss_rtol=LOSS_RTOL, grad_rtol=GRAD_RTOL,
        **errs)
    check(errs.pop("loss") <= LOSS_RTOL, "loss differs between meshes")
    for name, err in errs.items():
        check(err <= GRAD_RTOL, f"{name} differs between meshes: {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU but JAX found platform {platform!r} "
              f"({len(devices)} device(s)); not falling back", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly that many "
              f"TPU chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    compile_cache.snapshot()  # counting starts here
    kind = devices[0].device_kind
    log(phase="device", platform=platform, device_kind=kind,
        count=len(devices), compile_cache=cache_dir)

    key = jax.random.PRNGKey(args.seed)
    ka, kb, kd = jax.random.split(key, 3)
    if args.chips == 4:
        four_chips(kd, **GPLVM_4CHIP)
    else:
        from repro.core.distributed import make_gp_mesh

        gp, X, Y = regression_and_serving(ka, **REGRESSION)
        lvm, Ylvm, _ = gplvm_fit(kb, mesh=make_gp_mesh(), phase="b", **GPLVM)
        reference_check(gp, X, Y, lvm, Ylvm)
    counts = compile_cache.snapshot()
    log(phase="compile_cache", dir=cache_dir, hits=counts["cache_hits"],
        misses=counts["cache_misses"], backend_compile_s=counts["build_s"])
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
