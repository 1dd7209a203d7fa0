import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Dry-run for the PAPER'S OWN workload at production scale: one distributed
Bayesian GP-LVM Adam step, N datapoints sharded over the pod (the paper's §4
experiment x256 chips). This is perf-hillclimb cell C (EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.gp_dryrun --n 16777216 --m 128 \
        --backend fused --mesh pod
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import distributed  # noqa: E402
from repro.launch import hlo_cost, roofline  # noqa: E402
from repro.optim import AdamConfig, AdamState, adam_init, adam_update  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16_777_216)  # 65536 per chip (pod)
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--q", type=int, default=1)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "fused"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    args = ap.parse_args()

    n_chips = 256 if args.mesh == "pod" else 512
    mesh = jax.make_mesh((n_chips,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n_chips])
    N, M, Q, D = args.n, args.m, args.q, args.d

    params_a = {
        "kern": {"log_variance": jax.ShapeDtypeStruct((), jnp.float32),
                 "log_lengthscale": jax.ShapeDtypeStruct((Q,), jnp.float32)},
        "Z": jax.ShapeDtypeStruct((M, Q), jnp.float32),
        "log_beta": jax.ShapeDtypeStruct((), jnp.float32),
        "q_mu": jax.ShapeDtypeStruct((N, Q), jnp.float32),
        "q_logS": jax.ShapeDtypeStruct((N, Q), jnp.float32),
    }
    Y_a = jax.ShapeDtypeStruct((N, D), jnp.float32)
    adam = AdamConfig(lr=1e-2, clip_norm=None, weight_decay=0.0)
    opt_a = jax.eval_shape(lambda p: adam_init(p, adam), params_a)

    loss_fn = distributed.gplvm_loss_dist(mesh, backend=args.backend)

    def train_step(params, opt, Y):
        loss, grads = jax.value_and_grad(loss_fn)(params, Y)
        params, opt, gnorm = adam_update(grads, opt, params, adam)
        return params, opt, {"loss": loss, "gnorm": gnorm}

    local = P("data", None)
    pspec = {"kern": {"log_variance": P(), "log_lengthscale": P()}, "Z": P(),
             "log_beta": P(), "q_mu": local, "q_logS": local}
    shard = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                                      is_leaf=lambda x: isinstance(x, P))
    pshard = shard(pspec)
    oshard = AdamState(NamedSharding(mesh, P()), pshard, pshard)
    mshard = {"loss": NamedSharding(mesh, P()), "gnorm": NamedSharding(mesh, P())}

    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            train_step,
            in_shardings=(pshard, oshard, shard(local)),
            out_shardings=(pshard, oshard, mshard),
            donate_argnums=(0, 1),
        ).lower(params_a, opt_a, Y_a)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    cost = hlo_cost.analyze(compiled.as_text())
    terms = roofline.roofline_terms(cost.flops, cost.bytes, cost.coll_traffic)
    rec = {
        "arch": f"gplvm-N{N}-M{M}", "shape": "train_gp", "mesh": args.mesh,
        "kind": "train", "seq_len": 1, "global_batch": N, "status": "ok",
        "backend": args.backend, "n_chips": n_chips,
        "compile_s": round(t_compile, 2),
        "memory": {"peak_hbm_bytes_est": ma.argument_size_in_bytes
                   + ma.output_size_in_bytes + ma.temp_size_in_bytes
                   - ma.alias_size_in_bytes,
                   "argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes},
        "flops_per_chip": cost.flops,
        "bytes_per_chip": cost.bytes,
        "collectives": {"counts": cost.coll_counts,
                        "traffic_bytes_per_chip": cost.coll_traffic},
        "roofline": terms,
    }
    out = OUT_DIR / f"gplvm_{args.backend}_{args.mesh}.json"
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: rec[k] for k in ("backend", "compile_s", "flops_per_chip",
                                          "bytes_per_chip")}, indent=1))
    r = terms
    print(f"terms: compute {r['t_compute_s']*1e6:.1f} us | memory "
          f"{r['t_memory_s']*1e6:.1f} us | collective {r['t_collective_s']*1e6:.1f} us "
          f"| dominant {r['dominant']} | HBM {rec['memory']['peak_hbm_bytes_est']/2**30:.2f} GiB")


if __name__ == "__main__":
    main()
