"""Statistics kernels against their roofline: the least time the statistics
pass could take, forward and reverse, max(required FLOPs / bf16 peak,
required bytes / HBM bandwidth) per step (bench/work), over the device time
of the operations that compute it (metrics/ops.py), per chip."""
from bench.metrics import ops


def read(ctx):
    t = ctx["trace"]
    if "work" not in ctx:
        return None
    busy = t.mean_op_s(ops.is_psi)
    if busy <= 0:
        return None
    w, p = ctx["work"], ctx["peaks"]
    least = max(w["psi_flops"] / p["flops_bf16"],
                w["psi_bytes"] / p["hbm_bytes_per_s"]) * ctx["steps"]
    return 100.0 * least / busy
