"""Share of the step's device time spent in the statistics pass's ops."""
from bench.metrics import ops


def read(ctx):
    t = ctx["trace"]
    busy = t.mean_busy_s()
    psi = t.mean_op_s(ops.is_psi)
    if busy <= 0 or psi <= 0:
        return None
    return 100.0 * psi / busy
