"""Share of the traced window in which the device ran nothing while the host
was inside a step call that built its program: device-idle time within the
`gp.adam.step` host spans whose `compiles` is above 0, clipped to the
window, averaged over the chips, over the window. None where the program
writes no `gp.adam.step` span."""
from bench.harness.trace import _clip, _merged, union_length


def read(ctx):
    t = ctx["trace"]
    w0, w1 = t.window
    steps = [c for c in (_clip(e, w0, w1) for e in t.host
                         if e.name == "gp.adam.step") if c is not None]
    if not steps or not t.devices or t.window_s <= 0:
        return None
    building = _merged((c.start, c.end) for c in steps
                       if c.stats.get("compiles", 0) > 0)
    idle = 0.0
    for d in t.devices:
        for s, e in building:
            busy = union_length((max(o.start, s), min(o.end, e))
                                for o in t.ops[d] if o.end > s and o.start < e)
            idle += (e - s) - busy
    return 100.0 * idle * 1e-9 / len(t.devices) / t.window_s
