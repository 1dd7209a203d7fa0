"""Share of the traced window the host spent building the step program
(tracing, lowering, compiling or reading the persistent cache), as the
program counts it: the sum of `build_s` over the `gp.adam.step` host spans
in the window, each at most its part inside the window, over the window.
None where the program writes no such span."""
from bench.harness.trace import _clip


def read(ctx):
    t = ctx["trace"]
    w0, w1 = t.window
    steps = [c for c in (_clip(e, w0, w1) for e in t.host
                         if e.name == "gp.adam.step") if c is not None]
    if not steps or t.window_s <= 0:
        return None
    build = sum(min(c.stats.get("build_s", 0.0), c.dur * 1e-9) for c in steps)
    return 100.0 * build / t.window_s
