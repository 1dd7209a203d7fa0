"""Compilations and persistent-cache reads of the timed `fit` call, as the
program counts them: the sum of `compiles` over the program's `gp.fit` host
spans that overlap the traced window. None where the program writes no such
span."""
from bench.harness.trace import _clip


def read(ctx):
    t = ctx["trace"]
    w0, w1 = t.window
    fits = [e for e in t.host
            if e.name == "gp.fit" and _clip(e, w0, w1) is not None]
    if not fits:
        return None
    return float(sum(e.stats.get("compiles", 0) for e in fits))
