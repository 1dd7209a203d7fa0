"""Whole-step model FLOP/s utilization: the least FLOPs one training step
needs (bench/work), times the steps of the window, over the traced window,
the chips and the chip's bf16 peak. Transcendentals are not FLOPs."""


def read(ctx):
    t = ctx["trace"]
    if "work" not in ctx or t.window_s <= 0:
        return None
    flops = ctx["work"]["flops"] * ctx["steps"]  # per chip
    return 100.0 * flops / t.window_s / ctx["peaks"]["flops_bf16"]
