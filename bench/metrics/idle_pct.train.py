"""Share of the traced training window in which the device ran nothing:
1 - (union of device-op intervals) / window, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
