"""Device idle share of a join window, in %: 1 - busy / window, busy the
union of the device's operations in the profiler trace, averaged over the
cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
