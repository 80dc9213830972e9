"""Share of its roofline that the fused device verify program reached,
in %: the least time the chip could take for the join's work, the larger
of 2 * d * (distance computations) over peak FLOP/s and the bytes moved to
the device over peak HBM bytes/s, divided by the device time of every
``device_verify`` program in the trace (kernel and ``compact_pairs``).
The work is counted from the join's result, so it reads the same whatever
implements it."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    t = sum(v for k, v in tr["programs"].items() if "device_verify" in k)
    if t <= 0:
        return None
    c = ctx["counters"]
    peak = ctx["peak"]()
    flops = 2.0 * c["dim"] * c["num_distance_computations"] * c["joins"]
    least = max(flops / peak["flops_per_s"],
                c["h2d_bytes"] * c["joins"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / t
