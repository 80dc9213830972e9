"""Share of its roofline that the serving path's device verify reached,
in %: the least time the chip could take for the window's query verify
work, the larger of 2 * d * ``query_distance_computations`` (live probers
times bucket rows, per lane) over peak FLOP/s and ``query_h2d_bytes``
(query rows and bucket slabs sent) over peak HBM bytes/s, divided by the
device time of every ``device_verify`` program in the window's trace.
The counters are the window's, from the program's ``PipelineStats``."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr or "query_distance_computations" not in c:
        return None
    t = sum(v for k, v in tr["programs"].items() if "device_verify" in k)
    if t <= 0:
        return None
    peak = ctx["peak"]()
    flops = 2.0 * c["dim"] * c["query_distance_computations"]
    least = max(flops / peak["flops_per_s"],
                c["query_h2d_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / t
