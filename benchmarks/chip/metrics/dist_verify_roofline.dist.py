"""Share of its roofline that the distributed verify reached, in %: the
least time a chip could take for its share of the join's work, the larger
of 2 * d * (distance computations) over the chips and peak FLOP/s, and the
bytes moved to the devices over the chips and peak HBM bytes/s, divided by
the per-chip mean of the ``verify_edges_compact`` programs' device time
in the window. The work is counted from the join's result, so it reads
the same whatever implements the verify."""

PROGRAM = "verify_edges_compact"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["chips"]:
        return None
    chips = tr["chips"]
    t = sum(v for k, v in tr["programs"].items() if PROGRAM in k) / chips
    if t <= 0:
        return None
    c = ctx["counters"]
    peak = ctx["peak"]()
    flops = 2.0 * c["dim"] * c["distance_computations"] * c["joins"]
    least = max(flops / (chips * peak["flops_per_s"]),
                c["h2d_bytes"] * c["joins"]
                / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * least / t
