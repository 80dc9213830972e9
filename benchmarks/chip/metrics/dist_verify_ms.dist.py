"""Device time of the distributed verify per chip and per execution, in
ms: the ``verify_edges_compact`` programs' device time in the window,
summed over the chips, over the chips and over the executions (each job's
``verify_dispatches`` and the re-sends of ``compact_overflows``, from the
job counters). Every execution runs on each chip of the mesh."""

PROGRAM = "verify_edges_compact"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr or not tr["chips"]:
        return None
    t = sum(v for k, v in tr["programs"].items() if PROGRAM in k)
    n = c.get("joins", 0) * (c.get("verify_dispatches", 0)
                             + c.get("compact_overflows", 0))
    if t <= 0 or not n:
        return None
    return 1e3 * t / (tr["chips"] * n)
