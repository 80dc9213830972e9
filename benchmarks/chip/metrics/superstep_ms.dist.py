"""Mean ``dist.superstep`` span of the window, in ms: one window of the
distributed join's plan, from fetching its buckets through the sharded
verify to its pairs on the host (``core/distributed.py``)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "dist.superstep"]
    return sum(d) / len(d) / 1e3 if d else None
