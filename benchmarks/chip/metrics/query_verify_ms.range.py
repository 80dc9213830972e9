"""Mean ``query.verify`` span of the window, in ms: one query group's
device verify, from its first ``device_verify`` dispatch to its last
collect, the lanes' staging, the pair mapping and any overflow
re-dispatch included (``compute/engine.py``, ``DeviceQueryVerify``)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "query.verify"]
    return sum(d) / len(d) / 1e3 if d else None
