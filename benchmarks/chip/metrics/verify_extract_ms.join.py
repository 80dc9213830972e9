"""Mean ``verify.extract`` span of the window, in ms: the verify engine's
host time, per device batch, from the device's answer to the pairs in
host memory (harvest, D2H of rows, columns and d², sqrt and id mapping),
during which the device has no work queued (``compute/engine.py``)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "verify.extract"]
    return sum(d) / len(d) / 1e3 if d else None
