"""Mean ``verify.collect`` span of the window, in ms: the verify engine's
host time blocked on the device plus pair extraction, per device batch
(``compute/engine.py``)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "verify.collect"]
    return sum(d) / len(d) / 1e3 if d else None
