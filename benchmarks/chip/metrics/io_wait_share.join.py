"""Share of the join's executor time spent waiting for bucket loads, in %:
the sum of ``io.wait`` spans over the sum of ``join.run`` spans
(``core/executor.py``, ``repro.io``)."""


def read(ctx):
    run = sum(s["dur"] for s in ctx["spans"] if s["name"] == "join.run")
    if not run:
        return None
    wait = sum(s["dur"] for s in ctx["spans"] if s["name"] == "io.wait")
    return 100.0 * wait / run
