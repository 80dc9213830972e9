"""Share of the serving waves' time spent waiting on bucket reads, in %:
the sum of ``query.io_wait`` spans (a synchronous read, or the
prefetcher's next slab, ``core/index.py``) over the sum of ``serve.wave``
spans (``serve/scheduler.py``). Warm-cache hits wait on nothing."""


def read(ctx):
    wave = sum(s["dur"] for s in ctx["spans"] if s["name"] == "serve.wave")
    if not wave:
        return None
    wait = sum(s["dur"] for s in ctx["spans"] if s["name"] == "query.io_wait")
    return 100.0 * wait / wave
