"""Share of the distributed join's superstep loop spent reading buckets
from the store, in %: the sum of ``dist.prefetch`` spans (the next
window's missing buckets, read on the loop's thread while the verify
runs) over the sum of ``dist.superstep`` spans (``core/distributed.py``).
"""


def read(ctx):
    step = sum(s["dur"] for s in ctx["spans"]
               if s["name"] == "dist.superstep")
    if not step:
        return None
    read_ = sum(s["dur"] for s in ctx["spans"]
                if s["name"] == "dist.prefetch")
    return 100.0 * read_ / step
