"""Device time of the pairwise distance kernel per ``device_verify``
execution, in ms: the Pallas ``pairwise_l2_threshold_batched`` ops
(``kernels/pairwise_l2.py``) among the window's ten longest device ops,
over the executions of ``device_verify`` in the window (each join's
batches and re-compactions, from the join counters)."""
import re

KERNEL = "pairwise_l2_threshold_batched"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr:
        return None
    # an op's own name leads its HLO text; operands may name the kernel too
    t = sum(v for op, v in tr.get("device_ops", [])
            if re.match(r"%?" + KERNEL + r"\b", op))
    n = c.get("joins", 0) * (c.get("device_batches", 0)
                             + c.get("device_compact_overflows", 0))
    return 1e3 * t / n if n and t > 0 else None
