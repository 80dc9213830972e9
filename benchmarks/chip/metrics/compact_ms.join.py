"""Device time of ``compact_pairs`` per ``device_verify`` execution, in
ms: the ``device_verify`` programs' device time less the Pallas kernel's
ops (``pairwise_kernel_ms.join``), over the executions of
``device_verify`` in the window (each join's batches and
re-compactions, from the join counters). What remains besides the
compaction is the operand stack, under 0.1% of the program."""
import re

KERNEL = "pairwise_l2_threshold_batched"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr:
        return None
    module = sum(v for k, v in tr["programs"].items() if "device_verify" in k)
    kernel = sum(v for op, v in tr.get("device_ops", [])
                 if re.match(r"%?" + KERNEL + r"\b", op))
    n = c.get("joins", 0) * (c.get("device_batches", 0)
                             + c.get("device_compact_overflows", 0))
    if not n or module <= 0 or kernel <= 0:
        return None
    return 1e3 * (module - kernel) / n
