"""Device time of the distributed verify's compaction per chip and per
execution, in ms: the ``verify_edges_compact`` programs' device time less
the fusion that computes the lanes' distances (the one fusion whose
result holds an f32 (lanes, cap, cap) slab), over the chips and over the
executions (each job's ``verify_dispatches`` and the re-sends of
``compact_overflows``). What remains is ``compact_pairs``: the kept
flags' running count, the search of each rank and the gathers. Nothing
is read where the distance fusion is not among the trace's ten longest
operations."""
import re

PROGRAM = "verify_edges_compact"
_DISTANCES = re.compile(r"^%?[\w.-]+ = [^=]*\bf32\[\d+,(\d+),\1\][^=]* "
                        r"fusion\(.*kind=kOutput")


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr or not tr["chips"]:
        return None
    module = sum(v for k, v in tr["programs"].items() if PROGRAM in k)
    dist = sum(v for op, v in tr.get("device_ops", [])
               if _DISTANCES.match(op))
    n = c.get("joins", 0) * (c.get("verify_dispatches", 0)
                             + c.get("compact_overflows", 0))
    if not n or module <= 0 or dist <= 0:
        return None
    return 1e3 * (module - dist) / (tr["chips"] * n)
