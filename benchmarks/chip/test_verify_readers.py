"""CPU tests of the verify engine's and the device verify program's
per-layer readers: ``pairwise_kernel_ms.join`` and ``compact_ms.join`` on
a chip-recorded trace, checked against the JAX name stacks its device ops
carry, and ``verify_extract_ms.join`` in a traced rehearsal."""
from __future__ import annotations

import contextlib
import gzip
import io
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _scoped_union(ops, scope: str) -> float:
    """Seconds covered by the ops whose name stack (the fourth element
    of a recorded op) holds ``scope``."""
    import devtrace
    iv = np.asarray([[e[1], e[1] + e[2]] for e in ops
                     if len(e) > 3 and f"/{scope}/" in e[3]], np.float64)
    u = devtrace._union(iv.reshape(-1, 2))
    return float(np.sum(u[:, 1] - u[:, 0])) / 1e9


def test_device_readers_on_a_recorded_trace():
    """A TPU v5e trace of four ``device_verify`` batches (4 lanes, 2048
    rows a bucket): the kernel reader gives the ``verify.kernel`` scope's
    time a batch, and the compaction reader the rest of the program,
    which holds every ``compact.*`` scope."""
    import cells
    import devtrace
    with gzip.open(os.path.join(HERE, "testdata", "trace_scopes.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    dev = [p for p in raw["planes"] if p["name"] == "/device:TPU:0"][0]
    ops = [e for ln in dev["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    ctx = {"spans": [], "trace": devtrace.reduce(raw, chips=1),
           "counters": {"joins": 1, "device_batches": 3,
                        "device_compact_overflows": 1},
           "peak": lambda: {}}
    kernel = cells.load_reader("pairwise_kernel_ms.join")(ctx)
    compact = cells.load_reader("compact_ms.join")(ctx)
    assert kernel == pytest.approx(
        1e3 * _scoped_union(ops, "verify.kernel") / 4, rel=1e-9)
    module_ms = 1e3 * ctx["trace"]["programs"]["jit_device_verify"] / 4
    assert kernel + compact == pytest.approx(module_ms, rel=1e-9)
    scoped = sum(_scoped_union(ops, s) for s in
                 ("compact.count", "compact.search", "compact.gather"))
    assert 1e3 * scoped / 4 <= compact
    assert compact - 1e3 * scoped / 4 < 0.2 * module_ms


def test_traced_rehearsal_reports_verify_extract():
    """The verify engine's extraction is read from its own spans, nested
    in the collect; the device readers find no TPU trace on the CPU."""
    import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", "bigann-50k.selfjoin",
                       "--seed", str(2 ** 31 + 29), "--seconds", "1",
                       "--trace", "1", "--rehearse"])
    assert rc == 0, err.getvalue()[-3000:]
    m = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
    assert 0 < m["verify_extract_ms.join"]["value"] \
        <= m["verify_collect_ms.join"]["value"]
    assert "compact_ms.join" not in m
    assert "pairwise_kernel_ms.join" not in m
