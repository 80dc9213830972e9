"""CPU tests of the ε-range serving cell, ``ssnpp-1m.range-zipf``: its
rehearsal end to end at the configuration's tiny sizes with the Pallas
kernel interpreted, faults planted in the program and the control
(three bf16 passes) coming out not correct, and its per-layer readers on
a trace recorded on the chip."""
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

CELL = "ssnpp-1m.range-zipf"
SEED = 2 ** 31 + 41
READERS = ["query_verify_ms.range", "query_io_wait_share.range",
           "query_verify_roofline.range", "device_idle_share.range"]


def _rehearse(trace: int = 0):
    import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "1", "--trace", str(trace),
                       "--rehearse"])
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(trace):
    res, err = _rehearse(trace)
    assert set(res) - {"breakdown"} == {"correct", "attempted", "failed",
                                        "metrics", "device", "check"}
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = ({"query_verify_ms.range", "query_io_wait_share.range"} if trace
            else {"setup_s", "join_vectors_per_s"})
    assert want <= set(res["metrics"])
    # the device readers find no TPU trace on the CPU
    assert "query_verify_roofline.range" not in res["metrics"]
    assert "compiles in window: lowered=0 backend_compiled=0" in err
    assert "latency p50" in err and "query_device_batches=" in err


def _drop_nearest_bucket(fn):
    def broken(self, Q, *args, **kw):
        return [p[1:] for p in fn(self, Q, *args, **kw)]
    return broken


def _distance_past_band(fn):
    def broken(self):
        q, ids, d = fn(self)
        if d.size:
            d = d.copy()
            d[0] = self.eps * 1.01
        return q, ids, d
    return broken


def _id_twice(fn):
    def broken(self):
        q, ids, d = fn(self)
        return (np.r_[q, q[:1]], np.r_[ids, ids[:1]], np.r_[d, d[:1]])
    return broken


@pytest.mark.parametrize("fault", ["dropped_neighbours", "past_band",
                                   "id_twice"])
def test_planted_faults_are_not_correct(monkeypatch, fault):
    """A single dropped neighbour lies within the recall limit by design
    (Eq. 3 pruning may miss a tenth), so the recall fault drops each
    query's nearest candidate bucket, its own row and most true
    neighbours with it. One reported distance past ε's band, or one id
    twice in an answer, are single-pair faults."""
    from repro.compute.engine import DeviceQueryVerify
    from repro.core.index import DiskJoinIndex
    target, name, wrap, number = {
        "dropped_neighbours": (DiskJoinIndex, "plan_probes",
                               _drop_nearest_bucket, "recall"),
        "past_band": (DeviceQueryVerify, "finish", _distance_past_band,
                      "max_d2_err"),
        "id_twice": (DeviceQueryVerify, "finish", _id_twice, "malformed"),
    }[fault]
    monkeypatch.setattr(target, name, wrap(getattr(target, name)))
    res, _ = _rehearse()
    assert res["correct"] is False
    assert res["check"][number]["ok"] is False, res["check"]


def test_control_is_not_correct():
    """The reference in three bf16 passes, at the rehearsal size, fails
    the cell's limits on every seed tried."""
    import cells
    import range_check
    cell = cells.load_cell(cells.load_benchmark(ROOT), CELL, rehearse=True)
    for seed in (1, 2, 3):
        check = range_check.control_check(cell, seed)
        assert not check["max_d2_err"]["ok"], (seed, check)


def test_readers_on_a_recorded_trace():
    """A TPU v5e trace of a one-second window of the cell: each reader
    gives a number, the one the run printed, and nothing on an empty
    context. The file keeps the union of the device's ops with gaps
    under 100 ns closed (about 40,000 ops would not fit 100 KB), which
    moves the busy time by under 0.1%."""
    import cells
    import devtrace
    with gzip.open(os.path.join(HERE, "testdata", "trace_range.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    ctx = {"spans": raw["spans"], "trace": devtrace.reduce(raw, chips=1),
           "counters": raw["counters"], "peak": lambda: peak}
    empty = {"spans": [], "trace": None, "counters": {},
             "peak": lambda: peak}
    got = {}
    for name in READERS:
        read = cells.load_reader(name)
        got[name] = read(ctx)
        assert got[name] is not None, name
        assert got[name] == pytest.approx(raw["metrics"][name]["value"],
                                          rel=1e-3), name
        assert read(empty) is None, name
    verify = [s["dur"] for s in raw["spans"] if s["name"] == "query.verify"]
    assert got["query_verify_ms.range"] == pytest.approx(
        np.mean(verify) / 1e3)
    assert 0 < got["query_verify_roofline.range"] <= 100
    assert 0 <= got["query_io_wait_share.range"] <= 100
    assert 0 < got["device_idle_share.range"] < 100
