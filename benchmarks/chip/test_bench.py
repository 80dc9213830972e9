"""CPU tests of the chip benchmark: every cell rehearsed end to end at a
tiny size with the Pallas kernels interpreted, its refusals, its data
generator, its trace reduction, and its check of ``correct``: the control
(three bf16 passes) and faults planted in the program come out not
correct."""
from __future__ import annotations

import contextlib
import gzip
import io
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "check"}
SEED = 2 ** 31 + 17


def _run(argv) -> tuple[int, str, str]:
    import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(argv)
    return rc, out.getvalue(), err.getvalue()


def _rehearse(workload: str, trace: int = 0, seconds: float = 1.0):
    rc, out, err = _run(["--workload", workload, "--seed", str(SEED),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--rehearse"])
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("workload,trace", [
    ("bigann-50k.selfjoin", 0), ("bigann-50k.selfjoin", 1)])
def test_rehearsal_last_line(workload, trace):
    res, err = _rehearse(workload, trace)
    assert set(res) - {"breakdown"} == CONTRACT_KEYS
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["device"]["platform"] == "cpu"
    want = ({"verify_collect_ms.join", "io_wait_share.join"} if trace
            else {"setup_s", "join_vectors_per_s"})
    assert want <= set(res["metrics"])
    assert "compiles in window: lowered=0 backend_compiled=0" in err
    assert err.strip().splitlines()[-1].startswith("check ")
    assert "host_user_s=" in err and "involuntary_switches=" in err
    assert "device_wait_s=" in err and "device_batch_pairs_max=" in err
    assert re.search(r"^check: (\d+) jobs, 1 distinct results$", err, re.M)


def _per_layer_names() -> list:
    import cells
    return [m["name"] for m in cells.load_benchmark(ROOT)["per_layer"]]


@pytest.mark.parametrize("name", _per_layer_names())
def test_reader_with_nothing_to_read_returns_none(name):
    """A reader that finds no span, trace or program of its own returns
    nothing, and the metric is left out of the line (never 0)."""
    import cells
    read = cells.load_reader(name)
    empty_trace = {"chips": 0, "window_s": 0.0, "busy_s": 0.0,
                   "programs": {}}
    for trace in (None, empty_trace):
        ctx = {"spans": [{"name": "unrelated", "dur": 5.0}], "trace": trace,
               "counters": {}, "peak": lambda: {}}
        assert read(ctx) is None


def test_refuses_without_a_tpu():
    rc, out, err = _run(["--workload", "bigann-50k.selfjoin", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc == 1 and out == ""
    assert "no TPU" in err


def test_load_driver_finds_a_kind_by_name():
    import cells
    drive = cells.load_driver("join")
    assert drive.__module__ == "chipbench_kind_join"
    assert os.path.samefile(drive.__code__.co_filename,
                            os.path.join(HERE, "kinds", "join.py"))


def test_missing_files_fail_by_name(monkeypatch):
    import cells
    bench = cells.load_benchmark(ROOT)
    with pytest.raises(cells.CellError, match="no-such-cell"):
        cells.load_cell(bench, "no-such-cell")
    broken = json.loads(json.dumps(bench))
    broken["configs"][0]["file"] = "benchmarks/chip/configs/gone.json"
    name = broken["workloads"][0]["name"]
    with pytest.raises(cells.CellError, match="gone.json"):
        cells.load_cell(broken, name)
    broken = json.loads(json.dumps(bench))
    broken["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(cells.CellError, match="no-such-mix"):
        cells.load_cell(broken, name)
    broken = json.loads(json.dumps(bench))
    broken["per_layer"].append({"name": "no_such_metric.join",
                                "workloads": [name], "moves": "setup_s"})
    with pytest.raises(cells.CellError, match="no_such_metric.join"):
        cells.load_cell(broken, name)
    with pytest.raises(cells.CellError, match="no_such_kind"):
        cells.load_driver("no_such_kind")
    # a traffic mix whose kind has no file: the run exits 2, naming it
    real = cells.load_cell

    def with_kind(*args, **kw):
        cell = real(*args, **kw)
        cell.traffic = dict(cell.traffic, kind="no_such_kind")
        return cell

    monkeypatch.setattr(cells, "load_cell", with_kind)
    rc, out, err = _run(["--workload", name, "--seed", "1", "--seconds",
                         "1", "--trace", "0", "--rehearse"])
    assert rc == 2 and out == ""
    assert os.path.join("kinds", "no_such_kind.py") in err


def test_bare_checkout_prints_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files (no
    program) exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "bigann-50k.selfjoin", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_generator_calibrates_like_the_programs():
    """The benchmark's generator draws other rows than the program's
    ``clustered_vectors`` (module docstring of datagen.py), from the same
    mixture: both calibrate to the same ε within 5%."""
    import datagen
    from repro.data import clustered_vectors
    ours = datagen.clustered(6000, 64, structure_seed=3, seed=4)
    theirs = clustered_vectors(6000, 64, seed=3)
    e1 = datagen.epsilon_for_avg_neighbors(ours, 20)
    e2 = datagen.epsilon_for_avg_neighbors(theirs, 20)
    assert abs(e1 / e2 - 1) < 0.05, (e1, e2)
    assert np.array_equal(ours, datagen.clustered(6000, 64, structure_seed=3,
                                                  seed=4))


def test_seeds_rotate_one_dataset():
    """Two seeds give other coordinates and the same distances, so the
    same buckets and the same work."""
    import datagen

    def d2(x):
        x = x[:300].astype(np.float64)
        sq = np.sum(x * x, axis=1)
        return sq[:, None] - 2.0 * x @ x.T + sq[None, :]

    a = datagen.clustered(2000, 64, structure_seed=3, seed=2 ** 40 + 5)
    b = datagen.clustered(2000, 64, structure_seed=3, seed=6)
    assert np.abs(a - b).max() > 0.1
    np.testing.assert_allclose(d2(a), d2(b), rtol=0, atol=1e-4)


def _grid_busy(events, w0, w1, step):
    """Busy seconds by a fine time grid: an independent count."""
    t = np.arange(w0, w1, step) + step / 2
    on = np.zeros(t.size, bool)
    for _, s, d in events:
        on |= (t >= s) & (t < s + d)
    return on.sum() * step / 1e9


def test_trace_reduction_on_a_recorded_trace():
    import devtrace
    with gzip.open(os.path.join(HERE, "testdata", "trace_small.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    red = devtrace.reduce(raw, chips=1)
    w0, w1 = devtrace.window_bounds(raw)
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    dev = [p for p in raw["planes"] if p["name"] == "/device:TPU:0"][0]
    ops = [e for ln in dev["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    step = (w1 - w0) / 200000
    assert red["busy_s"] == pytest.approx(_grid_busy(ops, w0, w1, step),
                                          rel=1e-3, abs=2 * step / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    assert red["device_ops"][0][1] >= red["device_ops"][-1][1]
    mods = [e for ln in dev["lines"] if ln["name"] == "XLA Modules"
            for e in ln["events"]]
    total = sum(min(s + d, w1) - max(s, w0) for _, s, d in mods
                if s + d > w0 and s < w1) / 1e9
    assert sum(red["programs"].values()) == pytest.approx(total)


@pytest.mark.parametrize("workload", ["bigann-50k.selfjoin"])
def test_control_is_not_correct(workload):
    """The reference in three bf16 passes, at the cell's rehearsal size,
    fails the cell's limits on every seed tried."""
    import cells
    import control
    cell = cells.load_cell(cells.load_benchmark(ROOT), workload,
                           rehearse=True)
    for seed in (1, 2, 3):
        check = control.control_check(cell, seed)
        assert not all(c["ok"] for c in check.values()), (seed, check)


@pytest.mark.parametrize("faulty_job", [None, 1])
def test_check_judges_each_distinct_result_once(monkeypatch, faulty_job):
    """Jobs with the same result share one float64 comparison (the same
    pairs in another order are the same result), and every job is still
    judged: a fault in the second of three jobs makes the run not
    correct."""
    import types

    import cells
    import datagen
    import drivers
    import reference
    cell = cells.load_cell(cells.load_benchmark(ROOT), "bigann-50k.selfjoin",
                           rehearse=True)
    x = datagen.make(cell.config, SEED)
    eps = datagen.epsilon_for_avg_neighbors(x, cell.config["avg_neighbors"])
    ref = reference.Reference(x, eps)
    pairs = np.concatenate([
        np.stack([np.full(t.size, r), t], 1)
        for r, t in enumerate(ref.neighbors(x)) if (t > r).any()])
    pairs = pairs[pairs[:, 1] > pairs[:, 0]]
    dists = ref.pair_dist(pairs).astype(np.float32)
    shuffled = np.random.default_rng(0).permutation(pairs.shape[0])
    results = [(pairs, dists), (pairs, dists),
               (pairs[shuffled], dists[shuffled])]
    if faulty_job is not None:
        results[faulty_job] = (pairs[::2], dists[::2])
    calls = []
    compare = reference.compare_join
    monkeypatch.setattr(reference, "compare_join",
                        lambda *a: calls.append(1) or compare(*a))
    logs = []
    run = types.SimpleNamespace(cell=cell, seed=SEED, log=logs.append)
    check = drivers._check_join(run, x, eps, results)
    distinct = 1 if faulty_job is None else 2
    assert len(calls) == distinct
    assert logs == [f"check: 3 jobs, {distinct} distinct results"]
    assert all(c["ok"] for c in check.values()) == (faulty_job is None), \
        check


def _faulted(monkeypatch, target, name, wrap, workload):
    import importlib
    mod = importlib.import_module(target)
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    res, _ = _rehearse(workload)
    return res


def _drop_half_lanes(fn):
    def broken(na, nb, intra, *slabs, **kw):
        na = np.array(na)
        na[1::2] = 0
        return fn(na, nb, intra, *slabs, **kw)
    return broken


def _alter_distances(fn):
    def broken(*args, **kw):
        out = list(fn(*args, **kw))
        out[3] = out[3] * 1.01
        return tuple(out)
    return broken


@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_join_faults_are_not_correct(monkeypatch, fault):
    wrap = _drop_half_lanes if fault == "half_batch" else _alter_distances
    res = _faulted(monkeypatch, "repro.compute.engine", "device_verify",
                   wrap, "bigann-50k.selfjoin")
    assert res["correct"] is False, res["check"]
