"""CPU tests of the ``dist_join`` traffic kind (``kinds/dist_join.py``):
the ``bigann-200k.dist-join`` cell rehearsed end to end on four virtual
devices, its check of ``correct`` (the control and faults planted in the
distributed verify come out not correct), and its per-layer readers on a
device trace recorded on four chips."""
from __future__ import annotations

import gzip
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

WORKLOAD = "bigann-200k.dist-join"
SEED = 2 ** 31 + 41
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "check"}

# Each case runs ``run.main`` at the rehearsal sizes in one process that
# sees four CPU devices (JAX in the test process already has one), with
# ``core/distributed.verify_edges_compact`` wrapped by a planted fault:
# - half_pairs: every dispatch returns half of each edge's pairs;
# - no_exchange: the chips other than the first verify their edges
#   against a window slab that never reached them (zeros);
# - altered: every returned d² is 1% too large.
_CASES = textwrap.dedent("""
    import contextlib, io, json, sys
    sys.path[:0] = [{here!r}, {src!r}]
    import jax.numpy as jnp
    import run
    import repro.core.distributed as dist

    def half_pairs(fn):
        def broken(*a):
            out = fn(*a)
            return (out[0] // 2,) + tuple(out[1:])
        return broken

    def no_exchange(fn):
        def broken(slab, edges, *rest):
            out = fn(slab, edges, *rest)
            unsent = fn(jnp.zeros_like(slab), edges, *rest)
            first = jnp.arange(edges.shape[0]) < edges.shape[0] // 4
            return tuple(
                jnp.where(first.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
                for a, b in zip(out, unsent))
        return broken

    def altered(fn):
        def broken(*a):
            out = fn(*a)
            return tuple(out[:3]) + (out[3] * jnp.float32(1.01),)
        return broken

    real = dist.verify_edges_compact
    for case, trace, fault in [("clean", 0, None), ("traced", 1, None),
                               ("half_pairs", 0, half_pairs),
                               ("no_exchange", 0, no_exchange),
                               ("altered", 0, altered)]:
        dist.verify_edges_compact = fault(real) if fault else real
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), \\
                contextlib.redirect_stderr(err):
            rc = run.main(["--workload", {workload!r}, "--seed",
                           str({seed}), "--seconds", "1", "--trace",
                           str(trace), "--rehearse"])
        lines = out.getvalue().strip().splitlines()
        print(json.dumps({{"case": case, "rc": rc,
                           "last": lines[-1] if lines else "",
                           "err": err.getvalue()}}), flush=True)
""")


@pytest.fixture(scope="module")
def rehearsals() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _CASES.format(here=HERE, src=os.path.join(ROOT, "src"),
                         workload=WORKLOAD, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = {}
    for line in p.stdout.splitlines():
        rec = json.loads(line)
        assert rec["rc"] == 0, rec["err"][-3000:]
        out[rec["case"]] = (json.loads(rec["last"]), rec["err"])
    return out


@pytest.mark.parametrize("case", ["clean", "traced"])
def test_rehearsal_on_four_devices(rehearsals, case):
    res, err = rehearsals[case]
    assert set(res) - {"breakdown"} == CONTRACT_KEYS
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    want = {"superstep_ms.dist", "io_wait_share.dist"} if (
        case == "traced") else {"setup_s", "join_vectors_per_s.dist"}
    assert want <= set(res["metrics"])
    assert "compiles in window: lowered=0 backend_compiled=0" in err
    assert re.search(r"warm-up: [1-9]\d* window and chunk shapes of the "
                     r"plan in \S+ on mesh \{'data': 4\}", err)
    jobs = [ln for ln in err.splitlines() if ln.startswith("job: ")]
    assert len(jobs) == res["attempted"] >= 1
    assert all("verify_dispatches=" in j and "h2d_bytes=" in j
               for j in jobs)
    assert f"check: {len(jobs)} jobs, 1 distinct results" in err
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault", ["half_pairs", "no_exchange", "altered"])
def test_distributed_faults_are_not_correct(rehearsals, fault):
    res, _ = rehearsals[fault]
    assert res["correct"] is False, res["check"]


def test_control_is_not_correct():
    """The reference in three bf16 passes, at the cell's rehearsal size,
    fails the cell's limits on every seed tried."""
    import cells
    import control
    cell = cells.load_cell(cells.load_benchmark(ROOT), WORKLOAD,
                           rehearse=True)
    for seed in (1, 2, 3):
        check = control.control_check(cell, seed)
        assert not all(c["ok"] for c in check.values()), (seed, check)


def _busy_by_grid(events, w0, w1, step):
    """Busy seconds by a fine time grid: an independent count."""
    t = np.arange(w0, w1, step) + step / 2
    on = np.zeros(t.size, bool)
    for _, s, d in events:
        on |= (t >= s) & (t < s + d)
    return on.sum() * step / 1e9


def _reads_slab_to_distances(op: str) -> bool:
    """The lanes' distance fusion: an output fusion that reads the
    (window, 2048, 128) slab and writes (lanes, 2048, 2048) float32."""
    result, _, rest = op.partition(" fusion(")
    return ("kind=kOutput" in rest and ",2048,2048]" in result
            and re.search(r"f32\[\d+,2048,128\]", rest) is not None)


def test_compact_reader_needs_the_distance_fusion():
    """Where the distance fusion is not among the trace's longest ops,
    ``compact_ms.dist`` reads nothing rather than the whole module."""
    import cells
    c = {"joins": 1, "verify_dispatches": 8, "compact_overflows": 0}
    tr = {"chips": 4, "window_s": 1.0, "busy_s": 0.5,
          "programs": {"jit_verify_edges_compact": 0.1},
          "device_ops": [["%while.20 = (s32[]) while(s32[] %t)", 0.09]]}
    read = cells.load_reader("compact_ms.dist")
    assert read({"spans": [], "trace": tr, "counters": c}) is None


def test_dist_readers_on_a_recorded_trace():
    """A four-chip TPU v5e trace of a ``bigann-200k.dist-join`` window,
    cut after its first six verify executions, with the program's
    superstep and prefetch spans and counters for that part (the traced
    job's work, scaled to six of its executions; the file's ``about``
    says how it was made): each ``.dist`` reader against a count made
    here from the trace's own events, and the roofline at most 100%."""
    import cells
    import devtrace
    with gzip.open(os.path.join(HERE, "testdata",
                                "trace_dist.json.gz"), "rt") as f:
        raw = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    red = devtrace.reduce(raw, chips=4)
    c = raw["counters"]
    ctx = {"spans": raw["spans"], "trace": red, "counters": c,
           "peak": lambda: peak}
    w0, w1 = devtrace.window_bounds(raw)
    devs = [p for p in raw["planes"] if devtrace._DEVICE_PLANE.match(
        p["name"])]
    assert len(devs) == red["chips"] == 4
    module_s, dist_s, busy_s, ops_s = [], [], [], {}
    step = (w1 - w0) / 100000
    for plane in devs:
        mods = [e for ln in plane["lines"] if ln["name"] == "XLA Modules"
                for e in ln["events"] if "verify_edges_compact" in e[0]]
        inside = [e for e in mods if e[1] < w1 and e[1] + e[2] > w0]
        assert len(inside) == raw["executions"]
        module_s.append(sum(min(s + d, w1) - max(s, w0)
                            for _, s, d in inside) / 1e9)
        ops = [e for ln in plane["lines"] if ln["name"] == "XLA Ops"
               for e in ln["events"]]
        busy_s.append(_busy_by_grid(ops, w0, w1, step))
        dist_s.append(sum(min(s + d, w1) - max(s, w0) for n, s, d in ops
                          if _reads_slab_to_distances(n)
                          and s < w1 and s + d > w0) / 1e9)
        for n, s, d in ops:
            if s < w1 and s + d > w0:
                ops_s[n] = ops_s.get(n, 0.0) + (min(s + d, w1)
                                                - max(s, w0)) / 1e9
    executions = c["joins"] * (c["verify_dispatches"]
                               + c["compact_overflows"])
    assert executions == raw["executions"]
    ms = cells.load_reader("dist_verify_ms.dist")(ctx)
    assert ms == pytest.approx(1e3 * np.mean(module_s) / executions,
                               rel=1e-9)
    roofline = cells.load_reader("dist_verify_roofline.dist")(ctx)
    least = max(2 * c["dim"] * c["distance_computations"] * c["joins"]
                / (4 * peak["flops_per_s"]),
                c["h2d_bytes"] * c["joins"] / (4 * peak["hbm_bytes_per_s"]))
    assert roofline == pytest.approx(100 * least / np.mean(module_s),
                                     rel=1e-9)
    assert 0 < roofline <= 100
    # The distances take 0.3% of the module here, so in six executions
    # their fusion falls out of the ten longest ops that the reduction
    # keeps, and the reader reads nothing; given every op, it reads the
    # module less that fusion.
    read_compact = cells.load_reader("compact_ms.dist")
    assert min(dist_s) > 0
    every_op = dict(red, device_ops=sorted(ops_s.items(),
                                           key=lambda kv: -kv[1]))
    compact = read_compact(dict(ctx, trace=every_op))
    assert compact == pytest.approx(
        1e3 * (np.mean(module_s) - np.mean(dist_s)) / executions, rel=1e-9)
    assert 0 < compact < ms
    assert read_compact(ctx) in (None, pytest.approx(compact, rel=1e-9))
    steps = [s["dur"] for s in raw["spans"] if s["name"] == "dist.superstep"]
    reads = [s["dur"] for s in raw["spans"] if s["name"] == "dist.prefetch"]
    assert steps and reads
    assert cells.load_reader("superstep_ms.dist")(ctx) == pytest.approx(
        np.mean(steps) / 1e3, rel=1e-12)
    io = cells.load_reader("io_wait_share.dist")(ctx)
    assert io == pytest.approx(100 * sum(reads) / sum(steps), rel=1e-12)
    assert 0 < io < 100
    idle = cells.load_reader("device_idle_share.dist")(ctx)
    assert idle == pytest.approx(
        100 * (1 - np.mean(busy_s) / ((w1 - w0) / 1e9)),
        abs=100 * 2 * step / (w1 - w0))
    assert 0 < idle < 100


def test_span_readers_of_the_superstep_loop():
    """``superstep_ms.dist`` is the mean ``dist.superstep`` span and
    ``io_wait_share.dist`` the share of those spans spent in
    ``dist.prefetch``; other spans do not count."""
    import cells
    spans = [{"name": "dist.superstep", "ph": "X", "ts": 0.0, "dur": 30e3},
             {"name": "dist.prefetch", "ph": "X", "ts": 5e3, "dur": 3e3},
             {"name": "dist.superstep", "ph": "X", "ts": 31e3, "dur": 20e3},
             {"name": "dist.prefetch", "ph": "X", "ts": 33e3, "dur": 2e3},
             {"name": "jit.compile", "ph": "X", "ts": 60e3, "dur": 9e3}]
    ctx = {"spans": spans, "trace": None, "counters": {}}
    assert cells.load_reader("superstep_ms.dist")(ctx) == pytest.approx(25)
    assert cells.load_reader("io_wait_share.dist")(ctx) == pytest.approx(10)
