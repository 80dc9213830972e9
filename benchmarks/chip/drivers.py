"""The kinds of traffic, each driven through the program's normal path:
whole self-joins (``join``).

Each driver makes the cell's data from the seed, builds the index with the
configuration's settings, warms up every program shape its window will
use (set-up), runs the window, reads the device's peak memory, frees the
program's state, and only then compares what the window produced with the
float64 reference. It returns an ``Outcome``; ``run.py`` prints it.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import resource
import time

import numpy as np

import datagen
import reference
from cells import Cell

JAXPR_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Outcome:
    setup_s: float
    metrics: dict                 # end-to-end metric name -> value
    attempted: int
    failed: int
    check: dict                   # judged numbers (reference.judge)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    trace: dict | None = None     # devtrace.reduce of the window
    memory_peak_bytes: int = 0


class CompileCounter:
    """Programs lowered and compiled by JAX, and persistent-cache hits,
    counted per phase of the run (``phase`` names the current one)."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: dict = collections.defaultdict(collections.Counter)
        self.names: dict = collections.defaultdict(collections.Counter)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == JAXPR_TO_MLIR:
            self.counts[self.phase]["lowered"] += 1
            self.names[self.phase][kw.get("fun_name", "?")] += 1
        elif event == BACKEND_COMPILE:
            self.counts[self.phase]["compiled"] += 1

    def _event(self, event, **kw):
        if event == CACHE_HITS:
            self.counts[self.phase]["cache_hits"] += 1

    def line(self, phase: str) -> str:
        c = self.counts[phase]
        names = ", ".join(f"{n} x{k}" for n, k in
                          self.names[phase].most_common(8))
        return (f"compiles in {phase}: lowered={c['lowered']} "
                f"backend_compiled={c['compiled']} "
                f"persistent_cache_hits={c['cache_hits']}"
                + (f" ({names})" if names else ""))


@dataclasses.dataclass
class Run:
    """What a driver gets from ``run.py``."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    workdir: str
    devices: list
    compiles: CompileCounter
    log: object                   # log(str) to standard error


# ---------------------------------------------------------------------------
# shared set-up
# ---------------------------------------------------------------------------
def _array_store(x: np.ndarray):
    """The input dataset as a ``FlatVectorStore`` over memory: the build
    scans it as it would the file, and the run writes no copy of it."""
    from repro.store.io_stats import IOStats
    from repro.store.vector_store import FlatVectorStore
    store = FlatVectorStore.__new__(FlatVectorStore)
    store.path = None
    store.num_vectors, store.dim = x.shape
    store.dtype = x.dtype
    store.row_bytes = store.dim * store.dtype.itemsize
    store.stats = IOStats()
    store._mm = x
    return store


def _data(run: Run):
    cfg = run.cell.config
    x = datagen.make(cfg, run.seed)
    eps = cfg.get("epsilon")
    if eps is None:  # rehearsal sizes calibrate at run time
        eps = datagen.epsilon_for_avg_neighbors(x, cfg["avg_neighbors"])
    return x, float(eps)


def _join_config(cfg: dict, x: np.ndarray, eps: float):
    from repro.core import JoinConfig
    ix = cfg["index"]
    return JoinConfig(
        epsilon=eps, recall_target=ix["recall_target"],
        memory_budget_bytes=int(x.nbytes * ix["memory_budget_fraction"]),
        pad_align=ix["pad_align"], use_pallas=ix["use_pallas"],
        compute_mode=ix["compute_mode"], io_mode=ix["io_mode"],
        plan_mode=ix["plan_mode"], verify_batch=ix["verify_batch"])


def _build(run: Run, x: np.ndarray, eps: float):
    from repro.core.index import DiskJoinIndex
    jc = _join_config(run.cell.config, x, eps)
    t0 = time.perf_counter()
    index = DiskJoinIndex.build(_array_store(x), jc,
                                os.path.join(run.workdir, "index"))
    run.log(f"build: {time.perf_counter() - t0:.3f}s buckets="
            f"{index.num_buckets} capacity={index.bucket_capacity} "
            f"budget_bytes={jc.memory_budget_bytes} eps={eps!r}")
    return index, jc


def _pow2_upto(n: int) -> list[int]:
    from repro.compute import next_pow2
    out, p = [], 1
    while p <= next_pow2(n):
        out.append(p)
        p *= 2
    return out


def _k_caps(cap: int, doublings: int) -> list[int]:
    """The device compaction capacity a join starts at, and the raises
    an overflow reaches (each a program of its own)."""
    from repro.compute import PAIR_CAP_INIT, next_pow2
    base = min(next_pow2(max(PAIR_CAP_INIT, 8 * cap)), cap * cap)
    return sorted({min(base << i, cap * cap) for i in range(doublings + 1)})


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class _Window:
    """The measured window: compiles counted apart, and with ``--trace 1``
    the program's spans and the device trace recorded."""

    def __init__(self, run: Run):
        self.run = run
        self.events: list = []
        self.trace = None

    def __enter__(self):
        import contextlib

        from repro.obs import trace_session

        import devtrace
        self.run.compiles.phase = "window"
        self._stack = contextlib.ExitStack()
        if self.run.trace:
            self.tracer = self._stack.enter_context(trace_session(
                ring_capacity=1 << 20))
            self.logdir = os.path.join(self.run.workdir, "profile")
            self._stack.enter_context(devtrace.traced(self.logdir,
                                                      self.tracer))
        return self

    def __exit__(self, *exc):
        self._stack.close()
        self.run.compiles.phase = "after"
        if self.run.trace and exc[0] is None:
            import devtrace
            self.events = self.tracer.events()
            raw = devtrace.load(self.logdir)
            spans = devtrace.spans_on_profiler_clock(self.events, raw)
            self.trace = devtrace.reduce(raw, self.run.cell.chips, spans)
        return False


# ---------------------------------------------------------------------------
# whole self-joins
# ---------------------------------------------------------------------------
def _warm_join(run: Run, index, jc) -> None:
    """Every program a self-join of this index runs: the bucket graph's
    centre search, and the fused device verify at each power-of-two lane
    count up to ``verify_batch`` and each compaction capacity, with the
    slice that harvests a lane."""
    import jax

    from repro.compute.engine import device_verify
    from repro.core import build_bucket_graph
    build_bucket_graph(index.meta, jc)
    cap, dim = index.bucket_capacity, index.dim
    slab = np.zeros((cap, dim), np.float32)
    for lanes in _pow2_upto(jc.verify_batch):
        zero = np.zeros(lanes, np.int32)
        intra = np.zeros(lanes, bool)
        for k_cap in _k_caps(cap, run.cell.traffic["warm_k_cap_doublings"]):
            out = device_verify(zero, zero, intra, *([slab] * (2 * lanes)),
                                eps=float(jc.epsilon), k_cap=k_cap,
                                use_pallas=jc.use_pallas)
            jax.block_until_ready(out)
            np.asarray(out[4][0])


def _check_join(run: Run, x, eps, results) -> dict:
    ref = reference.Reference(x, eps)
    rows = reference.sample_rows(x.shape[0],
                                 run.cell.traffic["check_rows"], run.seed)
    jt = reference.JoinTruth(ref, rows)
    limits = run.cell.config["limits"]
    return reference.worst([
        reference.judge(reference.compare_join(jt, p, d), limits)
        for p, d in results])


def _host_usage() -> np.ndarray:
    """This process's CPU seconds (user, system), involuntary context
    switches and major page faults: system calls, read around each job,
    so that a slow job shows whether the host held it back."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return np.array([ru.ru_utime, ru.ru_stime, ru.ru_nivcsw, ru.ru_majflt])


def _join_window(run: Run, once) -> tuple[list, list, list]:
    """Whole jobs back to back: at least one; another only while the
    elapsed time plus the last job's duration stays within the window."""
    outs, durations, usage = [], [], []
    t_start = time.perf_counter()
    while True:
        u0, t0 = _host_usage(), time.perf_counter()
        outs.append(once())
        durations.append(time.perf_counter() - t0)
        usage.append(_host_usage() - u0)
        if time.perf_counter() - t_start + durations[-1] > run.seconds:
            return outs, durations, usage


def drive_join(run: Run) -> Outcome:
    import jax
    t0 = time.perf_counter()
    x, eps = _data(run)
    run.log(f"data: {x.shape[0]} x {x.shape[1]} float32 in "
            f"{time.perf_counter() - t0:.3f}s")
    index, jc = _build(run, x, eps)
    _warm_join(run, index, jc)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t0
    with _Window(run) as win:
        results, durations, usage = _join_window(run, index.self_join)
    peak = _memory_peak(run.devices[:run.cell.chips])
    index.close()
    last = results[-1]
    pipe = last.io_stats.get("pipeline") or {}
    counters = {"dim": index.dim, "joins": len(results),
                "num_distance_computations": last.num_distance_computations,
                "h2d_bytes": pipe.get("h2d_bytes", 0),
                "device_batches": pipe.get("device_batches", 0),
                "device_compact_overflows":
                    pipe.get("device_compact_overflows", 0)}
    for r, dt, u in zip(results, durations, usage):
        p = r.io_stats.get("pipeline") or {}
        t = r.timings
        run.log(f"join: {dt:.3f}s pairs={r.pairs.shape[0]} "
                f"distance_computations={r.num_distance_computations} "
                f"device_batches={p.get('device_batches', 0)} "
                f"compact_overflows={p.get('device_compact_overflows', 0)} "
                f"bucket_loads={r.bucket_loads} "
                f"orchestration_s={t['orchestration']:.3f} "
                f"execute_s={t['execute']:.3f} "
                f"engine_s={t['compute']:.3f} io_wait_s={t['io_wait']:.3f} "
                f"host_user_s={u[0]:.3f} host_sys_s={u[1]:.3f} "
                f"involuntary_switches={int(u[2])} "
                f"major_faults={int(u[3])}")
    check = _check_join(run, x, eps,
                        [(r.pairs, r.distances) for r in results])
    rows = x.shape[0] * len(results)
    return Outcome(
        setup_s=setup_s,
        metrics={"join_vectors_per_s": rows / sum(durations)},
        attempted=len(results), failed=0, check=check, counters=counters,
        spans=win.events, trace=win.trace, memory_peak_bytes=peak)


DRIVERS = {"join": drive_join}
