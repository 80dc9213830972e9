"""What the traffic kinds (``kinds/<kind>.py``) share.

A kind's ``drive(run)`` makes the cell's data from the seed, builds the
index with the configuration's settings, warms up every program shape its
window will use (set-up), runs the window, reads the device's peak memory,
frees the program's state, and only then compares what the window produced
with the float64 reference. It returns an ``Outcome``; ``run.py`` prints
it. Here are the pieces they have in common: the run's context (``Run``),
compile counting, the measured window, the data, the index build, the
window of whole jobs and the check of join results.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
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


def _result_key(pairs: np.ndarray, dists: np.ndarray) -> str:
    """sha256 of a join result in canonical form: pairs sorted by (low,
    high), with their distances in the same order."""
    p = np.asarray(pairs, np.int64).reshape(-1, 2)
    order = np.lexsort((p[:, 1], p[:, 0]))
    h = hashlib.sha256(p[order].tobytes())
    h.update(np.asarray(dists, np.float32)[order].tobytes())
    return h.hexdigest()


def _check_join(run: Run, x, eps, results) -> dict:
    """Every job's result judged against float64. Jobs with the same
    result (by ``_result_key``) share one comparison, which is exact: the
    numbers depend on nothing but the pairs and distances."""
    ref = reference.Reference(x, eps)
    rows = reference.sample_rows(x.shape[0],
                                 run.cell.traffic["check_rows"], run.seed)
    jt = reference.JoinTruth(ref, rows)
    limits = run.cell.config["limits"]
    numbers: dict = {}
    keys = []
    for p, d in results:
        key = _result_key(p, d)
        if key not in numbers:
            numbers[key] = reference.compare_join(jt, p, d)
        keys.append(key)
    run.log(f"check: {len(results)} jobs, {len(numbers)} distinct results")
    return reference.worst([reference.judge(numbers[k], limits)
                            for k in keys])


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
