"""Traffic kind ``range``: ε-range lookups through ``QueryScheduler`` on
one built index, as a closed loop.

Set-up makes the data from the seed, builds the index, and calls the
program's serving warm-up (``DiskJoinIndex.warm_queries``) for the mix's
wave size. The window keeps ``outstanding`` requests in the scheduler:
each query is an indexed row's own vector, the row drawn Zipf(θ) over all
rows with the hot rows' order drawn from the seed, one ε for all and no
deadline; a new request goes in as each one completes, until the window
closes, and the requests still out then run to their end. The rate is
answered requests over the time from the first submit to the last answer;
``failed`` counts requests that raised. The check judges each distinct
answer once against float64 (``range_check.py``).
"""
from __future__ import annotations

import queue
import time

import numpy as np

import drivers
import range_check

_CHUNK = 1 << 16


class ZipfRows:
    """Rows drawn Zipf(θ) over ``n`` rows: rank k with weight k^-θ, and
    which row holds each rank drawn from the seed."""

    def __init__(self, n: int, theta: float, seed: int):
        self.rng = np.random.default_rng(seed)
        self.rows = self.rng.permutation(n)
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
        self.cdf = np.cumsum(w) / w.sum()
        self._buf = np.zeros(0, np.int64)

    def next(self) -> int:
        if not self._buf.size:
            ranks = np.searchsorted(self.cdf, self.rng.random(_CHUNK))
            self._buf = self.rows[np.minimum(ranks, self.rows.size - 1)]
        row, self._buf = int(self._buf[0]), self._buf[1:]
        return row


def _closed_loop(run: drivers.Run, sched, x: np.ndarray, draw: ZipfRows,
                 outstanding: int):
    """The window: (distinct answers, latencies, failed, submitted,
    seconds from the first submit to the last answer)."""
    done: queue.SimpleQueue = queue.SimpleQueue()
    answers: dict = {}
    lat: list = []
    failed = submitted = 0

    def submit() -> None:
        nonlocal submitted
        row = draw.next()
        fut = sched.submit(x[row])
        fut.add_done_callback(lambda f, row=row: done.put((row, f)))
        submitted += 1

    t0 = time.perf_counter()
    end = t0 + run.seconds
    for _ in range(outstanding):
        submit()
    pending, t_last = outstanding, t0
    while pending:
        row, fut = done.get()
        pending -= 1
        t_last = time.perf_counter()
        if fut.exception() is not None:
            failed += 1
        else:
            ids, d = fut.result()
            key = (row, ids.tobytes(), d.tobytes())
            answers.setdefault(key, (row, ids, d))
            lat.append(fut.latency_s)
        if t_last < end:
            submit()
            pending += 1
    return list(answers.values()), lat, failed, submitted, t_last - t0


def drive(run: drivers.Run) -> drivers.Outcome:
    import jax

    from repro.core.index import DiskJoinIndex
    from repro.serve import QueryScheduler
    if not hasattr(DiskJoinIndex, "warm_queries"):
        raise RuntimeError("this program has no DiskJoinIndex.warm_queries: "
                           "it cannot warm its serving programs before the "
                           "window")
    tr = run.cell.traffic
    t0 = time.perf_counter()
    x, eps = drivers._data(run)
    run.log(f"data: {x.shape[0]} x {x.shape[1]} float32 in "
            f"{time.perf_counter() - t0:.3f}s")
    index, jc = drivers._build(run, x, eps)
    t1 = time.perf_counter()
    index.warm_queries(tr["wave_size"],
                       k_cap_doublings=tr["warm_k_cap_doublings"])
    sched = QueryScheduler(index, epsilon=eps, wave_size=tr["wave_size"],
                           max_wait_s=tr["max_wait_s"],
                           max_queue=tr["outstanding"])
    draw = ZipfRows(x.shape[0], tr["zipf_theta"], run.seed)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t0
    run.log(f"warm-up: {time.perf_counter() - t1:.3f}s")
    base = index.pipeline_snapshot()
    with drivers._Window(run) as win:
        answers, lat, failed, submitted, seconds = _closed_loop(
            run, sched, x, draw, tr["outstanding"])
    p = index.pipeline_snapshot()
    peak = drivers._memory_peak(run.devices[:run.cell.chips])
    waves = sched.snapshot()["waves"]
    sched.close()
    index.close()
    delta = {k: p[k] - base[k] for k in (
        "queries", "waves", "query_reads", "query_warm_hits",
        "query_device_batches", "query_compact_overflows",
        "query_distance_computations", "query_h2d_bytes",
        "shared_probe_reads", "reads_saved_by_sharing")}
    p50, p99 = (np.percentile(lat, [50, 99]) * 1e3 if lat
                else (float("nan"),) * 2)
    run.log(f"range: {len(lat)} answered, {failed} failed, {submitted} "
            f"submitted in {seconds:.3f}s, {waves} waves, "
            f"{len(answers)} distinct answers, latency p50 {p50:.3f} ms "
            f"p99 {p99:.3f} ms (not judged); "
            + " ".join(f"{k}={v}" for k, v in delta.items()))
    judged, numbers = range_check.check(x, eps, answers,
                                        run.cell.config["limits"],
                                        tr["check_rows"], run.seed)
    run.log(f"check: {len(lat)} answers, {len(answers)} distinct, "
            f"{numbers['pairs']} pairs")
    counters = {"dim": x.shape[1], **delta}
    return drivers.Outcome(
        setup_s=setup_s,
        metrics={"join_vectors_per_s": len(lat) / seconds},
        attempted=submitted, failed=failed, check=judged, counters=counters,
        spans=win.events, trace=win.trace, memory_peak_bytes=peak)
