"""Traffic kind ``join``: whole ``DiskJoinIndex.self_join`` calls back to
back on one built index.

Set-up makes the data from the seed, builds the index and warms every
program a self-join runs; the window runs whole joins (``drivers``'
``_join_window``); the check compares each distinct result with float64.
"""
from __future__ import annotations

import time

import numpy as np

import drivers


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


def _warm_join(run: drivers.Run, index, jc) -> None:
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


def drive(run: drivers.Run) -> drivers.Outcome:
    import jax
    t0 = time.perf_counter()
    x, eps = drivers._data(run)
    run.log(f"data: {x.shape[0]} x {x.shape[1]} float32 in "
            f"{time.perf_counter() - t0:.3f}s")
    index, jc = drivers._build(run, x, eps)
    _warm_join(run, index, jc)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t0
    with drivers._Window(run) as win:
        results, durations, usage = drivers._join_window(run,
                                                         index.self_join)
    peak = drivers._memory_peak(run.devices[:run.cell.chips])
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
                f"device_wait_s={p.get('device_wait_s', 0.0):.3f} "
                f"extract_s={p.get('extract_s', 0.0):.3f} "
                f"device_compact_slots={p.get('device_compact_slots', 0)} "
                f"device_batch_pairs_max="
                f"{p.get('device_batch_pairs_max', 0)} "
                f"host_user_s={u[0]:.3f} host_sys_s={u[1]:.3f} "
                f"involuntary_switches={int(u[2])} "
                f"major_faults={int(u[3])}")
    check = drivers._check_join(run, x, eps,
                                [(r.pairs, r.distances) for r in results])
    rows = x.shape[0] * len(results)
    return drivers.Outcome(
        setup_s=setup_s,
        metrics={"join_vectors_per_s": rows / sum(durations)},
        attempted=len(results), failed=0, check=check, counters=counters,
        spans=win.events, trace=win.trace, memory_peak_bytes=peak)
