"""Traffic kind ``dist_join``: whole distributed self-joins back to back,
each as a user calls one: ``build_bucket_graph`` on the built index's
meta, then ``DistributedJoin(...).run(graph)`` on a ``data`` mesh of the
cell's chips (``core/distributed.py``), which shards each superstep's
window edges over the chips.

Set-up warms every program a job runs, listed from the job's own plan
(``plan_supersteps``): for each window size and edge count in it, the
window slab's stack and the slices that harvest it, and the verify of
each edge chunk at each pair capacity up to the traffic's
``warm_k_cap_doublings`` raises, through the join's own chunking and
dispatch. The window runs whole jobs (``drivers``' ``_join_window``); the
check compares each distinct result with float64.
"""
from __future__ import annotations

import time

import numpy as np

import drivers


def _job(index, cfg, mesh):
    from repro.core import build_bucket_graph
    from repro.core.distributed import DistributedJoin

    def once():
        graph = build_bucket_graph(index.meta, cfg)
        return DistributedJoin(index.store, index.meta, cfg,
                               mesh=mesh).run(graph)
    return once


def _warm_dist(run: drivers.Run, index, cfg, mesh) -> int:
    """Every program of a job, without a job (module docstring). Returns
    the number of (window size, edge chunk) shapes warmed."""
    import jax

    from repro.compute import DeviceSlabPool
    from repro.core import build_bucket_graph
    from repro.core.distributed import DistributedJoin, plan_supersteps
    graph = build_bucket_graph(index.meta, cfg)
    dj = DistributedJoin(index.store, index.meta, cfg, mesh=mesh)
    steps = plan_supersteps(graph, cfg, dj.cache_buckets, meta=index.meta)
    sharding = jax.sharding.NamedSharding(
        dj.mesh, jax.sharding.PartitionSpec("data"))
    eps2 = float(cfg.epsilon) ** 2
    base, top = dj._pair_cap, dj.cap * dj.cap
    k_caps = sorted({min(base << i, top) for i in range(
        run.cell.traffic["warm_k_cap_doublings"] + 1)})
    shapes = {}
    for s in steps:
        w, e = len(s.bucket_ids), s.edges_local.shape[0]
        if e:
            chunks = tuple(ed.shape[0] for _, ed in dj._edge_chunks(
                np.zeros((e, 2), np.int32), sharding))
            shapes.setdefault((w, chunks), e)
    for (w, _), e in sorted(shapes.items()):
        entries = [dj._fetch(b) for b in range(w)]
        dj._dev_pool = DeviceSlabPool()
        for _ in range(2):  # first touch stacks host copies, then harvests
            slab = _stack_window(dj, entries)
        edges = np.zeros((e, 2), np.int32)
        for ce, edges_dev in dj._edge_chunks(edges, sharding):
            for k_cap in k_caps:
                dj._pair_cap = k_cap
                out = dj._dispatch_compact(slab, ce, edges_dev, entries,
                                           eps2)[3]
                jax.block_until_ready(out)
                np.asarray(out[0])
    return len(shapes)


def _stack_window(dj, entries):
    """A window slab as ``DistributedJoin.run`` stacks it, with the
    harvest of each first-touch bucket."""
    import jax.numpy as jnp
    slab = jnp.stack([dj._dev_pool.operand(b, e[0])
                      for b, e in enumerate(entries)])
    for wi in range(len(entries)):
        if dj._dev_pool.needs_harvest(wi):
            dj._dev_pool.harvest(wi, slab[wi])
    return slab


def drive(run: drivers.Run) -> drivers.Outcome:
    import jax

    from repro.core.types import merge_config
    chips = run.cell.chips
    t0 = time.perf_counter()
    x, eps = drivers._data(run)
    run.log(f"data: {x.shape[0]} x {x.shape[1]} float32 in "
            f"{time.perf_counter() - t0:.3f}s")
    index, _ = drivers._build(run, x, eps)
    cfg = merge_config(index.build_config, index.query_defaults)
    mesh = jax.make_mesh((chips,), ("data",), devices=run.devices[:chips])
    t_warm = time.perf_counter()
    n = _warm_dist(run, index, cfg, mesh)
    jax.effects_barrier()
    run.log(f"warm-up: {n} window and chunk shapes of the plan in "
            f"{time.perf_counter() - t_warm:.3f}s on mesh "
            f"{dict(mesh.shape)}")
    setup_s = time.perf_counter() - t0
    with drivers._Window(run) as win:
        results, durations, usage = drivers._join_window(
            run, _job(index, cfg, mesh))
    peak = drivers._memory_peak(run.devices[:chips])
    index.close()
    info = results[-1][1]
    counters = {"dim": x.shape[1], "joins": len(results),
                **{k: info.get(k, 0) for k in (
                    "distance_computations", "verify_dispatches",
                    "supersteps", "compact_overflows", "h2d_bytes")}}
    for (pairs, i), dt, u in zip(results, durations, usage):
        run.log(f"job: {dt:.3f}s pairs={pairs.shape[0]} "
                f"distance_computations={i['distance_computations']} "
                f"supersteps={i['supersteps']} "
                f"verify_dispatches={i['verify_dispatches']} "
                f"compact_overflows={i.get('compact_overflows', 0)} "
                f"h2d_bytes={i.get('h2d_bytes', 0)} "
                f"host_loads={i['host_loads']} host_hits={i['host_hits']} "
                f"prefetched_buckets={i['prefetched_buckets']} "
                f"host_user_s={u[0]:.3f} host_sys_s={u[1]:.3f} "
                f"involuntary_switches={int(u[2])} "
                f"major_faults={int(u[3])}")
    check = drivers._check_join(run, x, eps,
                                [(p, i["dists"]) for p, i in results])
    rows = x.shape[0] * len(results)
    return drivers.Outcome(
        setup_s=setup_s,
        metrics={"join_vectors_per_s.dist": rows / sum(durations)},
        attempted=len(results), failed=0, check=check, counters=counters,
        spans=win.events, trace=win.trace, memory_peak_bytes=peak)
