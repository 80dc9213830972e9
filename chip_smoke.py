"""Bring-up smoke run of DiskJoin on a TPU, checked against numpy float64.

One process drives the main path once at the SIFT1M / BIGANN-1M shape
(1M x 128 float32 vectors, big-ann-benchmarks), with a memory budget of a
tenth of the data so the join evicts and re-reads buckets. The cheap
phases run first, so a fault shows early:

1. build the 1M index with the Pallas assignment kernel;
2. reopen it and serve a few waves of epsilon-range queries through
   ``QueryScheduler`` in device mode, checked against float64;
3. on data of its own cut to 250k (see ``FULL``): host and device
   compute modes must give byte-identical pairs and distances on its
   first 100k rows;
4. self-join the 250k index in device mode with the Pallas verify kernel,
   then with XLA; check recall and every returned pair on sampled rows
   against float64 distances, and check that the two kernels disagree
   only within a tolerance band around epsilon.

``--chips 4`` runs only the distributed join on a 4-chip ``data`` mesh and
compares it with the one-chip join of the same 100k index. ``--rehearse`` runs
the same phases on the CPU at a tiny size with the Pallas kernels
interpreted; without it the script fails unless JAX reports a TPU.

Times printed here are bring-up observations, not benchmark numbers. The
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # distributed join, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# A pair whose float64 distance lies within this relative band of ε may
# fall on either side: float32 d² = a² − 2ab + b² carries rounding of a
# few 1e-5 of ε at |x|² ≈ 12. Anything further out is an error. Returned
# distances are held to the same rounding on d²: |d² − d64²| ≤ 2·REL_TOL·ε²
# (equal to the band near ε; sqrt would magnify it for near-zero pairs).
REL_TOL = 1e-3
AVG_NEIGHBORS = 20
RECALL_TARGET = 0.9


@dataclasses.dataclass(frozen=True)
class Size:
    n: int            # vectors in the built and served index
    join_n: int       # vectors in the joined index (data of its own)
    dim: int
    parity_n: int     # host/device byte-parity cut of the join's data
    ref_rows: int     # rows checked against float64 distances
    waves: int        # serving waves
    wave_size: int    # queries per wave
    # edges per chip per distributed dispatch; 0 keeps the index's
    dist_verify_batch: int = 0


# The published shape is SIFT1M / BIGANN-1M, 1M x 128. The one-chip run
# builds and serves an index at 1M. Its joins run on an index of their own
# at 250k: on one v5e the device-mode join with the Pallas kernel took
# 939 s at 1M, and the run must hold two joins and the host/device parity
# joins at 100k inside its time limit. The four-chip run cuts N to 100k,
# the smallest size at which the budget holds more than two buckets, so
# windows hold several edges to shard over the chips (it pays a one-chip
# join and the distributed one, on four chips). There the budget holds 4
# buckets and windows a few edges, so one edge per chip per dispatch lets
# the wider windows span two dispatches. The budget stays a tenth of
# the data and the bucket capacity stays 2048 rows, so the kernel shapes
# are those of 1M.
PUBLISHED_N = 1_000_000
FULL = Size(n=1_000_000, join_n=250_000, dim=128, parity_n=100_000,
            ref_rows=1000, waves=4, wave_size=64)
FOUR_CHIPS = Size(n=100_000, join_n=100_000, dim=128, parity_n=0,
                  ref_rows=1000, waves=0, wave_size=0, dist_verify_batch=1)
REHEARSAL = Size(n=4000, join_n=3000, dim=32, parity_n=1500, ref_rows=200,
                 waves=2, wave_size=16, dist_verify_batch=1)


class CheckFailed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


# ---------------------------------------------------------------------------
# float64 reference, independent of every device program
# ---------------------------------------------------------------------------
class Reference:
    """Exact float64 ε-neighbourhoods of chosen points against the data."""

    def __init__(self, x, eps: float):
        self.x64 = x.astype(np.float64)
        self.sq = np.sum(self.x64 ** 2, axis=1)
        self.eps = float(eps)

    def neighbors(self, q, block: int = 64):
        """Per query row: ids within ε in float64 (the truth sets)."""
        q = np.asarray(q, np.float64)
        out = []
        for i0 in range(0, q.shape[0], block):
            qb = q[i0:i0 + block]
            d2 = (np.sum(qb * qb, axis=1)[:, None] - 2.0 * qb @ self.x64.T
                  + self.sq[None, :])
            for row in d2:
                out.append(np.flatnonzero(row <= self.eps * self.eps))
        return out

    def dist(self, a, b):
        """Float64 distances by direct difference, one per (a, b) row."""
        return np.sqrt(np.sum((np.asarray(a, np.float64)
                               - np.asarray(b, np.float64)) ** 2, axis=1))

    def pair_dist(self, pairs):
        return self.dist(self.x64[pairs[:, 0]], self.x64[pairs[:, 1]])

    def beyond_band(self, d64):
        return d64 > self.eps * (1.0 + REL_TOL)

    def in_band(self, d64):
        return abs(d64 - self.eps) <= REL_TOL * self.eps

    def sq_err(self, d, d64) -> float:
        """Largest |d² − d64²| in units of ε² (0 for no pairs)."""
        if not np.size(d):
            return 0.0
        return float(np.abs(np.asarray(d, np.float64) ** 2
                            - np.asarray(d64, np.float64) ** 2).max()
                     ) / self.eps ** 2

    def check_sq_err(self, d, d64, label: str) -> float:
        err = self.sq_err(d, d64)
        check(err <= 2 * REL_TOL, f"{label}: distances differ by "
                                  f"{err!r} eps^2 in d^2")
        return err


def check_join_rows(ref: Reference, pairs, dists, rows, label: str) -> None:
    """Recall on ``rows`` and every returned pair touching them."""
    truth = ref.neighbors(ref.x64[rows])
    on = np.isin(pairs[:, 0], rows) | np.isin(pairs[:, 1], rows)
    got, got_d = pairs[on], dists[on]
    d64 = ref.pair_dist(got)
    found = 0
    total = 0
    keys = set(map(tuple, np.sort(got, axis=1).tolist()))
    for r, t in zip(rows, truth):
        t = t[t != r]
        total += t.size
        found += sum((min(r, j), max(r, j)) in keys for j in t.tolist())
    rec = found / total if total else 1.0
    worst = float(d64.max()) if d64.size else 0.0
    log(f"{label}: rows={len(rows)} truth_pairs={total} "
        f"returned_pairs={got.shape[0]} recall={rec:.6f} "
        f"(target {RECALL_TARGET}) max_f64_dist={worst!r} "
        f"eps={ref.eps!r} ({worst / ref.eps!r} of eps) "
        f"max_d2_err={ref.sq_err(got_d, d64)!r} eps^2")
    check(rec >= RECALL_TARGET, f"{label}: recall {rec} < {RECALL_TARGET}")
    bad = int(ref.beyond_band(d64).sum())
    check(bad == 0, f"{label}: {bad} returned pairs lie beyond eps by more "
                    f"than {REL_TOL} relative (worst {worst!r})")
    ref.check_sq_err(got_d, d64, label)


def compare_joins(ref: Reference, pa, da, pb, db, label: str) -> None:
    """Two joins of one index agree outside the ε band: the pairs only
    one of them returned lie within it, shared pairs' distances agree
    within it."""
    def keys(p):
        return (p[:, 0].astype(np.int64) << 32) | p[:, 1].astype(np.int64)

    ka, kb = keys(pa), keys(pb)
    only = np.concatenate([pa[~np.isin(ka, kb)], pb[~np.isin(kb, ka)]])
    d64 = ref.pair_dist(only)
    out_of_band = int((~ref.in_band(d64)).sum())
    _, ia, ib = np.intersect1d(ka, kb, return_indices=True)
    log(f"{label}: pairs {pa.shape[0]} vs {pb.shape[0]}, disagree on "
        f"{only.shape[0]} (all within {REL_TOL} of eps: "
        f"{out_of_band == 0}), max |d_a^2 - d_b^2| on shared pairs "
        f"{ref.sq_err(da[ia], db[ib])!r} eps^2, bytes equal "
        f"{da.tobytes() == db.tobytes() and np.array_equal(pa, pb)}")
    check(out_of_band == 0, f"{label}: {out_of_band} disagreeing pairs lie "
                            f"outside the eps band")
    ref.check_sq_err(da[ia], db[ib], label)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def make_data(n: int, dim: int, seed: int, path: str):
    from repro.data import clustered_vectors, epsilon_for_avg_neighbors
    from repro.store.vector_store import FlatVectorStore
    t0 = time.perf_counter()
    x = clustered_vectors(n, dim, seed=seed)
    eps = epsilon_for_avg_neighbors(x, AVG_NEIGHBORS, seed=seed)
    store = FlatVectorStore.from_array(path, x)
    log(f"data: n={n} dim={dim} float32 bytes={x.nbytes} "
        f"eps={eps!r} ({AVG_NEIGHBORS} neighbours on average) "
        f"made in {time.perf_counter() - t0:.3f}s")
    check(bool(np.isfinite(x).all()), "data holds non-finite values")
    return x, eps, store


def join_config(x, eps: float):
    from repro.core import JoinConfig
    # a tenth of the data, as in the paper: the join evicts and re-reads
    return JoinConfig(epsilon=eps, recall_target=RECALL_TARGET,
                      memory_budget_bytes=x.nbytes // 10, pad_align=128,
                      use_pallas=True, compute_mode="device",
                      io_mode="prefetch")


def build_index(store, cfg, workdir: str, label: str):
    from repro.core.index import DiskJoinIndex
    t0 = time.perf_counter()
    index = DiskJoinIndex.build(store, cfg, workdir)
    log(f"{label}: build (Pallas assignment) {time.perf_counter() - t0:.3f}s"
        f" buckets={index.num_buckets} capacity={index.bucket_capacity} "
        f"budget_bytes={cfg.memory_budget_bytes} "
        f"phases={ {k: round(v, 3) for k, v in index.build_timings.items()} }")
    return index


def check_assignment(x, index, eps: float) -> None:
    """The Pallas assignment kernel against the XLA reference on one
    build block: the same d², and the same nearest centre unless the two
    centres are equally near within rounding (in float64)."""
    from repro.kernels import ops, ref
    block = x[:8192]
    centers = index.meta.centers
    d_p, i_p = (np.asarray(a) for a in ops.bucket_assign(block, centers))
    d_r, i_r = (np.asarray(a) for a in ref.bucket_assign(block, centers))
    differ = np.flatnonzero(i_p != i_r)
    b64 = block[differ].astype(np.float64)
    gap = np.abs(np.sum((b64 - centers[i_p[differ]]) ** 2, axis=1)
                 - np.sum((b64 - centers[i_r[differ]]) ** 2, axis=1))
    err = float(np.abs(d_p - d_r).max()) / (eps * eps)
    log(f"assignment kernel vs xla: rows={block.shape[0]} "
        f"centers={centers.shape[0]} differ={differ.size} "
        f"max |d2_p - d2_x|={err!r} eps^2")
    check(bool((gap <= 2 * REL_TOL * eps * eps).all()),
          "assignment kernel picks a farther centre")
    check(err <= 2 * REL_TOL, "assignment kernel distances differ")


def best_of(fn, reps: int = 3) -> float:
    """Fastest of ``reps`` timed calls after one untimed call, in ms."""
    import jax
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return 1e3 * min(times)


def verify_program(index, x, eps: float, use_pallas: bool,
                   tpu: bool) -> None:
    """Compile the fused device verify at the join's batch shape and print
    its fingerprint (on the chip the Pallas build must hold the kernel);
    then time one batch of it, and of the kernel alone, on data rows."""
    import jax
    import jax.numpy as jnp

    from repro.compute import PAIR_CAP_INIT, next_pow2
    from repro.compute.engine import device_verify
    from repro.kernels import ops
    lanes = next_pow2(index.query_defaults.verify_batch)
    cap, dim = index.bucket_capacity, index.dim
    slab = jax.ShapeDtypeStruct((cap, dim), jnp.float32)
    count = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    intra = jax.ShapeDtypeStruct((lanes,), jnp.bool_)
    # the device engine's first compaction capacity
    k_cap = min(next_pow2(max(PAIR_CAP_INIT, 8 * cap)), cap * cap)
    t0 = time.perf_counter()
    compiled = device_verify.lower(
        count, count, intra, *([slab] * (2 * lanes)), eps=eps,
        k_cap=k_cap, use_pallas=use_pallas).compile()
    text = compiled.as_text()
    kernel = "tpu_custom_call" in text
    log(f"verify program use_pallas={use_pallas}: compile "
        f"{time.perf_counter() - t0:.3f}s fingerprint="
        f"{hashlib.sha256(text.encode()).hexdigest()[:16]} "
        f"tpu_custom_call={kernel}")
    if tpu:
        check(kernel == use_pallas, f"use_pallas={use_pallas} but "
                                    f"tpu_custom_call={kernel}")
    slabs = [jnp.asarray(s) for s in np.resize(x, (2 * lanes, cap, dim))]
    full = jnp.full((lanes,), cap, jnp.int32)
    cross = jnp.zeros((lanes,), bool)
    u, v = jnp.stack(slabs[:lanes]), jnp.stack(slabs[lanes:])
    kern = jax.jit(lambda u, v: ops.verify_pairs_batch(
        u, v, eps, use_pallas=use_pallas))
    log(f"verify batch use_pallas={use_pallas} ({lanes} edges of "
        f"{cap}x{cap}): kernel+compaction "
        f"{best_of(lambda: compiled(full, full, cross, *slabs)):.3f}ms, "
        f"kernel alone {best_of(lambda: kern(u, v)):.3f}ms")


def timed_join(index, label: str, **overrides):
    t0 = time.perf_counter()
    res = index.self_join(**overrides)
    wall = time.perf_counter() - t0
    io = res.io_stats.get("pipeline") or {}
    log(f"{label}: wall {wall:.3f}s pairs={res.pairs.shape[0]} "
        f"distance_computations={res.num_distance_computations} "
        f"({res.num_distance_computations / wall:.6g} pairs verified/s) "
        f"bucket_loads={res.bucket_loads} "
        f"cache_hit_rate={res.cache_hit_rate:.4f} "
        f"execute_s={res.timings['execute']:.3f} "
        f"compute_s={res.timings['compute']:.3f} "
        f"io_wait_s={res.timings['io_wait']:.3f} "
        f"device_batches={io.get('device_batches', 0)} "
        f"compact_overflows={io.get('device_compact_overflows', 0)} "
        f"h2d_bytes={io.get('h2d_bytes', 0)} "
        f"d2h_bytes={io.get('d2h_bytes', 0)}")
    return res


def phase_join(index, x, ref: Reference, rows, eps: float,
               tpu: bool) -> None:
    results = {}
    for use_pallas in (True, False):
        name = "pallas" if use_pallas else "xla"
        verify_program(index, x, eps, use_pallas, tpu)
        res = timed_join(index, f"join[{name}]", compute_mode="device",
                         io_mode="prefetch", use_pallas=use_pallas)
        check_join_rows(ref, res.pairs, res.distances, rows,
                        f"join[{name}] vs float64")
        results[name] = res
    p, q = results["pallas"], results["xla"]
    compare_joins(ref, p.pairs, p.distances, q.pairs, q.distances,
                  "pallas vs xla")


def phase_parity(x, size: Size, seed: int, workdir: str) -> None:
    from repro.data import epsilon_for_avg_neighbors
    from repro.store.vector_store import FlatVectorStore
    xp = np.ascontiguousarray(x[:size.parity_n])
    eps = epsilon_for_avg_neighbors(xp, AVG_NEIGHBORS, seed=seed)
    os.makedirs(workdir)
    store = FlatVectorStore.from_array(os.path.join(workdir, "x.bin"), xp)
    with build_index(store, join_config(xp, eps), os.path.join(
            workdir, "index"), f"parity n={size.parity_n}") as index:
        host = timed_join(index, "parity join[host]", compute_mode="host")
        dev = timed_join(index, "parity join[device]", compute_mode="device")
    same_pairs = np.array_equal(host.pairs, dev.pairs)
    same = (same_pairs
            and host.distances.tobytes() == dev.distances.tobytes())
    differ = (int((host.distances.view(np.uint32)
                   != dev.distances.view(np.uint32)).sum())
              if same_pairs else "n/a")
    log(f"parity host vs device: pairs={host.pairs.shape[0]} "
        f"pairs_equal={same_pairs} distances_differing={differ} "
        f"byte_identical={same}")
    check(same, "host and device compute modes differ")


def phase_serving(workdir: str, ref: Reference, x, size: Size,
                  seed: int) -> None:
    from repro.core.index import DiskJoinIndex
    from repro.serve import QueryScheduler
    rng = np.random.default_rng(seed + 1)
    nq = size.waves * size.wave_size
    base = rng.choice(x.shape[0], size=nq, replace=False)
    # each query sits about a quarter of ε away from a data point
    noise = rng.normal(scale=0.25 * ref.eps / np.sqrt(x.shape[1]),
                       size=(nq, x.shape[1]))
    queries = (x[base] + noise).astype(np.float32)
    answers = []
    with DiskJoinIndex.open(workdir) as index, QueryScheduler(
            index, epsilon=ref.eps, wave_size=size.wave_size,
            max_wait_s=0.05, compute_mode="device") as sched:
        for w in range(size.waves):
            qs = queries[w * size.wave_size:(w + 1) * size.wave_size]
            t0 = time.perf_counter()
            futs = [sched.submit(q) for q in qs]
            answers.extend(f.result(timeout=600) for f in futs)
            log(f"serving wave {w}: {len(qs)} queries in "
                f"{time.perf_counter() - t0:.4f}s")
        waves = sched.waves
    truth = ref.neighbors(queries)
    found = total = 0
    got_d, got_d64 = [np.zeros(0)], [np.zeros(0)]
    for q, (ids, dists), t in zip(queries, answers, truth):
        total += t.size
        found += int(np.isin(t, ids).sum())
        got_d.append(dists)
        got_d64.append(ref.dist(ref.x64[ids],
                                np.broadcast_to(q, (ids.size, q.size))))
    d, d64 = np.concatenate(got_d), np.concatenate(got_d64)
    rec = found / total if total else 1.0
    worst = float(d64.max()) if d64.size else 0.0
    log(f"serving vs float64: queries={nq} waves={waves} truth={total} "
        f"returned={d.size} recall={rec:.6f} (target {RECALL_TARGET}) "
        f"max_f64_dist={worst!r} max_d2_err={ref.sq_err(d, d64)!r} eps^2")
    check(rec >= RECALL_TARGET, f"serving recall {rec} < {RECALL_TARGET}")
    bad = int(ref.beyond_band(d64).sum())
    check(bad == 0, f"serving returned {bad} ids beyond the eps band")
    ref.check_sq_err(d, d64, "serving")


def precision_probe(x, ref: Reference, rows) -> None:
    """How far one bf16 pass (DEFAULT) and full f32 (HIGHEST) matmuls
    move d² near ε on this device: pairs on the wrong side of ε beyond
    the band, against float64."""
    import jax
    import jax.numpy as jnp
    a = jnp.asarray(x[rows])
    b = jnp.asarray(x[:min(x.shape[0], 65536)])
    d64 = np.sqrt(np.maximum(
        np.sum(ref.x64[rows] ** 2, 1)[:, None]
        - 2.0 * ref.x64[rows] @ ref.x64[:b.shape[0]].T
        + ref.sq[None, :b.shape[0]], 0.0))
    outside = ~ref.in_band(d64)
    truth = d64 <= ref.eps
    for prec in (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST):
        d2 = np.asarray(jax.jit(lambda p, q, prec=prec: (
            jnp.sum(p * p, 1)[:, None]
            - 2.0 * jnp.matmul(p, q.T, precision=prec)
            + jnp.sum(q * q, 1)[None, :]))(a, b))
        flips = int(((d2 <= ref.eps ** 2) != truth)[outside].sum())
        err = ref.sq_err(np.sqrt(np.maximum(d2, 0))[truth], d64[truth])
        log(f"precision probe {prec.name}: {len(rows)}x{b.shape[0]} "
            f"distances, {int(truth.sum())} within eps, max |d^2 - d64^2| "
            f"there {err!r} eps^2, eps-flips beyond the band {flips}")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def sample_rows(n: int, size: Size, seed: int):
    return np.sort(np.random.default_rng(seed).choice(
        n, size=size.ref_rows, replace=False))


def run_one_chip(size: Size, seed: int, workdir: str, tpu: bool) -> None:
    # a failed check of one phase does not stop the phases that do not
    # depend on it, so one run shows every fault; the run still fails
    failed = []

    def phase(fn, *args):
        try:
            fn(*args)
        except CheckFailed as e:
            log(f"CHECK FAILED: {e}")
            failed.append(str(e))

    # build and serve at the full size
    x, eps, store = make_data(size.n, size.dim, seed,
                              os.path.join(workdir, "x.bin"))
    cfg = join_config(x, eps)
    index_dir = os.path.join(workdir, "index")
    with build_index(store, cfg, index_dir, f"index n={size.n}") as index:
        phase(check_assignment, x, index, eps)
    phase(phase_serving, index_dir, Reference(x, eps), x, size, seed)
    del x, store
    # the joins, at their cut
    x, eps, store = make_data(size.join_n, size.dim, seed,
                              os.path.join(workdir, "join.bin"))
    ref = Reference(x, eps)
    rows = sample_rows(size.join_n, size, seed)
    precision_probe(x, ref, rows[:256])
    phase(phase_parity, x, size, seed, os.path.join(workdir, "parity"))
    with build_index(store, join_config(x, eps), os.path.join(
            workdir, "join_index"), f"join index n={size.join_n}") as index:
        phase_join(index, x, ref, rows, eps, tpu)
    check(not failed, f"{len(failed)} phases failed: {failed}")


def run_four_chips(size: Size, seed: int, workdir: str) -> None:
    import jax

    from repro.core import build_bucket_graph
    from repro.core.distributed import DistributedJoin
    from repro.core.types import merge_config
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs four devices, JAX reports "
                             f"{len(devices)}")
    x, eps, store = make_data(size.n, size.dim, seed,
                              os.path.join(workdir, "x.bin"))
    ref = Reference(x, eps)
    rows = sample_rows(size.n, size, seed)
    with build_index(store, join_config(x, eps), os.path.join(
            workdir, "index"), f"index n={size.n}") as index:
        check_assignment(x, index, eps)
        one = timed_join(index, "one-chip join[device]")
        cfg = merge_config(index.build_config, index.query_defaults)
        if size.dist_verify_batch:
            cfg = dataclasses.replace(cfg,
                                      verify_batch=size.dist_verify_batch)
        graph = build_bucket_graph(index.meta, cfg)
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        t0 = time.perf_counter()
        pairs, info = DistributedJoin(index.store, index.meta, cfg,
                                      mesh=mesh).run(graph)
        wall = time.perf_counter() - t0
    dists = info["dists"]
    log(f"distributed join on mesh {dict(mesh.shape)}: wall {wall:.3f}s "
        f"pairs={pairs.shape[0]} distance_computations="
        f"{info['distance_computations']} "
        f"({info['distance_computations'] / wall:.6g} pairs verified/s) "
        f"supersteps={info['supersteps']} verify_batch={cfg.verify_batch} "
        f"dispatches={info['verify_dispatches']} "
        f"compact_overflows={info['compact_overflows']} "
        f"h2d_bytes={info['h2d_bytes']}")
    check_join_rows(ref, pairs, dists, rows, "distributed join vs float64")
    compare_joins(ref, pairs, dists, one.pairs, one.distances,
                  "distributed vs one-chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpreted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse and args.chips == 4 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    tpu = dev.platform == "tpu"
    if not tpu and not args.rehearse:
        log("no TPU: JAX reports no accelerator (use --rehearse on CPU)")
        return 1
    if not args.rehearse:
        log(f"compile cache: {enable_compile_cache(ROOT)}")
    size = (REHEARSAL if args.rehearse
            else FOUR_CHIPS if args.chips == 4 else FULL)
    log(f"size: dim={size.dim}, index n={size.n}, join n={size.join_n}, "
        f"parity n={size.parity_n}; joins cut from the published "
        f"{PUBLISHED_N} rows to fit the run's time (see FULL)")
    log("times below are bring-up observations, not benchmark numbers")
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            if args.chips == 4:
                run_four_chips(size, args.seed, workdir)
            else:
                run_one_chip(size, args.seed, workdir, tpu)
    except Exception:
        traceback.print_exc()
        log(f"FAILED after {time.perf_counter() - t0:.1f}s")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
