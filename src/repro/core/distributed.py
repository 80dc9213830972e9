"""Distributed DiskJoin execution over a JAX mesh (DESIGN §5).

Mapping of the paper's single-box design onto a pod:

  SSD               → host-side bucketed store (per-host shard of buckets)
  DRAM cache        → per-superstep device slab: the Gorder window's buckets,
                      assembled by the host under the same Belady policy,
                      then placed sharded over the ``data`` axis
  edge tasks        → sharded over ``data``: each device verifies its slice
                      of the window's edges; remote buckets arrive via the
                      gather XLA inserts for cross-shard ``jnp.take``
  verify kernel     → vmapped pairwise-L2 threshold (Pallas on TPU)

Supersteps inherit the Gorder locality: consecutive windows share most of
their buckets, so the host cache (Belady) converts that into fewer
host→device transfers — the pod analogue of fewer SSD reads.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ordering
from repro.core.executor import PAD_COORD
from repro.core.types import (BucketGraph, BucketMeta, JoinConfig,
                              dedup_pairs, resolve_bucket_capacity,
                              resolve_cache_buckets, round_up as _round_up)
from repro.kernels import ref
from repro.obs import get_tracer


@partial(jax.jit, static_argnames=("eps2",))
def verify_edges(slab: jax.Array, edges: jax.Array, eps2: float):
    """slab: (W, cap, d) window bucket slab; edges: (E, 2) int32 into slab.

    Returns (counts (E,), mask (E, cap, cap) bool, d2 (E, cap, cap)) —
    the squared distances ride along so the host can emit pair distances
    without recomputing them. Under pjit with edges sharded over
    ``data``, the slab gathers become collectives.
    """
    u = jnp.take(slab, edges[:, 0], axis=0)      # (E, cap, d)
    v = jnp.take(slab, edges[:, 1], axis=0)
    d2 = jax.vmap(ref.pairwise_l2)(u, v)         # (E, cap, cap)
    mask = d2 <= eps2
    return jnp.sum(mask, axis=(1, 2)), mask, d2


@partial(jax.jit, static_argnames=("eps2", "k_cap"))
def verify_edges_compact(slab: jax.Array, edges: jax.Array, na: jax.Array,
                         nb: jax.Array, intra: jax.Array, eps2: float,
                         k_cap: int):
    """Compacted variant (``compute_mode="device"``): instead of shipping
    the full (E, cap, cap) mask back to the host, pairs are compacted
    on-device (``repro.compute.compact_pairs``) — D2H shrinks from
    E·cap² bytes to E·(1 + 3·k_cap) values. ``na``/``nb`` carry the
    live-row counts (0 masks a padding lane out entirely)."""
    from repro.compute import compact_pairs
    u = jnp.take(slab, edges[:, 0], axis=0)
    v = jnp.take(slab, edges[:, 1], axis=0)
    d2 = jax.vmap(ref.pairwise_l2)(u, v)
    return compact_pairs(d2, d2 <= eps2, na, nb, intra, k_cap)


def with_auto_axes(mesh: jax.sharding.Mesh) -> jax.sharding.Mesh:
    """The same devices and axis names with Auto axis types.

    ``jax.make_mesh`` gives Explicit axes, under which a gather by
    data-sharded edge indices must name its output sharding. With Auto
    axes the compiler keeps the window slab replicated and shards the
    gathered operands with their edges."""
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names)


@dataclasses.dataclass
class Superstep:
    bucket_ids: np.ndarray   # (W,) global bucket ids in this window
    edges_local: np.ndarray  # (E, 2) int32 indices into bucket_ids
    edges_global: np.ndarray  # (E, 2) original bucket ids


def plan_supersteps(graph: BucketGraph, config: JoinConfig,
                    cache_buckets: int,
                    meta: BucketMeta) -> list[Superstep]:
    """Gorder → windows of ≤cache_buckets buckets covering all edges.

    Each edge lands in the first window containing both endpoints; the
    window advances greedily along the node order (self-pairs implicit —
    every bucket appears in ≥1 window). The order comes from
    ``ordering.compute_node_order`` (shared with the single-box executor,
    incl. the spatial strategy).
    """
    node_order = ordering.compute_node_order(graph, meta, config,
                                             cache_buckets)
    tasks, _, _ = ordering.edge_schedule(graph, node_order)

    steps: list[Superstep] = []
    cur_buckets: list[int] = []
    cur_edges: list[tuple[int, int]] = []
    seen: dict[int, int] = {}

    def flush():
        nonlocal cur_buckets, cur_edges, seen
        if not cur_buckets:
            return
        bids = np.asarray(cur_buckets, dtype=np.int64)
        eg = (np.asarray(cur_edges, dtype=np.int64)
              if cur_edges else np.zeros((0, 2), np.int64))
        el = np.stack([[seen[int(a)] for a, _ in cur_edges],
                       [seen[int(b)] for _, b in cur_edges]], axis=1
                      ).astype(np.int32) if cur_edges else \
            np.zeros((0, 2), np.int32)
        steps.append(Superstep(bids, el, eg))
        cur_buckets, cur_edges, seen = [], [], {}

    cap = max(2, cache_buckets)
    for t in tasks:
        need = [t[1]] if t[0] == "touch" else [t[1], t[2]]
        new = [b for b in need if int(b) not in seen]
        if len(cur_buckets) + len(new) > cap:
            flush()
            new = need
        for b in need:
            b = int(b)
            if b not in seen:
                seen[b] = len(cur_buckets)
                cur_buckets.append(b)
        if t[0] == "touch":
            cur_edges.append((int(t[1]), int(t[1])))  # self edge
        else:
            cur_edges.append((int(t[1]), int(t[2])))
    flush()
    return steps


class DistributedJoin:
    """Superstep-wise distributed execution of a planned join.

    ``mesh`` must have a ``data`` axis; edges shard over it. The host keeps
    a Belady-managed slab cache so consecutive supersteps reuse transfers.
    """

    def __init__(self, store, meta: BucketMeta, config: JoinConfig,
                 mesh: jax.sharding.Mesh | None = None):
        self.store = store
        self.meta = meta
        self.config = config
        self.mesh = with_auto_axes(mesh) if mesh is not None else None
        self.cap = resolve_bucket_capacity(config, meta.sizes)
        self.cache_buckets = resolve_cache_buckets(config, self.cap,
                                                   store.dim)
        self._host_cache: dict[int, np.ndarray] = {}
        self._staged: dict[int, tuple] = {}  # prefetched, not yet fetched
        self.loads = 0
        self.hits = 0
        self.prefetched = 0  # window w+1 loads issued under w's verify
        self.dispatches = 0  # verify programs issued (chunks of windows)
        self.overflows = 0   # chunks re-sent at a larger pair capacity
        # compute_mode="device": per-bucket device slabs persist across
        # supersteps (evicted on the host keep-set), so consecutive
        # windows re-transfer only their *new* buckets instead of
        # device_put-ing the whole window slab every superstep
        from repro.compute import DeviceSlabPool, next_pow2
        self._dev_pool = (DeviceSlabPool() if config.compute_mode == "device"
                          else None)
        self._next_pow2 = next_pow2
        self._pair_cap = min(next_pow2(max(1024, 8 * self.cap)),
                             self.cap * self.cap)

    def _read_padded(self, b: int) -> tuple[np.ndarray, np.ndarray, int]:
        from repro.io.retry import read_with_retry
        vecs, ids = read_with_retry(
            lambda: self.store.read_bucket(b),
            retries=self.config.io_retries,
            backoff_s=self.config.io_retry_backoff_s)
        n = vecs.shape[0]
        pad = self.cap - n
        if pad > 0:
            vecs = np.concatenate(
                [vecs, np.full((pad, vecs.shape[1]), PAD_COORD, vecs.dtype)])
        return (vecs.astype(np.float32), ids, n)

    def _fetch(self, b: int) -> tuple[np.ndarray, np.ndarray, int]:
        if b in self._host_cache:
            self.hits += 1
            return self._host_cache[b]
        entry = self._staged.pop(b, None)
        if entry is None:            # not prefetched: load now
            entry = self._read_padded(b)
            self.loads += 1          # prefetched loads were counted at issue
        self._host_cache[b] = entry
        return entry

    def _evict_to(self, keep: set[int]) -> None:
        # host cache follows the superstep plan: keep only upcoming window
        # + LRU slack up to capacity (Belady degenerate form: the plan IS
        # the future, and the next window is the nearest future access)
        if len(self._host_cache) <= self.cache_buckets:
            return
        for b in list(self._host_cache.keys()):
            if b not in keep and len(self._host_cache) > self.cache_buckets:
                del self._host_cache[b]
                if self._dev_pool is not None:
                    self._dev_pool.evict(b)  # device mirrors host residency

    def _prefetch_window(self, step: "Superstep") -> None:
        """ROADMAP "prefetch for the distributed join": while window w's
        verify runs on-device (async dispatch), pull window w+1's missing
        buckets from disk. They land in a *staging* dict, not the host
        cache: staged entries must not add eviction pressure before
        window w's keep-set trim runs, or gap-retained buckets (kept by
        PR 2's upcoming-window keep-set) would be pushed out early and
        re-read. ``_fetch`` merges staged entries in when w+1 begins."""
        with get_tracer().span("dist.prefetch",
                               buckets=len(step.bucket_ids)):
            for b in step.bucket_ids:
                b = int(b)
                if b not in self._host_cache and b not in self._staged:
                    self._staged[b] = self._read_padded(b)
                    self.loads += 1
                    self.prefetched += 1

    def _edge_chunks(self, edges, sharding):
        """A window's edges as dispatches of at most ``verify_batch``
        edges per shard, in both compute modes: each dispatch holds
        (E, cap, cap) distance temporaries, which for a whole window's
        edges can outgrow device memory. A chunk pads to the next pow2
        (bounded recompiles) and, under a mesh, to a shard multiple; pad
        lanes point at slab 0 and their results are dropped. Yields
        (chunk edges, padded device edges)."""
        shards = self.mesh.shape["data"] if sharding is not None else 1
        chunk = self.config.verify_batch * shards
        for c0 in range(0, edges.shape[0], chunk):
            ce = edges[c0:c0 + chunk]
            Ep = _round_up(self._next_pow2(ce.shape[0]), shards)
            edges_dev = jnp.asarray(np.concatenate(
                [ce, np.zeros((Ep - ce.shape[0], 2), ce.dtype)]))
            if sharding is not None:
                edges_dev = jax.device_put(edges_dev, sharding)
            yield ce, edges_dev

    def _dispatch_compact(self, slab, ce, edges_dev, entries, eps2):
        """Issue the compacted verify of one chunk (async) at the current
        pair capacity. Pad lanes carry na = nb = 0 so the compaction
        masks them out entirely. The handle keeps the capacity the chunk
        was sent at: a raise by an earlier chunk's overflow must not
        hide this one's."""
        Ep, E = edges_dev.shape[0], ce.shape[0]
        rowc = np.array([e[2] for e in entries], np.int32)
        na = np.zeros(Ep, np.int32)
        nb = np.zeros(Ep, np.int32)
        na[:E] = rowc[ce[:, 0]]
        nb[:E] = rowc[ce[:, 1]]
        intra = np.zeros(Ep, bool)
        intra[:E] = ce[:, 0] == ce[:, 1]
        args = (slab, edges_dev, jnp.asarray(na), jnp.asarray(nb),
                jnp.asarray(intra))
        k_cap = self._pair_cap
        return ce, args, k_cap, verify_edges_compact(*args, eps2, k_cap)

    def _extract_compact(self, handles, entries, eps2):
        """Fetch a superstep's compacted pairs (+ distances), chunk by
        chunk in edge order. A chunk whose densest edge holds more pairs
        than the capacity it was sent at is re-sent at the next pow2;
        the raise is sticky for later chunks and steps."""
        res, res_d = [], []
        for ce, args, k_cap, out in handles:
            counts = np.asarray(out[0])
            top = int(counts[:ce.shape[0]].max())
            if top > k_cap:
                self.overflows += 1
                self._pair_cap = max(self._pair_cap, min(
                    self._next_pow2(top), self.cap * self.cap))
                out = verify_edges_compact(*args, eps2, self._pair_cap)
                counts = np.asarray(out[0])
            rows_c = np.asarray(out[1])
            cols_c = np.asarray(out[2])
            dist_c = np.sqrt(np.asarray(out[3]))
            for ei, (a, b) in enumerate(ce):
                k = int(counts[ei])
                if k:
                    ida, idb = entries[a][1], entries[b][1]
                    res.append(np.stack([ida[rows_c[ei, :k]],
                                         idb[cols_c[ei, :k]]], axis=1))
                    res_d.append(dist_c[ei, :k].astype(np.float32))
        return res, res_d

    @staticmethod
    def _extract_host(handles, entries):
        """Pairs (+ distances) from each chunk's full mask and d²."""
        res, res_d = [], []
        for ce, out in handles:
            mask = np.asarray(out[1])
            d2 = np.asarray(out[2])
            for ei, (a, b) in enumerate(ce):
                na, nb = entries[a][2], entries[b][2]
                m = mask[ei][:na, :nb]
                if a == b:
                    m = np.triu(m, k=1)
                rows, cols = np.nonzero(m)
                if rows.size:
                    ida, idb = entries[a][1], entries[b][1]
                    res.append(np.stack([ida[rows], idb[cols]], axis=1))
                    res_d.append(np.sqrt(d2[ei][:na, :nb][rows, cols]
                                         ).astype(np.float32))
        return res, res_d

    def fingerprint(self) -> str:
        """Session digest guarding checkpoint compatibility: config +
        bucket layout + store extent. A checkpoint written under a
        different digest must not be resumed into this run."""
        from repro.ft.atomic import fingerprint as _fp
        return _fp({"config": dataclasses.asdict(self.config),
                    "sizes": self.meta.sizes.tolist(),
                    "num_buckets": int(self.meta.num_buckets),
                    "dim": int(self.store.dim)})

    def run(self, graph: BucketGraph, *, checkpointer=None,
            resume_from=None, fault=None):
        """Execute the planned join → (pairs, info).

        ``checkpointer``: a ``repro.ft.JoinCheckpointer`` recording
        superstep progress (the raw emission stream) without ever
        blocking the verify pipeline. ``resume_from``: a checkpoint
        directory path or a ``ResumeState`` — committed supersteps are
        replayed from the spill files and execution restarts at the
        cursor; the final pairs+distances are byte-identical to an
        uninterrupted run. ``fault``: a ``repro.ft.FaultInjector``
        consulted at each superstep boundary (tests/benchmarks only).
        """
        eps2 = float(self.config.epsilon) ** 2
        steps = plan_supersteps(graph, self.config, self.cache_buckets,
                                meta=self.meta)
        pairs_out, dists_out = [], []
        start_si = 0
        restore_s = 0.0
        fp = (self.fingerprint()
              if checkpointer is not None or resume_from is not None
              else None)
        if resume_from is not None:
            from repro.ft import JoinCheckpointer
            rs = resume_from
            if isinstance(rs, str):
                rs = JoinCheckpointer.restore(rs, fingerprint=fp)
            if rs is not None:
                # the committed raw stream, in emission order — replayed
                # verbatim so the final dedup sees the same concatenation
                # an uninterrupted run would
                pairs_out.extend(rs.pairs)
                dists_out.extend(rs.dists)
                start_si = rs.superstep + 1
                restore_s = rs.restore_s
        if checkpointer is not None:
            checkpointer.begin(fp, start_si)
        sharding = None
        if self.mesh is not None:
            sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec("data"))

        dc = 0
        tracer = get_tracer()
        for si, step in enumerate(steps):
            if si < start_si:
                continue  # committed by the restored checkpoint chain
            if fault is not None:
                fault.superstep(si)
            edges = step.edges_local
            if edges.shape[0] == 0:
                # defensive: planner always pairs buckets w/ edges — but
                # the checkpoint cursor must advance through empty steps
                if checkpointer is not None:
                    checkpointer.step_done(si, [], [])
                continue
            step_span = tracer.span("dist.superstep", step=si,
                                    buckets=len(step.bucket_ids),
                                    edges=int(edges.shape[0]))
            step_span.__enter__()
            entries = [self._fetch(int(b)) for b in step.bucket_ids]
            chunks = self._edge_chunks(edges, sharding)
            if self._dev_pool is not None:
                # device mode: the window slab is a stack of per-bucket
                # slabs already resident on-device (one transfer per host
                # residency), and the verify returns compacted pairs
                slab = jnp.stack(
                    [self._dev_pool.operand(int(b), e[0])
                     for b, e in zip(step.bucket_ids, entries)])
                # harvest this window's first-touch buckets as device-
                # resident slices NOW (queue idle): the next overlapping
                # window then stacks device arrays instead of
                # re-transferring staged host copies
                for wi, b in enumerate(step.bucket_ids):
                    if self._dev_pool.needs_harvest(int(b)):
                        self._dev_pool.harvest(int(b), slab[wi])
                handles = [self._dispatch_compact(slab, ce, edges_dev,
                                                  entries, eps2)
                           for ce, edges_dev in chunks]
            else:
                slab = jnp.asarray(np.stack([e[0] for e in entries]))
                handles = [(ce, verify_edges(slab, edges_dev, eps2))
                           for ce, edges_dev in chunks]
            self.dispatches += len(handles)
            # verify is dispatched asynchronously: pull window w+1's
            # missing buckets from disk while this window's kernel runs
            if si + 1 < len(steps):
                self._prefetch_window(steps[si + 1])
            dc += sum(
                (entries[a][2] * entries[b][2]) if a != b
                else entries[a][2] * (entries[a][2] - 1) // 2
                for a, b in edges)
            if self._dev_pool is not None:
                step_pairs, step_dists = self._extract_compact(
                    handles, entries, eps2)
            else:
                step_pairs, step_dists = self._extract_host(handles,
                                                            entries)
            pairs_out.extend(step_pairs)
            dists_out.extend(step_dists)
            if checkpointer is not None:
                checkpointer.step_done(si, step_pairs, step_dists)
            # keep-set is the *upcoming* window: evicting on the finished
            # window's set discards exactly the slabs superstep w+1 reuses
            # (e.g. buckets loaded in w-1 that skip w and return in w+1),
            # while keeping the finished window would park dead slabs
            # above the memory budget
            if si + 1 < len(steps):
                keep = set(int(b) for b in steps[si + 1].bucket_ids)
            else:
                keep = set(int(b) for b in step.bucket_ids)
            self._evict_to(keep)
            step_span.__exit__(None, None, None)

        if checkpointer is not None:
            checkpointer.finish()

        watermark = sum(len(p) for p in pairs_out)
        if pairs_out:
            pairs, dists = dedup_pairs(np.concatenate(pairs_out),
                                       np.concatenate(dists_out))
        else:
            pairs = np.zeros((0, 2), np.int64)
            dists = np.zeros(0, np.float32)
        info = {"supersteps": len(steps), "host_loads": self.loads,
                "host_hits": self.hits, "prefetched_buckets": self.prefetched,
                "distance_computations": dc, "dists": dists,
                "watermark_rows": watermark,
                "verify_dispatches": self.dispatches}
        if resume_from is not None:
            info["resumed_at"] = start_si
            info["restore_s"] = restore_s
        if checkpointer is not None:
            info["ckpt"] = dict(checkpointer.stats)
        if self._dev_pool is not None:
            info["h2d_transfers"] = self._dev_pool.transfers
            info["device_slab_hits"] = self._dev_pool.hits
            info["h2d_bytes"] = self._dev_pool.h2d_bytes
            info["compact_overflows"] = self.overflows
        return pairs, info
