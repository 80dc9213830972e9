"""Verify engines: the executor's batched pair-verification backends.

Both engines replay the same edge stream and produce byte-identical
(pairs, distances) — they differ only in where operands live and where
pair extraction happens (``JoinConfig.compute_mode``):

``HostVerifyEngine`` ("host")
    Stages each batch's operand slabs into a pinned host buffer, runs ONE
    batched kernel dispatch (Pallas grid or vmapped reference — shared
    path, ``kernels.ops.verify_pairs_batch``), fetches the full
    (E, cap, cap) d2/mask arrays and extracts pairs with numpy. Padded
    batch lanes are *masked out* (sliced away per edge), never filled by
    replaying edge 0; partial flushes dispatch at the next power-of-two
    lane count, so a 3-edge final flush pays a 4-lane kernel, not a
    ``verify_batch``-lane one.

``DeviceVerifyEngine`` ("device")
    Operands come from a ``DeviceSlabPool`` that mirrors the host cache
    schedule — each bucket slab crosses H2D once per cache residency, and
    every further edge reference is a ``device_slab_hit``. Host checkout
    pins are released at enqueue (the pool holds an independent copy), so
    pending batches never hold host pool slabs. Dispatch is
    double-buffered: batch k is issued as ONE asynchronous fused jit
    (in-program stack → kernel → compaction; first-touch slabs ride the
    dispatch as plain arguments) and the engine issues no eager device
    work until batch k's results are collected at the head of flush k+1 —
    so the entire enqueue/walk/staging of batch k+1 overlaps batch k's
    kernel (``d2h_overlap_s``). The kernel returns compacted
    (row, col, d²) triples via an on-device mask → prefix-sum →
    gather compaction, so the host never materializes an (E, cap, cap)
    mask.

Distance parity: both modes take d² from the same jitted program and
apply numpy's IEEE float32 sqrt on the host — bitwise identical. (A
TPU's float32 sqrt is not correctly rounded, so a sqrt on the device
would differ from the host engine's in the last bit.) Pair order
parity: the compaction scatter walks the mask in row-major flat order,
exactly ``np.nonzero``'s order.

Device stages carry ``jax.named_scope`` names, which the profiler keeps
on each device op: ``verify.stack`` (operand stack), ``verify.kernel``
(the distance kernel) and ``compact.count`` / ``compact.search`` /
``compact.gather`` (``compact_batch`` / ``compact_pairs``). They are
trace-time metadata and cost nothing at run time.

The join's compaction capacity is pooled over the batch
(``compact_batch``): one capacity bounds the pairs of all lanes, so the
search covers the batch's real total and not every lane's worst case.
It adapts: a batch whose total overflows the current capacity is
re-compacted from its still-resident d2/mask at the next power of two
(the kernel output was sized too small, not wrong), and the larger
capacity sticks for later batches. ``DeviceQueryVerify`` runs a query
wave's probed buckets through the same ``device_verify``; the
distributed path (``core/distributed.verify_edges_compact``) runs
``compact_batch`` on each chip's own lanes, one capacity a chip.
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compute.slab_pool import DeviceSlabPool
from repro.kernels import ops as kops
from repro.obs import compiles, get_tracer

PAIR_CAP_INIT = 1024  # initial per-edge compaction capacity (pairs)


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _kept_flags(mask, na, nb, intra):
    """The pairs a compaction keeps, as (E, M, N) flags: the kernel's
    threshold mask within each lane's live rows (``na``/``nb``, 0 kills a
    padded lane), strictly upper for ``intra`` lanes."""
    E, M, N = mask.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (M, N), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (M, N), 1)
    live = ((rows[None] < na[:, None, None])
            & (cols[None] < nb[:, None, None]))
    tri = (~intra)[:, None, None] | (rows[None] < cols[None])
    return mask & live & tri


SEARCH_BLOCK = 128  # entries a rank gathers per level of _first_at_least


def _first_at_least(a, rows, ks):
    """For each i, the first column c with ``a[rows[i], c] >= ks[i]``, and
    the entry before it (``a[rows[i], c - 1]``, 0 at c = 0), in an (R, n)
    int32 array of non-negative rows that never decrease. Where no entry
    reaches ``ks[i]``, c is n or more.

    A search over blocks of ``SEARCH_BLOCK``: each level gathers one
    contiguous block per rank and counts its entries below the rank, so
    a rank costs about log_128(n) gathers of a block where a binary
    search costs log_2(n) gathers of one entry. One row of up to 16
    blocks is compared whole, with no gather.
    """
    R, n = a.shape
    if n <= SEARCH_BLOCK or (R == 1 and n <= 16 * SEARCH_BLOCK):
        top = a if R == 1 else a[rows]
        below = top < ks[:, None]
        return (jnp.sum(below, axis=1, dtype=jnp.int32),
                jnp.max(jnp.where(below, top, 0), axis=1))
    pad = -n % SEARCH_BLOCK
    if pad:  # repeat each row's last entry: rows still never decrease
        a = jnp.concatenate(
            [a, jnp.broadcast_to(a[:, -1:], (R, pad))], axis=1)
    nblk = a.shape[1] // SEARCH_BLOCK
    blocks = a.reshape(R, nblk, SEARCH_BLOCK)
    # searching the blocks' last entries finds the rank's block, and the
    # entry before that block
    b, before = _first_at_least(blocks[:, :, -1], rows, ks)
    b = jnp.minimum(b, nblk - 1)
    blk = blocks[rows, b]
    below = blk < ks[:, None]
    return (b * SEARCH_BLOCK + jnp.sum(below, axis=1, dtype=jnp.int32),
            jnp.maximum(before, jnp.max(jnp.where(below, blk, 0), axis=1)))


@functools.partial(jax.jit, static_argnames=("k_cap",))
def compact_pairs(d2: jax.Array, mask: jax.Array, na: jax.Array,
                  nb: jax.Array, intra: jax.Array, k_cap: int):
    """On-device pair compaction, one capacity per lane: mask → prefix-sum
    → gather.

    d2/mask: (E, M, N); na/nb: (E,) int32 live-row counts (0 kills a
    padded batch lane); intra: (E,) bool — keep strictly-upper pairs only
    (self-join bucket-vs-itself edges). Returns (counts (E,) int32,
    rows (E, k_cap) int32, cols (E, k_cap) int32, d2 (E, k_cap) f32);
    entries past an edge's count are zeros, pairs past ``k_cap`` are
    dropped (the caller detects counts > k_cap and re-compacts larger).
    The caller takes the sqrt on the host (module docstring: parity).
    """
    E, M, N = d2.shape
    with jax.named_scope("compact.count"):
        flat = _kept_flags(mask, na, nb, intra).reshape(E, M * N)
        counts = jnp.sum(flat, axis=1, dtype=jnp.int32)
        # prefix-sum + binary search: the j-th pair's flat position is the
        # first index where the running count reaches j+1 — row-major flat
        # order == np.nonzero extraction order (host parity). k_cap·log(M·N)
        # searches vectorize where an XLA scatter would serialize per
        # update and a full sort would pay M·N·log(M·N).
        cs = jnp.cumsum(flat, axis=1, dtype=jnp.int32)
    with jax.named_scope("compact.search"):
        ks = jnp.arange(1, k_cap + 1, dtype=jnp.int32)
        order = jax.vmap(lambda c: jnp.searchsorted(c, ks, side="left"))(cs)
    with jax.named_scope("compact.gather"):
        valid = ks[None, :] <= counts[:, None]
        order = jnp.minimum(order, M * N - 1)  # clamp past-count sentinels
        out_r = jnp.where(valid, (order // N).astype(jnp.int32), 0)
        out_c = jnp.where(valid, (order % N).astype(jnp.int32), 0)
        out_d2 = jnp.where(
            valid, jnp.take_along_axis(d2.reshape(E, M * N), order, axis=1),
            0.0)
    return counts, out_r, out_c, out_d2


@functools.partial(jax.jit, static_argnames=("k_cap",))
def compact_batch(d2: jax.Array, mask: jax.Array, na: jax.Array,
                  nb: jax.Array, intra: jax.Array, k_cap: int):
    """On-device pair compaction, one capacity pooled over the batch.

    Same inputs and kept pairs as ``compact_pairs``, but ``k_cap`` bounds
    the batch's total: ranks 1..k_cap are searched once in the running
    count of the batch's E·M·N flags, first for the slab row that holds
    the rank, then for its block of ``SEARCH_BLOCK`` columns in that row,
    then for its column in that block. Returns (counts (E,)
    int32, rows (k_cap,) int32, cols (k_cap,) int32, d2 (k_cap,) f32),
    lane-major: lane e's pairs occupy ``[off[e], off[e] + counts[e])``
    with ``off`` the exclusive cumsum of ``counts``, each lane in
    ``np.nonzero`` order. Counts stay exact past ``k_cap``; only the
    first ``k_cap`` pairs of the batch land (the caller re-compacts at
    the total).
    """
    E, M, N = d2.shape
    if E * M * N >= 2 ** 31:
        raise ValueError(f"batch of {E}x{M}x{N} flags overflows the int32 "
                         "running count")
    R = E * M
    with jax.named_scope("compact.count"):
        kept = _kept_flags(mask, na, nb, intra).reshape(R, N)
        pad = -N % SEARCH_BLOCK
        if pad:
            kept = jnp.pad(kept, ((0, 0), (0, pad)))
        blocks = kept.reshape(R, -1, SEARCH_BLOCK)
        # each slab row's running count at its blocks' ends, and the
        # batch's running count at the rows' ends; the full running count
        # is never formed, only the block a rank lands in
        blk_end = jnp.cumsum(jnp.sum(blocks, axis=2, dtype=jnp.int32),
                             axis=1, dtype=jnp.int32)
        row_n = blk_end[:, -1]
        counts = jnp.sum(row_n.reshape(E, M), axis=1, dtype=jnp.int32)
        row_end = jnp.cumsum(row_n, dtype=jnp.int32)
    with jax.named_scope("compact.search"):
        ks = jnp.arange(1, k_cap + 1, dtype=jnp.int32)
        row, row_start = _first_at_least(row_end[None], jnp.zeros_like(ks),
                                         ks)
        row = jnp.minimum(row, R - 1)
        in_row = ks - row_start
        b, blk_start = _first_at_least(blk_end, row, in_row)
        b = jnp.minimum(b, blocks.shape[1] - 1)
        run = jnp.cumsum(blocks[row, b], axis=1, dtype=jnp.int32)
        col = b * SEARCH_BLOCK + jnp.sum(
            run < (in_row - blk_start)[:, None], axis=1, dtype=jnp.int32)
        col = jnp.minimum(col, N - 1)
    with jax.named_scope("compact.gather"):
        valid = ks <= row_end[-1]
        out_r = jnp.where(valid, row % M, 0)
        out_c = jnp.where(valid, col, 0)
        out_d2 = jnp.where(valid, d2.reshape(R, N)[row, col], 0.0)
    return counts, out_r, out_c, out_d2


@functools.partial(jax.jit, static_argnames=("eps", "k_cap", "use_pallas"))
def device_verify(na, nb, intra, *slabs, eps: float, k_cap: int,
                  use_pallas: bool = False):
    """Fused verify + compaction over individually-resident slabs.

    ``slabs`` is the batch's 2B operand slabs (u lanes then v lanes) as
    separate arguments: the (B, cap, d) stack happens INSIDE the program,
    so the whole batch is ONE asynchronous dispatch — an eager
    ``jnp.stack`` would synchronize with the in-flight previous batch
    and stall the double buffer. First-touch slabs may arrive as numpy
    arrays (their H2D rides the dispatch). ``k_cap`` is the batch's pair
    capacity (``compact_batch``: lane-major flat outputs).
    """
    B = len(slabs) // 2
    with jax.named_scope("verify.stack"):
        u = jnp.stack(slabs[:B])
        v = jnp.stack(slabs[B:])
    with jax.named_scope("verify.kernel"):
        d2, mask = kops.verify_pairs_batch(u, v, eps, use_pallas=use_pallas)
    counts, out_r, out_c, out_d = compact_batch(d2, mask, na, nb, intra,
                                                k_cap)
    # the stacked operands come back as outputs so the engine can harvest
    # first-touch lanes into the device slab pool once the batch lands
    return counts, out_r, out_c, out_d, u, v


class _EngineBase:
    """Shared bookkeeping: edge accounting and result accumulation."""

    def __init__(self, cache, *, epsilon: float, capacity_rows: int,
                 dim: int, verify_batch: int, use_pallas: bool = False,
                 attribute_mask: np.ndarray | None = None, pstats=None,
                 xfer_gb_s: float = 0.0, tracer=None):
        self.cache = cache
        self.eps = float(epsilon)
        self.cap = int(capacity_rows)
        self.dim = int(dim)
        self.verify_batch = max(1, int(verify_batch))
        self.use_pallas = bool(use_pallas)
        self.attribute_mask = attribute_mask
        self.pstats = pstats
        self.tracer = tracer if tracer is not None else get_tracer()
        compiles.install()
        self.xfer_gb_s = float(xfer_gb_s)
        self.dc = 0              # distance computations (live pairs)
        self.compute_s = 0.0     # engine wall time in stage/dispatch/extract
        self.pairs_out: list[np.ndarray] = []
        self.dists_out: list[np.ndarray] = []

    def _count_dc(self, na: int, nb: int, intra: bool) -> None:
        self.dc += na * (na - 1) // 2 if intra else na * nb

    def _stat(self, field: str, amount) -> None:
        if self.pstats is not None:
            self.pstats.add(field, amount)

    def _charge_link(self, nbytes: int) -> None:
        """Emulated host↔device link cost (``emulate_xfer_gb_s``) — the
        transfer-volume analogue of the store's emulated read latency.
        Traced as a ``link.xfer`` span (bytes arg) so the live
        calibrator can derive an observed GB/s for the cost model."""
        if self.xfer_gb_s > 0 and nbytes > 0:
            if self.tracer.enabled:
                t0 = time.perf_counter()
                time.sleep(nbytes / (self.xfer_gb_s * 1e9))
                self.tracer.complete("link.xfer", t0,
                                     time.perf_counter() - t0,
                                     bytes=int(nbytes))
            else:
                time.sleep(nbytes / (self.xfer_gb_s * 1e9))

    def results(self) -> tuple[list[np.ndarray], list[np.ndarray]]:
        return self.pairs_out, self.dists_out

    def evict(self, b: int) -> None:  # device engine overrides
        pass

    def set_verify_batch(self, n: int) -> None:
        """Planner hook: retune the flush threshold between enqueues
        (per schedule region). Only the threshold moves — a pending
        batch larger than the new value flushes at the next enqueue."""
        self.verify_batch = max(1, int(n))

    def set_route(self, route: str) -> None:
        """Planner hook: a single-mode engine ignores routing (the
        routed wrapper overrides)."""

    @property
    def pending(self) -> bool:
        raise NotImplementedError


class HostVerifyEngine(_EngineBase):
    """Host staging + full-mask fetch (the reference compute path)."""

    def __init__(self, cache, **kw):
        super().__init__(cache, **kw)
        self._u = np.empty((self.verify_batch, self.cap, self.dim),
                           np.float32)
        self._v = np.empty_like(self._u)
        self._batch: list[tuple] = []  # (entry_a, entry_b, intra)

    def set_verify_batch(self, n: int) -> None:
        # the staging buffers were sized at construction: a larger plan
        # batch clamps to the allocation rather than reallocating
        self.verify_batch = max(1, min(int(n), self._u.shape[0]))

    @property
    def pending(self) -> bool:
        return bool(self._batch)

    def enqueue(self, bu: int, bv: int, intra: bool) -> None:
        self._batch.append((self.cache.checkout(bu),
                            self.cache.checkout(bv), intra))
        if len(self._batch) >= self.verify_batch:
            self.flush()

    def flush(self) -> None:
        if not self._batch:
            return
        with self.tracer.span("verify.flush", edges=len(self._batch)):
            self._flush()

    def _flush(self) -> None:
        t0 = time.perf_counter()
        E = len(self._batch)
        # partial flushes dispatch at the next pow2 lane count; lanes past
        # E hold stale staging content and are masked out by the per-edge
        # extraction below (no edge-0 replay, no duplicate verification).
        # Clamp to the staging allocation, not the current threshold — a
        # planner region switch may shrink the threshold below a batch
        # accumulated under the previous region's (larger) one.
        B = min(self._u.shape[0], next_pow2(E))
        for i, (ea, eb, _) in enumerate(self._batch):
            self._u[i] = ea[0]
            self._v[i] = eb[0]
        u = jnp.asarray(self._u[:B])
        v = jnp.asarray(self._v[:B])
        staged = 2 * B * self.cap * self.dim * 4
        self._stat("h2d_transfers", 2)
        self._stat("h2d_bytes", staged)
        self._charge_link(staged)
        d2, mask = kops.verify_pairs_batch(u, v, self.eps,
                                           use_pallas=self.use_pallas)
        d2 = np.asarray(d2)
        masks = np.asarray(mask)
        self._stat("d2h_bytes", d2.nbytes + masks.nbytes)
        self._charge_link(d2.nbytes + masks.nbytes)
        attr = self.attribute_mask
        for i, (ea, eb, intra) in enumerate(self._batch):
            na, nb = ea[2], eb[2]
            m = masks[i][:na, :nb]
            if intra:
                m = np.triu(m, k=1)
            self._count_dc(na, nb, intra)
            if attr is not None:
                # slice to the live rows: prefetch-mode id slabs are
                # capacity-padded with -1 past each bucket's rows
                m = m & attr[ea[1][:na]][:, None] & attr[eb[1][:nb]][None, :]
            rows, cols = np.nonzero(m)
            if rows.size:
                d = np.sqrt(d2[i][rows, cols])
                self.pairs_out.append(
                    np.stack([ea[1][rows], eb[1][cols]],
                             axis=1).astype(np.int64))
                self.dists_out.append(d.astype(np.float32))
        for ea, eb, _ in self._batch:  # drop the batch's slab pins
            self.cache.release(ea)
            self.cache.release(eb)
        self._batch.clear()
        self.compute_s += time.perf_counter() - t0

    def finish(self) -> None:
        self.flush()

    def abort(self) -> None:
        # an exception mid-run leaves checkout pins in the pending batch;
        # on a shared (session) pool they would leak for the session's
        # lifetime and starve the next join's liveness floor
        for ea, eb, _ in self._batch:
            self.cache.release(ea)
            self.cache.release(eb)
        self._batch.clear()


class DeviceVerifyEngine(_EngineBase):
    """Device-resident operands + double-buffered compacted dispatch."""

    def __init__(self, cache, **kw):
        pair_cap = kw.pop("pair_cap", None)
        super().__init__(cache, **kw)
        # slab transfers accrue link debt paid in one sleep per flush:
        # hundreds of sub-millisecond sleeps would each round up to the
        # OS timer slack and dwarf the modeled cost
        self._link_debt = 0
        self.pool = DeviceSlabPool(self.pstats,
                                   on_transfer=self._defer_link_charge,
                                   tracer=self.tracer)
        self._batch: list[tuple] = []
        self._inflight: tuple | None = None
        # the batch's pair capacity starts at ~8 pairs per slab row: dense
        # enough that overflow re-compaction (and its recompile) is rare,
        # small enough that the compacted D2H stays ≪ the full mask. A
        # planned per-lane ``pair_cap`` floors it at pair_cap × lanes.
        self.lane_cap = next_pow2(pair_cap) if pair_cap else 0
        self.pair_cap = 0 if pair_cap else min(
            next_pow2(max(PAIR_CAP_INIT, 8 * self.cap)), self.cap * self.cap)

    def _batch_cap(self, lanes: int, total: int = 0) -> int:
        """Pair capacity of a ``lanes``-lane batch: the sticky batch
        capacity, the planned per-lane floor and ``total`` pairs, as a
        power of two, and never more than the batch's flags."""
        k = max(self.pair_cap, self.lane_cap * lanes, next_pow2(total))
        return min(k, lanes * self.cap * self.cap)

    @property
    def pending(self) -> bool:
        # only a staged (undispatched) batch counts: in-flight batches
        # hold no host pins, so a stall-flush has nothing to release
        return bool(self._batch)

    def evict(self, b: int) -> None:
        self.pool.evict(b)

    def enqueue(self, bu: int, bv: int, intra: bool) -> None:
        with self.tracer.span("verify.enqueue"):
            ea = self.cache.checkout(bu)
            eb = self.cache.checkout(bv)
            try:
                da = self.pool.operand(bu, ea[0])
                db = self.pool.operand(bv, eb[0])
                # id sidecars live in recyclable pool slots: copy the live
                # rows so the pins can drop now (the pool operand is
                # already an independent copy)
                meta = (np.array(ea[1][:ea[2]]), ea[2],
                        np.array(eb[1][:eb[2]]), eb[2], intra)
            finally:
                self.cache.release(ea)
                self.cache.release(eb)
        self._batch.append((da, db, bu, bv, meta))
        if len(self._batch) >= self.verify_batch:
            self.flush()

    def flush(self) -> None:
        """Collect the in-flight batch, then dispatch the staged one
        asynchronously. Between this dispatch and the next collect the
        engine issues NO eager device work — on single-stream backends
        any eager op would synchronize with the running kernel — so the
        whole enqueue/walk of the next batch overlaps this one's kernel
        (double buffering)."""
        if not self._batch:
            return
        if self._link_debt:
            # pay accrued transfer debt while the previous batch's kernel
            # is still in flight — on real hardware the DMA overlaps
            # compute, so the modeled link time overlaps it here too
            self._charge_link(self._link_debt)
            self._link_debt = 0
        self._collect()        # previous batch; drains the device queue
        self._dispatch()

    def _dispatch(self) -> None:
        span = self.tracer.span("verify.dispatch", edges=len(self._batch))
        span.__enter__()
        t0 = time.perf_counter()
        E = len(self._batch)
        # pow2 of the actual batch, never below it: the threshold may
        # have been retuned (planner region switch) below the pending E
        B = next_pow2(E)

        def fresh(b, captured):
            # operands were captured at enqueue, possibly before the
            # previous batch's harvest: re-query the pool so a bucket
            # harvested since then rides as a device array instead of
            # re-transferring its staged host copy
            cur = self.pool.current(b)
            return captured if cur is None else cur

        ops_u = [fresh(bu, da) for da, _, bu, _, _ in self._batch]
        ops_v = [fresh(bv, db) for _, db, _, bv, _ in self._batch]
        slabs = (ops_u + [ops_u[0]] * (B - E)
                 + ops_v + [ops_v[0]] * (B - E))
        # na = nb = 0 masks the pad lanes out inside the compaction
        na = np.zeros(B, np.int32)
        nb = np.zeros(B, np.int32)
        intra = np.zeros(B, bool)
        metas = []
        harvest: list[tuple[int, int, int]] = []  # (bucket, side, lane)
        staged: set[int] = set()
        for i, (_, _, bu, bv, (ids_a, n_a, ids_b, n_b, is_intra)) \
                in enumerate(self._batch):
            na[i], nb[i], intra[i] = n_a, n_b, is_intra
            metas.append((ids_a, ids_b))
            self._count_dc(n_a, n_b, is_intra)
            if bu not in staged and self.pool.needs_harvest(bu):
                harvest.append((bu, 0, i))
                staged.add(bu)
            if bv not in staged and self.pool.needs_harvest(bv):
                harvest.append((bv, 1, i))
                staged.add(bv)
        k_cap = self._batch_cap(B)
        out = device_verify(na, nb, intra, *slabs, eps=self.eps,
                            k_cap=k_cap, use_pallas=self.use_pallas)
        self._batch.clear()
        self._stat("device_batches", 1)
        self._inflight = (out, slabs, na, nb, intra, metas, harvest,
                          k_cap, time.perf_counter())
        self.compute_s += time.perf_counter() - t0
        span.__exit__(None, None, None)

    def _defer_link_charge(self, nbytes: int) -> None:
        self._link_debt += nbytes

    def _collect(self) -> None:
        if self._inflight is None:
            return
        (out, slabs, na, nb, intra, metas, harvest, k_cap,
         t_dispatch) = self._inflight
        self._inflight = None
        span = self.tracer.span("verify.collect")
        span.__enter__()
        t0 = time.perf_counter()
        # host time since dispatch ran concurrently with the kernel
        self._stat("d2h_overlap_s", max(0.0, t0 - t_dispatch))
        counts = np.asarray(out[0])
        t_wait = time.perf_counter()
        # the wait and extract spans share their intervals with the
        # device_wait_s / extract_s accumulators (as io.wait does)
        self._stat("device_wait_s", t_wait - t0)
        self.tracer.complete("verify.wait", t0, t_wait - t0)
        t_extract = t_wait
        total = int(counts.sum())
        self._stat("device_compact_slots", k_cap)
        if self.pstats is not None:
            self.pstats.observe_max("device_batch_pairs_max", total)
        if total > k_cap:
            # capacity overflow: the kernel output was sized too small,
            # not wrong — re-dispatch at the next pow2, which sticks
            k_cap = self._batch_cap(na.size, total)
            self.pair_cap = max(self.pair_cap, k_cap)
            self._stat("device_compact_overflows", 1)
            self._stat("device_compact_slots", k_cap)
            out = device_verify(na, nb, intra, *slabs, eps=self.eps,
                                k_cap=k_cap, use_pallas=self.use_pallas)
            counts = np.asarray(out[0])
            t_extract = time.perf_counter()
            self.tracer.complete("verify.recompact", t_wait,
                                 t_extract - t_wait, top=total, k_cap=k_cap)
        # the queue is idle now: slice first-touch lanes out of the
        # stacked operands into the pool (device-resident for later
        # batches of this residency)
        for b, side, lane in harvest:
            self.pool.harvest(b, out[4 + side][lane])
        rows = np.asarray(out[1])
        cols = np.asarray(out[2])
        d2 = np.asarray(out[3])
        fetched = counts.nbytes + rows.nbytes + cols.nbytes + d2.nbytes
        self._stat("d2h_bytes", fetched)
        self._charge_link(fetched)
        dists = np.sqrt(d2[:total])
        ends = np.cumsum(counts)
        attr = self.attribute_mask
        for i, (ids_a, ids_b) in enumerate(metas):
            k = int(counts[i])
            if k == 0:
                continue
            lane = slice(int(ends[i]) - k, int(ends[i]))
            pa = ids_a[rows[lane]]
            pb = ids_b[cols[lane]]
            d = dists[lane]
            if attr is not None:
                keep = attr[pa] & attr[pb]
                pa, pb, d = pa[keep], pb[keep], d[keep]
                if pa.size == 0:
                    continue
            self.pairs_out.append(np.stack([pa, pb], axis=1)
                                  .astype(np.int64))
            self.dists_out.append(d.astype(np.float32))
        t1 = time.perf_counter()
        self._stat("extract_s", t1 - t_extract)
        self.tracer.complete("verify.extract", t_extract, t1 - t_extract)
        self.compute_s += t1 - t0
        span.__exit__(None, None, None)

    def finish(self) -> None:
        self.flush()
        self._collect()

    def abort(self) -> None:
        self._batch.clear()
        self._inflight = None
        self.pool.clear()


class QueryLaneState:
    """What an index session's device query path keeps between waves:
    the query rows a lane holds, the sticky pair capacity, host staging
    buffers and the device-resident zero lanes that pad a dispatch.

    ``rows`` is the session's one query row count (operand u of every
    query lane), so lane shapes never follow a wave's size."""

    def __init__(self, rows: int, capacity_rows: int, dim: int):
        self.rows = int(rows)
        self.cap = int(capacity_rows)
        self.dim = int(dim)
        # ~8 pairs per bucket row, as the join's batches start
        self.pair_cap = next_pow2(max(PAIR_CAP_INIT, 8 * self.cap))
        self._lock = threading.Lock()
        self._staging: list[tuple[np.ndarray, np.ndarray]] = []
        self._pad = None

    def batch_cap(self, lanes: int, lane_cap: int = 0,
                  total: int = 0) -> int:
        """Pair capacity of a ``lanes``-lane batch: the sticky capacity,
        a planned per-lane floor and ``total`` pairs, as a power of two,
        never more than the batch's flags."""
        k = max(self.pair_cap, lane_cap * lanes, next_pow2(total))
        return min(k, lanes * self.rows * self.cap)

    def raise_cap(self, total: int) -> None:
        with self._lock:
            self.pair_cap = max(self.pair_cap, next_pow2(total))

    def take_staging(self, lanes: int) -> tuple[np.ndarray, np.ndarray]:
        with self._lock:
            for i, (u, _) in enumerate(self._staging):
                if u.shape[0] == lanes:
                    return self._staging.pop(i)
        return (np.zeros((lanes, self.rows, self.dim), np.float32),
                np.zeros((lanes, self.cap, self.dim), np.float32))

    def give_back(self, buf: tuple[np.ndarray, np.ndarray]) -> None:
        with self._lock:
            self._staging.append(buf)

    def pad_lanes(self):
        """Zero (u, v) operands kept on the device: a dispatch's pad
        lanes (na = nb = 0) cross no bytes."""
        with self._lock:
            if self._pad is None:
                self._pad = (
                    jax.device_put(np.zeros((self.rows, self.dim),
                                            np.float32)),
                    jax.device_put(np.zeros((self.cap, self.dim),
                                            np.float32)))
            return self._pad


class DeviceQueryVerify:
    """Device verify of one query wave's probed buckets
    (``DiskJoinIndex.execute_probes``, ``compute_mode="device"``).

    Each probed bucket becomes a lane of ``device_verify``: operand u
    holds up to ``state.rows`` of its probing queries (more probers take
    more lanes), operand v the bucket's padded slab, ``na`` the live
    probers, ``nb`` the bucket's rows, ``intra`` False. Up to
    ``verify_batch`` lanes ride one dispatch, padded to a power of two
    with device-resident zero lanes. Lanes are staged into one of two
    host buffers while the other's batch runs, and a batch is collected
    just before the next one goes out. The pair capacity is pooled over
    the batch (``compact_batch``); a batch whose total overflows it is
    re-dispatched at ``next_pow2(total)``, and the raise sticks in
    ``state`` for the session. ``finish`` returns the wave's (query,
    id, distance) triples, the sqrt taken on the host.
    """

    def __init__(self, state: QueryLaneState, Q: np.ndarray, *,
                 epsilon: float, verify_batch: int, use_pallas: bool,
                 lane_cap: int = 0, pstats=None, tracer=None):
        self.s = state
        self.Q = Q
        self.eps = float(epsilon)
        self.verify_batch = max(1, int(verify_batch))
        self.use_pallas = bool(use_pallas)
        self.lane_cap = next_pow2(lane_cap) if lane_cap else 0
        self.pstats = pstats
        self.tracer = tracer if tracer is not None else get_tracer()
        compiles.install()
        self._bufs = [state.take_staging(self.verify_batch),
                      state.take_staging(self.verify_batch)]
        self._cur = 0
        self._lanes: list[tuple] = []    # (query rows, ids, bucket rows)
        self._inflight: tuple | None = None
        self._out: list[tuple] = []
        self.lanes = self.dispatches = self.overflows = 0
        self._t_first: float | None = None

    def _stat(self, field: str, amount) -> None:
        if self.pstats is not None:
            self.pstats.add(field, amount)

    def add(self, qidx, vecs: np.ndarray, ids: np.ndarray, n: int) -> None:
        """Stage bucket rows ``vecs[:n]`` (ids ``ids[:n]``) against the
        wave's queries ``qidx``. ``vecs`` may be a recyclable pool slab:
        it is copied before this returns."""
        qidx = np.asarray(qidx, np.int64)
        ids = np.array(ids[:n])
        for c0 in range(0, qidx.size, self.s.rows):
            chunk = qidx[c0:c0 + self.s.rows]
            u, v = self._bufs[self._cur]
            e = len(self._lanes)
            u[e, :chunk.size] = self.Q[chunk]
            v[e, :n] = vecs[:n]   # rows past nb are never kept
            self._lanes.append((chunk, ids, n))
            if len(self._lanes) == self.verify_batch:
                self._flush()

    def _flush(self) -> None:
        self._collect()
        self._dispatch()

    def _dispatch(self) -> None:
        t0 = time.perf_counter()
        if self._t_first is None:
            self._t_first = t0
        lanes, self._lanes = self._lanes, []
        E, B = len(lanes), next_pow2(len(lanes))
        u, v = self._bufs[self._cur]
        self._cur ^= 1
        pu, pv = self.s.pad_lanes()
        slabs = ([u[i] for i in range(E)] + [pu] * (B - E)
                 + [v[i] for i in range(E)] + [pv] * (B - E))
        na = np.zeros(B, np.int32)
        nb = np.zeros(B, np.int32)
        for i, (chunk, _, n) in enumerate(lanes):
            na[i], nb[i] = chunk.size, n
        intra = np.zeros(B, bool)
        k_cap = self.s.batch_cap(B, self.lane_cap)
        out = device_verify(na, nb, intra, *slabs, eps=self.eps,
                            k_cap=k_cap, use_pallas=self.use_pallas)
        sent = E * (self.s.rows + self.s.cap) * self.s.dim * 4
        self.lanes += E
        self.dispatches += 1
        self._stat("query_device_batches", 1)
        self._stat("query_distance_computations",
                   int(np.dot(na.astype(np.int64), nb)))
        self._stat("h2d_transfers", 2 * E)
        self._sent(sent)
        self._inflight = (out, slabs, na, nb, intra, lanes, k_cap, sent)

    def _sent(self, nbytes: int) -> None:
        self._stat("query_h2d_bytes", nbytes)
        self._stat("h2d_bytes", nbytes)

    def _collect(self) -> None:
        if self._inflight is None:
            return
        out, slabs, na, nb, intra, lanes, k_cap, sent = self._inflight
        self._inflight = None
        counts = np.asarray(out[0])
        total = int(counts.sum())
        if total > k_cap:
            # the output was sized too small, not wrong: re-dispatch
            # the still-staged lanes at the next pow2, which sticks
            self.s.raise_cap(total)
            k_cap = self.s.batch_cap(na.size, self.lane_cap, total)
            out = device_verify(na, nb, intra, *slabs, eps=self.eps,
                                k_cap=k_cap, use_pallas=self.use_pallas)
            counts = np.asarray(out[0])
            self.dispatches += 1
            self.overflows += 1
            self._stat("query_compact_overflows", 1)
            self._sent(sent)
        rows = np.asarray(out[1])[:total]
        cols = np.asarray(out[2])[:total]
        dists = np.sqrt(np.asarray(out[3])[:total])
        self._stat("d2h_bytes", counts.nbytes + 3 * 4 * k_cap)
        off = 0
        for (chunk, ids, _), k in zip(lanes, counts.tolist()):
            if k:
                lane = slice(off, off + k)
                self._out.append((chunk[rows[lane]], ids[cols[lane]],
                                  dists[lane]))
                off += k

    def finish(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Dispatch what is staged, collect everything, and return the
        wave's (query index, id, float32 distance) triples."""
        if self._lanes:
            self._flush()
        self._collect()
        if self._t_first is not None:
            self.tracer.complete(
                "query.verify", self._t_first,
                time.perf_counter() - self._t_first, lanes=self.lanes,
                dispatches=self.dispatches, overflows=self.overflows)
        self.close()
        if not self._out:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float32))
        q, ids, d = zip(*self._out)
        return (np.concatenate(q), np.concatenate(ids).astype(np.int64),
                np.concatenate(d).astype(np.float32))

    def close(self) -> None:
        """Hand the staging buffers back (idempotent); an unfinished
        wave's in-flight batch is dropped."""
        self._inflight = None
        for buf in self._bufs:
            self.s.give_back(buf)
        self._bufs = []


class RoutedVerifyEngine:
    """Mixed host/device routing under one engine surface.

    The planner's ``JoinPlan`` may route each verify unit to whichever
    path models cheaper; this wrapper owns one engine of each kind and
    forwards every enqueue to the route selected via ``set_route``
    (called by the executor from the plan cursor, immediately before the
    enqueue). Cache evictions reach both engines — the device slab pool
    must mirror the host cache schedule even for buckets whose edges all
    ran host-side — and results concatenate: duplicate pairs across the
    two engines carry byte-identical distances (both paths take d² from
    the same jitted program + IEEE f32 sqrt), so the executor's
    ``dedup_pairs`` is order-insensitive and planner-on results stay
    byte-identical to single-engine runs.
    """

    def __init__(self, host: HostVerifyEngine, device: DeviceVerifyEngine):
        self.host = host
        self.device = device
        self._target = host

    def set_route(self, route: str) -> None:
        self._target = self.device if route == "device" else self.host

    def set_verify_batch(self, n: int) -> None:
        self._target.set_verify_batch(n)

    def enqueue(self, bu: int, bv: int, intra: bool) -> None:
        self._target.enqueue(bu, bv, intra)

    def flush(self) -> None:
        self.host.flush()
        self.device.flush()

    def finish(self) -> None:
        self.host.finish()
        self.device.finish()

    def abort(self) -> None:
        self.host.abort()
        self.device.abort()

    def evict(self, b: int) -> None:
        self.host.evict(b)
        self.device.evict(b)

    @property
    def pending(self) -> bool:
        return self.host.pending or self.device.pending

    @property
    def dc(self) -> int:
        return self.host.dc + self.device.dc

    @property
    def compute_s(self) -> float:
        return self.host.compute_s + self.device.compute_s

    def results(self) -> tuple[list[np.ndarray], list[np.ndarray]]:
        hp, hd = self.host.results()
        dp, dd = self.device.results()
        return hp + dp, hd + dd


def make_verify_engine(config, cache, capacity_rows: int, dim: int,
                       attribute_mask=None, pstats=None, tracer=None,
                       plan=None):
    """Engine per ``JoinConfig.compute_mode`` ("host" | "device"), or per
    the ``JoinPlan``'s resolved routing when one is supplied: the plan's
    ``pair_cap`` seeds the device compaction capacity, and a "mixed"
    plan gets a ``RoutedVerifyEngine`` wrapping one engine of each kind.
    """
    kw = dict(epsilon=float(config.epsilon), capacity_rows=capacity_rows,
              dim=dim, verify_batch=int(config.verify_batch),
              use_pallas=bool(config.use_pallas),
              attribute_mask=attribute_mask, pstats=pstats,
              tracer=tracer, xfer_gb_s=float(config.emulate_xfer_gb_s))
    mode = plan.compute_mode if plan is not None else config.compute_mode
    pair_cap = plan.pair_cap if plan is not None else None
    if mode == "mixed":
        return RoutedVerifyEngine(
            HostVerifyEngine(cache, **kw),
            DeviceVerifyEngine(cache, pair_cap=pair_cap, **kw))
    if mode == "device":
        return DeviceVerifyEngine(cache, pair_cap=pair_cap, **kw)
    return HostVerifyEngine(cache, **kw)
