"""Device-resident verify pipeline (repro.compute): host/device parity
matrix (pairs AND distances byte-identical), device slab-pool residency
accounting (transfers == residencies, not edges), on-device compaction
vs np.nonzero, verify_batch config, batched Pallas dispatch, distributed
device mode + next-window prefetch, and the device query path."""
import numpy as np
import pytest


def _store(x, tmp_path, name):
    from repro.store.vector_store import FlatVectorStore
    return FlatVectorStore.from_array(str(tmp_path / name), x)


# ---------------------------------------------------------------------------
# host/device parity matrix — the engines must agree byte for byte
# ---------------------------------------------------------------------------
class TestHostDeviceParity:
    @pytest.mark.parametrize("io_mode,devices", [
        ("sync", 1), ("prefetch", 1), ("sync", 4), ("prefetch", 4)])
    def test_self_join_byte_identical(self, small_dataset, tmp_path,
                                      io_mode, devices):
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join

        x, eps = small_dataset
        base = dict(epsilon=eps, pad_align=64, num_buckets=24,
                    memory_budget_bytes=1 << 20, io_mode=io_mode,
                    io_devices=devices,
                    io_batch_reads=devices > 1, io_coalesce=devices > 1)
        rh = similarity_self_join(_store(x, tmp_path, "h.bin"),
                                  JoinConfig(compute_mode="host", **base))
        rd = similarity_self_join(_store(x, tmp_path, "d.bin"),
                                  JoinConfig(compute_mode="device", **base))
        assert rh.pairs.shape[0] > 0
        assert np.array_equal(rh.pairs, rd.pairs)
        assert np.array_equal(rh.distances, rd.distances)  # byte-identical
        assert rh.num_distance_computations == rd.num_distance_computations
        assert rh.bucket_loads == rd.bucket_loads  # same schedule replay

    @pytest.mark.parametrize("io_mode,devices", [
        ("sync", 1), ("prefetch", 1), ("prefetch", 4)])
    def test_cross_join_byte_identical(self, tmp_path, io_mode, devices):
        from repro.core import JoinConfig
        from repro.core.join import similarity_cross_join
        from repro.data import clustered_vectors

        rng = np.random.default_rng(3)
        x = clustered_vectors(2000, 32, seed=5)
        y = (x[:1200] + rng.normal(scale=0.05, size=(1200, 32))
             ).astype(np.float32)
        base = dict(epsilon=0.3, pad_align=64, num_buckets=16,
                    memory_budget_bytes=1 << 20, io_mode=io_mode,
                    io_devices=devices,
                    io_batch_reads=devices > 1, io_coalesce=devices > 1)
        rh = similarity_cross_join(_store(x, tmp_path, "xh"),
                                   _store(y, tmp_path, "yh"),
                                   JoinConfig(compute_mode="host", **base))
        rd = similarity_cross_join(_store(x, tmp_path, "xd"),
                                   _store(y, tmp_path, "yd"),
                                   JoinConfig(compute_mode="device",
                                              **base))
        assert rh.pairs.shape[0] > 0
        assert np.array_equal(rh.pairs, rd.pairs)
        assert np.array_equal(rh.distances, rd.distances)

    def test_attribute_mask_parity(self, small_dataset, tmp_path):
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join

        x, eps = small_dataset
        mask = np.arange(x.shape[0]) % 3 != 0
        base = dict(epsilon=eps, pad_align=64, num_buckets=16,
                    memory_budget_bytes=1 << 20)
        rh = similarity_self_join(_store(x, tmp_path, "ah"),
                                  JoinConfig(**base), attribute_mask=mask)
        rd = similarity_self_join(_store(x, tmp_path, "ad"),
                                  JoinConfig(compute_mode="device", **base),
                                  attribute_mask=mask)
        assert rh.pairs.shape[0] > 0
        assert mask[rd.pairs].all()
        assert np.array_equal(rh.pairs, rd.pairs)
        assert np.array_equal(rh.distances, rd.distances)

    @pytest.mark.parametrize("vb", [1, 5, 32])
    def test_verify_batch_sizes_agree(self, small_dataset, tmp_path, vb):
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join

        x, eps = small_dataset
        x = x[:1500]
        base = dict(epsilon=eps, pad_align=64, num_buckets=12,
                    memory_budget_bytes=1 << 20)
        ref = similarity_self_join(_store(x, tmp_path, f"r{vb}"),
                                   JoinConfig(**base))
        for cm in ("host", "device"):
            r = similarity_self_join(
                _store(x, tmp_path, f"{cm}{vb}"),
                JoinConfig(compute_mode=cm, verify_batch=vb, **base))
            assert np.array_equal(ref.pairs, r.pairs)
            assert np.array_equal(ref.distances, r.distances)

    def test_pallas_path_parity(self, tmp_path):
        """Pallas (interpret) and device mode share the batched dispatch:
        use_pallas host vs use_pallas device must stay byte-identical."""
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join
        from repro.data import clustered_vectors, epsilon_for_avg_neighbors

        x = clustered_vectors(900, 32, seed=5)
        eps = epsilon_for_avg_neighbors(x, 8)
        base = dict(epsilon=eps, pad_align=64, num_buckets=8,
                    memory_budget_bytes=1 << 19, use_pallas=True)
        rp = similarity_self_join(_store(x, tmp_path, "p"),
                                  JoinConfig(**base))
        rd = similarity_self_join(_store(x, tmp_path, "pd"),
                                  JoinConfig(compute_mode="device", **base))
        rr = similarity_self_join(_store(x, tmp_path, "pr"),
                                  JoinConfig(**{**base,
                                               "use_pallas": False}))
        assert np.array_equal(rp.pairs, rd.pairs)
        assert np.array_equal(rp.distances, rd.distances)
        # pallas vs reference kernel: same pair set (bit-level d2 may
        # differ between the two accumulation orders)
        assert set(map(tuple, rp.pairs.tolist())) == \
            set(map(tuple, rr.pairs.tolist()))

    def test_config_validation(self):
        from repro.core import JoinConfig
        from repro.core.types import QueryConfig

        with pytest.raises(ValueError, match="compute_mode"):
            JoinConfig(epsilon=0.1, compute_mode="gpu")
        with pytest.raises(ValueError, match="verify_batch"):
            JoinConfig(epsilon=0.1, verify_batch=0)
        with pytest.raises(ValueError, match="verify_batch"):
            QueryConfig(epsilon=0.1, verify_batch=-1)
        # both are query-time: per-call overrides must be accepted
        from repro.core.types import QUERY_TIME_FIELDS
        assert {"compute_mode", "verify_batch",
                "emulate_xfer_gb_s"} <= QUERY_TIME_FIELDS


# ---------------------------------------------------------------------------
# device slab pool: transfers bounded by residencies, not edges
# ---------------------------------------------------------------------------
class TestDeviceSlabPool:
    def test_operand_transfers_once_per_residency(self):
        from repro.compute import DeviceSlabPool

        pool = DeviceSlabPool()
        slab = np.ones((8, 4), np.float32)
        pool.operand(3, slab)
        for _ in range(5):
            pool.operand(3, slab)      # resident: no new transfer
        assert (pool.transfers, pool.hits) == (1, 5)
        pool.evict(3)
        pool.operand(3, slab)          # new residency: one new transfer
        assert pool.transfers == 2
        assert pool.h2d_bytes == 2 * slab.nbytes

    def test_staged_operand_harvested_to_device(self):
        import jax

        from repro.compute import DeviceSlabPool

        pool = DeviceSlabPool()
        slab = np.arange(12, dtype=np.float32).reshape(3, 4)
        first = pool.operand(7, slab)
        assert isinstance(first, np.ndarray)  # staged host copy
        assert pool.needs_harvest(7)
        dev = jax.device_put(slab)
        pool.harvest(7, dev)
        assert not pool.needs_harvest(7)
        assert pool.operand(7, slab) is dev   # later batches go device

    def test_executor_transfers_equal_residencies(self, tmp_path):
        """End to end under a tight budget: every verified residency is
        exactly one H2D transfer — edges re-touching a resident bucket
        hit the device pool instead of re-staging."""
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join
        from repro.data import clustered_vectors, epsilon_for_avg_neighbors

        x = clustered_vectors(3000, 32, seed=7, clusters=6)
        eps = epsilon_for_avg_neighbors(x, 15)
        cfg = JoinConfig(epsilon=eps, pad_align=64, num_buckets=10,
                         memory_budget_bytes=200_000,  # forces evictions
                         compute_mode="device")
        res = similarity_self_join(_store(x, tmp_path, "t"), cfg)
        p = res.io_stats["pipeline"]
        # with ~300-row buckets every residency carries an intra edge, so
        # every load is verified: transfers == loads == residencies
        assert p["h2d_transfers"] == res.bucket_loads
        assert res.bucket_loads > cfg.num_buckets  # evictions + reloads
        assert p["h2d_transfers_saved"] > 0
        assert p["device_slab_hits"] == p["h2d_transfers_saved"]
        # and strictly below the per-edge staging baseline: 2 operand
        # stagings per edge reference
        refs = p["h2d_transfers"] + p["h2d_transfers_saved"]
        assert p["h2d_transfers"] < refs

    def test_host_vs_device_h2d_bytes(self, small_dataset, tmp_path):
        """Acceptance gate: device h2d volume strictly below the host
        per-edge staging baseline on the same join."""
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join

        x, eps = small_dataset
        base = dict(epsilon=eps, pad_align=64, num_buckets=24,
                    memory_budget_bytes=1 << 20, io_mode="prefetch")
        rh = similarity_self_join(_store(x, tmp_path, "bh"),
                                  JoinConfig(compute_mode="host", **base))
        rd = similarity_self_join(_store(x, tmp_path, "bd"),
                                  JoinConfig(compute_mode="device", **base))
        ph = rh.io_stats["pipeline"]
        pd = rd.io_stats["pipeline"]
        assert 0 < pd["h2d_bytes"] < ph["h2d_bytes"]
        assert 0 < pd["d2h_bytes"] < ph["d2h_bytes"]


# ---------------------------------------------------------------------------
# on-device compaction kernel
# ---------------------------------------------------------------------------
class TestCompaction:
    def _mask_case(self, seed=0, E=3, M=24, N=17, thresh=0.2):
        rng = np.random.default_rng(seed)
        d2 = rng.random((E, M, N)).astype(np.float32)
        mask = d2 <= thresh
        return d2, mask

    def test_matches_nonzero_order_and_values(self):
        import jax.numpy as jnp

        from repro.compute import compact_pairs

        d2, mask = self._mask_case()
        E, M, N = d2.shape
        na = np.array([M, M - 5, 0], np.int32)   # lane 2 masked out
        nb = np.array([N, N - 3, N], np.int32)
        intra = np.array([False, True, False])
        counts, r, c, d = [np.asarray(o) for o in compact_pairs(
            jnp.asarray(d2), jnp.asarray(mask), jnp.asarray(na),
            jnp.asarray(nb), jnp.asarray(intra), 256)]
        for e in range(E):
            m = mask[e][:na[e], :nb[e]]
            if intra[e]:
                m = np.triu(m, k=1)
            rows, cols = np.nonzero(m)
            k = rows.size
            assert counts[e] == k
            assert np.array_equal(r[e, :k], rows)
            assert np.array_equal(c[e, :k], cols)
            np.testing.assert_array_equal(d[e, :k], d2[e][rows, cols])
        assert counts[2] == 0  # na = 0 kills the padded lane

    def test_overflow_reports_true_count(self):
        import jax.numpy as jnp

        from repro.compute import compact_pairs

        d2, mask = self._mask_case(thresh=0.9)  # dense: many pairs
        E, M, N = d2.shape
        k_cap = 8
        na = np.full(E, M, np.int32)
        nb = np.full(E, N, np.int32)
        counts, r, c, d = [np.asarray(o) for o in compact_pairs(
            jnp.asarray(d2), jnp.asarray(mask), jnp.asarray(na),
            jnp.asarray(nb), jnp.asarray(np.zeros(E, bool)), k_cap)]
        true_counts = mask.sum((1, 2))
        assert np.array_equal(counts, true_counts)  # exact despite overflow
        assert (true_counts > k_cap).all()
        # the k_cap entries that did land are the FIRST pairs in
        # row-major order
        rows, cols = np.nonzero(mask[0])
        assert np.array_equal(r[0], rows[:k_cap])
        assert np.array_equal(c[0], cols[:k_cap])

    @pytest.mark.parametrize("R,n", [
        (1, 1), (1, 128), (1, 129), (1, 70000), (5, 300), (3, 2048)])
    def test_block_search_matches_searchsorted(self, R, n):
        import jax.numpy as jnp

        from repro.compute.engine import _first_at_least

        rng = np.random.default_rng(n)
        a = np.cumsum(rng.random((R, n)) < 0.3, axis=1).astype(np.int32)
        rows = rng.integers(0, R, 500).astype(np.int32)
        ks = rng.integers(0, a.max() + 3, 500).astype(np.int32)
        ks[:5] = a.max() + 1
        got, before = [np.asarray(o) for o in _first_at_least(
            jnp.asarray(a), jnp.asarray(rows), jnp.asarray(ks))]
        want = np.array([np.searchsorted(a[r], k, side="left")
                         for r, k in zip(rows, ks)])
        found = want < n
        assert found.any() and not found.all()
        assert np.array_equal(got[found], want[found])
        assert (got[~found] >= n).all()  # no entry reaches the rank
        prev = np.where(want > 0, a[rows, np.maximum(want - 1, 0)], 0)
        assert np.array_equal(before[found], prev[found])

    @staticmethod
    def _lanes_nonzero(d2, mask, na, nb, intra):
        """Per-lane ``np.nonzero`` pairs, concatenated lane-major."""
        rows, cols, vals, counts = [], [], [], []
        for e in range(d2.shape[0]):
            m = mask[e][:na[e], :nb[e]]
            if intra[e]:
                m = np.triu(m, k=1)
            r, c = np.nonzero(m)
            rows.append(r)
            cols.append(c)
            vals.append(d2[e][r, c])
            counts.append(r.size)
        return (np.array(counts), np.concatenate(rows),
                np.concatenate(cols), np.concatenate(vals))

    @pytest.mark.parametrize("E,M,N,k_cap,overflow", [
        (1, 24, 17, 256, False), (3, 24, 17, 512, False),
        (8, 24, 17, 1024, False), (8, 24, 17, 128, True),
        (4, 136, 260, 32768, False)])
    def test_batch_matches_lane_major_nonzero(self, E, M, N, k_cap,
                                              overflow):
        import jax.numpy as jnp

        from repro.compute import compact_batch

        d2, mask = self._mask_case(seed=E, E=E, M=M, N=N)
        lane = np.arange(E)
        na = np.where(lane % 4 == 2, 0, M - lane).astype(np.int32)
        nb = np.full(E, N, np.int32)
        intra = lane % 3 == 1
        counts, r, c, d = [np.asarray(o) for o in compact_batch(
            jnp.asarray(d2), jnp.asarray(mask), jnp.asarray(na),
            jnp.asarray(nb), jnp.asarray(intra), k_cap)]
        want_counts, rows, cols, vals = self._lanes_nonzero(
            d2, mask, na, nb, intra)
        assert r.shape == c.shape == d.shape == (k_cap,)
        assert np.array_equal(counts, want_counts)  # exact past k_cap
        total = int(want_counts.sum())
        # the overflow case: the batch exceeds k_cap, no lane does
        assert (total > k_cap) == overflow
        assert want_counts.max() <= k_cap
        k = min(total, k_cap)
        assert np.array_equal(r[:k], rows[:k])
        assert np.array_equal(c[:k], cols[:k])
        np.testing.assert_array_equal(d[:k], vals[:k])
        assert not (r[k:].any() or c[k:].any() or d[k:].any())
        if E >= 3:
            assert counts[2] == 0  # na = 0 kills the lane

    def test_executor_overflow_recovery(self, tmp_path):
        """A pair-dense workload whose first batches overflow the initial
        compaction capacity must still match host results exactly."""
        from repro.core import JoinConfig
        from repro.core.join import similarity_self_join
        from repro.compute import engine as eng

        rng = np.random.default_rng(11)
        # one tight clump: nearly all pairs within ε of each other
        x = (rng.normal(scale=0.02, size=(600, 16))).astype(np.float32)
        base = dict(epsilon=1.0, pad_align=64, num_buckets=4,
                    memory_budget_bytes=1 << 19, prune=False)
        rh = similarity_self_join(_store(x, tmp_path, "oh"),
                                  JoinConfig(**base))
        old = eng.PAIR_CAP_INIT
        try:
            eng.PAIR_CAP_INIT = 8  # force the overflow path
            rd = similarity_self_join(
                _store(x, tmp_path, "od"),
                JoinConfig(compute_mode="device", **base))
        finally:
            eng.PAIR_CAP_INIT = old
        assert rh.pairs.shape[0] > 1000
        assert np.array_equal(rh.pairs, rd.pairs)
        assert np.array_equal(rh.distances, rd.distances)
        pipe = rd.io_stats["pipeline"]
        assert pipe["device_compact_overflows"] >= 1
        assert pipe["device_batch_pairs_max"] > 8
        # every returned pair sat in a compaction slot
        assert pipe["device_compact_slots"] >= rd.pairs.shape[0]


# ---------------------------------------------------------------------------
# batched kernel dispatch (the use_pallas per-edge loop fix)
# ---------------------------------------------------------------------------
class TestBatchedKernel:
    def test_batched_pallas_matches_reference(self):
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        rng = np.random.default_rng(0)
        u = rng.normal(size=(4, 64, 32)).astype(np.float32)
        v = rng.normal(size=(4, 64, 32)).astype(np.float32)
        d2r, mr = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v),
                                          1.2)
        d2p, mp = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v),
                                          1.2, use_pallas=True)
        np.testing.assert_allclose(np.asarray(d2r), np.asarray(d2p),
                                   atol=1e-4)
        assert np.array_equal(np.asarray(mr), np.asarray(mp))

    def test_batched_pallas_pads_odd_shapes(self):
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        rng = np.random.default_rng(1)
        u = rng.normal(size=(2, 192, 160)).astype(np.float32)
        v = rng.normal(size=(2, 192, 160)).astype(np.float32)
        d2r, mr = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v),
                                          4.0)
        d2p, mp = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v),
                                          4.0, use_pallas=True)
        assert d2p.shape == (2, 192, 192)
        np.testing.assert_allclose(np.asarray(d2r), np.asarray(d2p),
                                   atol=1e-3)


# ---------------------------------------------------------------------------
# distributed join: device slabs + next-window prefetch
# ---------------------------------------------------------------------------
class TestDistributedDevice:
    def _setup(self, tmp_path, budget):
        from repro.core import JoinConfig, build_bucket_graph, bucketize
        from repro.data import clustered_vectors, epsilon_for_avg_neighbors

        x = clustered_vectors(3000, 32, seed=5)
        eps = epsilon_for_avg_neighbors(x, 10)
        cfg = dict(epsilon=eps, recall_target=0.95, pad_align=64,
                   memory_budget_bytes=budget, num_buckets=24)
        store = _store(x, tmp_path, "x.bin")
        bs, meta, _ = bucketize(store, str(tmp_path / "bk"),
                                JoinConfig(**cfg))
        graph = build_bucket_graph(meta, JoinConfig(**cfg))
        return bs, meta, graph, cfg

    def test_device_mode_identical_pairs(self, tmp_path):
        from repro.core import JoinConfig
        from repro.core.distributed import DistributedJoin

        bs, meta, graph, cfg = self._setup(tmp_path, 150_000)
        ph, ih = DistributedJoin(bs, meta, JoinConfig(**cfg)).run(graph)
        pd, idv = DistributedJoin(
            bs, meta, JoinConfig(compute_mode="device", **cfg)).run(graph)
        assert np.array_equal(ph, pd)
        assert ih["supersteps"] > 1
        assert ih["host_loads"] == idv["host_loads"]
        # device transfers bounded by host residencies
        assert idv["h2d_transfers"] <= idv["host_loads"]
        assert idv["device_slab_hits"] > 0

    def test_next_window_prefetch_overlaps(self, tmp_path):
        """ROADMAP item: window w+1's missing buckets are pulled while
        window w verifies — loads unchanged, most issued as prefetch."""
        from repro.core import JoinConfig
        from repro.core.distributed import DistributedJoin

        bs, meta, graph, cfg = self._setup(tmp_path, 150_000)
        _, info = DistributedJoin(bs, meta, JoinConfig(**cfg)).run(graph)
        assert info["supersteps"] > 1
        assert info["prefetched_buckets"] > 0
        # prefetched loads are a subset of total loads (never extra I/O)
        assert info["prefetched_buckets"] <= info["host_loads"]

    def test_overflow_in_one_chunk_keeps_later_chunks_whole(self, tmp_path):
        """A superstep's chunks are all sent before any is read. When one
        overflows and raises the pair capacity, a later chunk sent at the
        old capacity must be re-sent, not read truncated."""
        from repro.core import JoinConfig
        from repro.core.distributed import DistributedJoin

        bs, meta, graph, cfg = self._setup(tmp_path, 150_000)
        cfg["verify_batch"] = 1  # one edge per chunk
        ph, ih = DistributedJoin(bs, meta, JoinConfig(**cfg)).run(graph)
        dj = DistributedJoin(bs, meta,
                             JoinConfig(compute_mode="device", **cfg))
        dj._pair_cap = 1  # the first chunk to hold two pairs overflows
        pd, idv = dj.run(graph)
        assert idv["verify_dispatches"] > 3 * idv["supersteps"]
        assert ih["verify_dispatches"] == idv["verify_dispatches"]
        assert idv["compact_overflows"] >= 1
        assert np.array_equal(ph, pd)
        assert np.array_equal(ih["dists"], idv["dists"])


# ---------------------------------------------------------------------------
# online queries through the device path
# ---------------------------------------------------------------------------
class TestQueryDevice:
    def test_query_batch_device_parity(self, small_dataset, tmp_path):
        from repro.core import DiskJoinIndex, JoinConfig

        x, eps = small_dataset
        store = _store(x, tmp_path, "q.bin")
        cfg = JoinConfig(epsilon=eps, pad_align=64, num_buckets=32,
                         memory_budget_bytes=1 << 20)
        with DiskJoinIndex.build(store, cfg,
                                 str(tmp_path / "idx")) as index:
            Q = x[:30] + 0.001
            host = index.query_batch(Q)
            base = index.pipeline_snapshot()
            dev = index.query_batch(Q, compute_mode="device")
            snap = index.pipeline_snapshot()
            for (ih, dh), (idv, ddv) in zip(host, dev):
                oh, od = np.argsort(ih), np.argsort(idv)
                assert np.array_equal(np.sort(ih), np.sort(idv))
                # both take d² in float32, by different formulations
                # (the host accumulates in float64): close, not
                # byte-identical
                np.testing.assert_allclose(np.asarray(dh)[oh],
                                           np.asarray(ddv)[od], atol=1e-3)
            # the wave's lanes went out in batched device_verify
            # dispatches, and every byte sent is counted for queries
            delta = {k: snap[k] - base[k] for k in (
                "h2d_bytes", "query_h2d_bytes", "query_device_batches",
                "query_distance_computations")}
            assert delta["query_device_batches"] >= 1
            assert delta["query_h2d_bytes"] == delta["h2d_bytes"] > 0
            assert delta["query_distance_computations"] > 0


def _query_index(tmp_path, x, eps, **kw):
    """A small index whose candidate sets are complete (no Eq. 3
    pruning, every bucket a candidate), so an ε-range answer is exact up
    to float32 rounding."""
    from repro.core import DiskJoinIndex, JoinConfig
    cfg = JoinConfig(**{**dict(epsilon=eps, pad_align=64, num_buckets=16,
                               memory_budget_bytes=1 << 20, prune=False,
                               max_candidates=64, compute_mode="device",
                               verify_batch=4), **kw})
    return DiskJoinIndex.build(_store(x, tmp_path, "rq.bin"), cfg,
                               str(tmp_path / "rq"))


def _range_truth(x, Q, eps):
    """Plain float64 brute-force ε-range answers: per query, the ids
    within ε and their squared distances."""
    x64, q64 = x.astype(np.float64), Q.astype(np.float64)
    d2 = ((q64[:, None, :] - x64[None, :, :]) ** 2).sum(-1)
    return [(np.flatnonzero(r <= eps * eps), r) for r in d2]


def _as_dict(ids, dists):
    assert ids.size == np.unique(ids).size  # no id twice in an answer
    return dict(zip(ids.tolist(), np.asarray(dists, np.float64).tolist()))


class TestQueryDeviceBatched:
    """The device query path batches a wave's probed buckets into
    ``device_verify`` lanes (``compute.DeviceQueryVerify``)."""

    @pytest.fixture(scope="class")
    def data(self):
        from repro.data import clustered_vectors, epsilon_for_avg_neighbors
        x = clustered_vectors(1500, 24, seed=9)
        return x, epsilon_for_avg_neighbors(x, 12)

    @staticmethod
    def _wave(x, n, seed=0):
        """n queries: indexed rows (hot: repeated) and perturbed rows."""
        rng = np.random.default_rng(seed)
        hot = x[rng.integers(0, 8, n)]
        cold = x[rng.integers(0, x.shape[0], n)] + rng.normal(
            scale=0.02, size=(n, x.shape[1])).astype(np.float32)
        return np.where((np.arange(n) % 2 == 0)[:, None], hot, cold)

    @pytest.mark.parametrize("use_pallas,wave", [
        (False, 64), (True, 9)])
    def test_matches_float64_reference(self, data, tmp_path, use_pallas,
                                       wave):
        """Same id sets as a float64 brute force, outside a band of
        float32 rounding around ε; d² within float32's error. The device
        forms d² = |q|² − 2 q·x + |x|² in float32, whose rounding is at
        most a few units of 2⁻²⁴ times |q|² + |x|² per term summed over
        the dimension: 64·2⁻²⁴·(|q|² + |x|²) bounds it with room."""
        x, eps = data
        Q = self._wave(x, wave)
        with _query_index(tmp_path, x, eps, use_pallas=use_pallas) as idx:
            got = idx.query_batch(Q)
        sq = (x.astype(np.float64) ** 2).sum(1)
        for q, (ids, d), (want, d2) in zip(Q, got,
                                            _range_truth(x, Q, eps)):
            tol = 64 * 2.0 ** -24 * ((q.astype(np.float64) ** 2).sum()
                                     + sq)
            band = np.abs(d2 - eps * eps) <= tol
            ans = _as_dict(ids, d)
            assert set(want) - set(np.flatnonzero(band)) <= set(ans)
            assert set(ans) <= set(want) | set(np.flatnonzero(band))
            for i, di in ans.items():
                assert abs(di * di - d2[i]) <= tol[i], (i, di, d2[i])

    @pytest.mark.parametrize("io_mode", ["sync", "prefetch"])
    def test_matches_host_path(self, data, tmp_path, io_mode):
        x, eps = data
        Q = self._wave(x, 40, seed=1)
        with _query_index(tmp_path, x, eps, io_mode=io_mode,
                          prune=True, max_candidates=8) as idx:
            host = idx.query_batch(Q, compute_mode="host")
            dev = idx.query_batch(Q)
        for (ih, dh), (idv, ddv) in zip(host, dev):
            h, d = _as_dict(ih, dh), _as_dict(idv, ddv)
            assert set(h) == set(d)
            np.testing.assert_allclose([d[i] for i in h], list(h.values()),
                                       atol=1e-3)

    def test_warm_up_leaves_nothing_to_compile(self, data, tmp_path):
        """After warm_queries(64), waves of 1, 7 and 64 queries, hot and
        cold, lower and compile nothing."""
        from repro.obs import compile_counts
        from repro.serve import QueryScheduler

        x, eps = data
        with _query_index(tmp_path, x, eps) as idx:
            idx.warm_queries(64)
            before = compile_counts()
            with QueryScheduler(idx, epsilon=eps, wave_size=64,
                                max_wait_s=0.0) as sched:
                for n in (1, 7, 64):
                    futs = [sched.submit(q) for q in self._wave(x, n, n)]
                    assert all(f.result(timeout=60)[0].size for f in futs)
            direct = idx.query_batch(self._wave(x, 33, 2))
            after = compile_counts()
            snap = idx.pipeline_snapshot()
        assert all(ids.size for ids, _ in direct)
        assert snap["query_device_batches"] >= 3
        for k in ("lowerings", "compiles"):
            assert after[k] == before[k], (k, after["by_name"])

    def test_overflow_redispatches_and_matches(self, data, tmp_path):
        x, eps = data
        Q = self._wave(x, 24, seed=3)
        with _query_index(tmp_path, x, eps) as idx:
            want = idx.query_batch(Q, compute_mode="host")
            idx._query_lane_state().pair_cap = 1
            got = idx.query_batch(Q)
            snap = idx.pipeline_snapshot()
            assert idx._query_lane_state().pair_cap > 1  # the raise sticks
        assert snap["query_compact_overflows"] >= 1
        for (ih, dh), (idv, ddv) in zip(want, got):
            assert set(ih.tolist()) == set(idv.tolist())

    def test_midwave_cancel_skips_reads(self, data, tmp_path):
        x, eps = data
        Q = self._wave(x, 6, seed=4)
        with _query_index(tmp_path, x, eps, io_mode="sync") as idx:
            plan = idx.plan_probes(Q)
            buckets = {int(b) for p in plan for b in p}
            out = idx.execute_probes(Q, plan, cancel=lambda qi: True)
            snap = idx.pipeline_snapshot()
            # query 0 cancelled: the others' answers are whole
            full = idx.execute_probes(Q, plan)
            part = idx.execute_probes(Q, plan, cancel=lambda qi: qi == 0)
        assert snap["midwave_skipped_reads"] == len(buckets)
        assert snap["query_device_batches"] == 0
        assert all(ids.size == 0 for ids, _ in out)
        for (a, _), (b, _) in zip(full[1:], part[1:]):
            assert set(a.tolist()) == set(b.tolist())


@pytest.mark.parametrize("m,n", [(64, 2048), (200, 256)])
def test_verify_pairs_batch_unequal_rows(m, n):
    """Query lanes pair m query rows with n bucket rows: each operand is
    padded to its own block multiple and sliced back."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    rng = np.random.default_rng(m + n)
    u = rng.normal(size=(2, m, 16)).astype(np.float32)
    v = (u[:, rng.integers(0, m, n)] + rng.normal(
        scale=0.2, size=(2, n, 16))).astype(np.float32)
    d2p, mp = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v), 1.2,
                                      use_pallas=True)
    d2r, mr = kops.verify_pairs_batch(jnp.asarray(u), jnp.asarray(v), 1.2)
    assert d2p.shape == mp.shape == (2, m, n)
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r),
                               rtol=1e-5, atol=1e-4)
    assert np.asarray(mr).any()
    agree = np.asarray(mp) == np.asarray(mr)
    near = np.abs(np.asarray(d2r) - 1.2 ** 2) < 1e-4
    assert (agree | near).all()


# ---------------------------------------------------------------------------
# verify engine spans and counters, compiles counted in the program
# ---------------------------------------------------------------------------
class _Slabs:
    """Checkout/release surface of the executor's bucket caches over
    fixed in-memory slabs."""

    def __init__(self, slabs):
        self.slabs = slabs

    def checkout(self, b):
        vecs = self.slabs[b]
        return (vecs, np.arange(vecs.shape[0]) + 1000 * b, vecs.shape[0],
                None)

    def release(self, entry):
        pass


class TestVerifySpans:
    def _traced_engine_run(self, pair_cap):
        from repro.compute import DeviceVerifyEngine
        from repro.io import PipelineStats
        from repro.obs import trace_session

        rng = np.random.default_rng(5)
        cap, dim = 32, 8
        slabs = {b: (rng.normal(size=(cap, dim)) * 0.3).astype(np.float32)
                 for b in range(4)}
        stats = PipelineStats()
        with trace_session() as tr:
            eng = DeviceVerifyEngine(_Slabs(slabs), epsilon=0.6,
                                     capacity_rows=cap, dim=dim,
                                     verify_batch=2, pstats=stats,
                                     tracer=tr, pair_cap=pair_cap)
            for bu, bv in [(0, 0), (0, 1), (1, 2), (2, 3), (3, 3), (1, 3)]:
                eng.enqueue(bu, bv, bu == bv)
            eng.finish()
        assert sum(p.shape[0] for p in eng.results()[0]) > 0
        return [e for e in tr.events() if e["ph"] == "X"], stats

    @pytest.mark.parametrize("pair_cap", [None, 1])
    def test_collect_stages_nest_in_collect(self, pair_cap):
        spans, stats = self._traced_engine_run(pair_cap)
        collects = [e for e in spans if e["name"] == "verify.collect"]
        assert len(collects) == stats.device_batches == 3
        stages = {"verify.wait", "verify.extract"}
        if pair_cap == 1:
            stages.add("verify.recompact")
            assert stats.device_compact_overflows >= 1
        names = {e["name"] for e in spans}
        assert stages <= names
        for e in spans:
            if e["name"] in stages | {"verify.recompact"}:
                assert any(c["tid"] == e["tid"] and c["ts"] <= e["ts"]
                           and e["ts"] + e["dur"] <= c["ts"] + c["dur"]
                           for c in collects), e
        assert {"verify.enqueue"} <= names
        recompact = [e for e in spans if e["name"] == "verify.recompact"]
        assert all(e["args"]["k_cap"] > 1 for e in recompact)

    def test_counters_share_the_spans_intervals(self):
        spans, stats = self._traced_engine_run(None)
        total = {n: sum(e["dur"] for e in spans if e["name"] == n) / 1e6
                 for n in ("verify.collect", "verify.wait",
                           "verify.extract")}
        assert stats.device_wait_s > 0 and stats.extract_s > 0
        assert stats.device_wait_s + stats.extract_s <= \
            total["verify.collect"]
        assert stats.device_wait_s == pytest.approx(total["verify.wait"],
                                                    rel=1e-6)
        assert stats.extract_s == pytest.approx(total["verify.extract"],
                                                rel=1e-6)

    def test_batch_total_overflow_parity_and_counters(self, monkeypatch):
        """Lanes that each fit the batch capacity but together overflow
        it: the batch re-compacts at the total's power of two, results
        stay byte-identical to the host engine, and the two compaction
        counters read what they define."""
        from repro.compute import DeviceVerifyEngine, HostVerifyEngine
        from repro.compute import engine as eng
        from repro.io import PipelineStats

        rng = np.random.default_rng(8)
        cap, dim = 16, 4
        # start capacity 8 pairs a slab row: 128 a batch, as at cap 2048
        monkeypatch.setattr(eng, "PAIR_CAP_INIT", 8)
        slabs = {b: (rng.normal(size=(cap, dim)) * 0.5).astype(np.float32)
                 for b in range(4)}
        edges = [(0, 0), (0, 1), (1, 2), (2, 3), (3, 3), (1, 3), (0, 2)]
        calls = []
        real = eng.device_verify

        def recorded(*args, **kw):
            out = real(*args, **kw)
            calls.append((kw["k_cap"], np.asarray(out[0])))
            return out

        monkeypatch.setattr(eng, "device_verify", recorded)
        kw = dict(epsilon=1.0, capacity_rows=cap, dim=dim, verify_batch=4)
        stats = PipelineStats()
        dev = DeviceVerifyEngine(_Slabs(slabs), pstats=stats, **kw)
        host = HostVerifyEngine(_Slabs(slabs), **kw)
        for bu, bv in edges:
            dev.enqueue(bu, bv, bu == bv)
            host.enqueue(bu, bv, bu == bv)
        dev.finish()
        host.finish()
        first_k = calls[0][0]
        assert first_k == 128
        overflowed = [cnt for k, cnt in calls
                      if k == first_k and cnt.sum() > k]
        assert overflowed and all(c.max() <= first_k for c in overflowed)
        assert stats.device_compact_overflows >= 1
        assert stats.device_compact_slots == sum(k for k, _ in calls)
        assert stats.device_batch_pairs_max == max(
            int(cnt.sum()) for _, cnt in calls)
        assert stats.device_batch_pairs_max > first_k
        hp, hd = host.results()
        dp, dd = dev.results()
        assert np.array_equal(np.concatenate(hp), np.concatenate(dp))
        assert np.array_equal(np.concatenate(hd), np.concatenate(dd))

    def test_fresh_shape_is_one_counted_compile(self):
        import jax

        from repro.compute.engine import device_verify
        from repro.obs import compile_counts, trace_session

        slab = np.zeros((16, 8), np.float32)
        lanes = np.zeros(1, np.int32)
        before = compile_counts()
        with trace_session() as tr:
            # k_cap 24 is a static value no other test uses: a new program
            jax.block_until_ready(device_verify(
                lanes, lanes, np.zeros(1, bool), slab, slab, eps=0.5,
                k_cap=24))
        after = compile_counts()
        spans = [e for e in tr.events() if e["name"] == "jit.compile"]
        assert len(spans) == 1
        assert "device_verify" in spans[0]["args"]["fun_name"]
        assert spans[0]["dur"] > 0
        assert after["compiles"] - before["compiles"] == 1
        with trace_session() as tr:              # cached: no compile
            jax.block_until_ready(device_verify(
                lanes, lanes, np.zeros(1, bool), slab, slab, eps=0.5,
                k_cap=24))
        assert compile_counts()["compiles"] == after["compiles"]
        assert not [e for e in tr.events() if e["name"] == "jit.compile"]
