"""Unified telemetry subsystem (repro.obs): tracer thread-safety and
nesting, Chrome-trace schema validity, disabled-overhead bound,
TraceAnalysis interval math on synthetic spans, MetricsRegistry instruments
and rollup merge, PipelineStats.merge regression, and the end-to-end
acceptance: a traced prefetch+device self-join whose span-derived hidden
fraction agrees with the stats-derived overlap efficiency."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import DiskJoinIndex, JoinConfig
from repro.data import clustered_vectors
from repro.io import PipelineStats
from repro.obs import (NOOP_SPAN, Counter, Gauge, Histogram,
                       MetricsRegistry, TraceAnalysis, Tracer, get_tracer,
                       log_bounds, trace_session, validate_chrome_trace)
from repro.obs.tracer import _DISABLED
from repro.serve import QueryScheduler, VectorQueryService
from repro.store.vector_store import FlatVectorStore


def _disabled_span_cost_s(n: int = 200_000) -> float:
    """Measured per-call cost of the disabled tracer's span fast path
    (including the caller's kwargs construction — the full price an
    instrumentation site pays when tracing is off)."""
    tr = Tracer(enabled=False)
    span = tr.span
    best = float("inf")
    for _ in range(3):                       # best-of-3 against CI jitter
        t0 = time.perf_counter()
        for _ in range(n):
            with span("io.read", dev=0):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    return best


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_records_both(self):
        tr = Tracer()
        with tr.span("outer", a=1):
            with tr.span("inner"):
                time.sleep(0.001)
        evs = tr.events()
        by_name = {e["name"]: e for e in evs}
        assert set(by_name) == {"outer", "inner"}
        o, i = by_name["outer"], by_name["inner"]
        assert o["ph"] == i["ph"] == "X"
        # inner nests inside outer on the timeline
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1.0  # µs slack
        assert o["args"] == {"a": 1}

    def test_span_set_attaches_args(self):
        tr = Tracer()
        with tr.span("s") as sp:
            sp.set(rows=7)
        (ev,) = tr.events()
        assert ev["args"] == {"rows": 7}

    def test_complete_uses_caller_interval(self):
        tr = Tracer()
        t0 = time.perf_counter()
        tr.complete("io.read", t0, 0.25, dev=3)
        (ev,) = tr.events()
        assert ev["dur"] == pytest.approx(0.25e6)
        assert ev["args"] == {"dev": 3}

    def test_instant_counter_async_phases(self):
        tr = Tracer()
        tr.instant("mark", k=1)
        tr.counter("depth", 4)
        tr.async_begin("req", 9, src="test")
        tr.async_end("req", 9, ok=True)
        phases = {e["name"]: e for e in tr.events()}
        assert phases["mark"]["ph"] == "i"
        assert phases["depth"]["ph"] == "C"
        assert phases["depth"]["args"]["value"] == 4
        bs = [e for e in tr.events() if e["ph"] == "b"]
        es = [e for e in tr.events() if e["ph"] == "e"]
        assert bs[0]["id"] == es[0]["id"] == 9
        assert bs[0]["cat"] == "async"

    def test_threads_do_not_corrupt_each_other(self):
        tr = Tracer()
        n_threads, n_each = 8, 500

        def work(k):
            for i in range(n_each):
                with tr.span(f"t{k}", i=i):
                    pass

        ts = [threading.Thread(target=work, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        evs = tr.events()
        assert len(evs) == n_threads * n_each
        assert tr.dropped == 0
        for k in range(n_threads):
            mine = [e for e in evs if e["name"] == f"t{k}"]
            assert len(mine) == n_each
            # one ring per thread: all of a thread's events share one tid
            assert len({e["tid"] for e in mine}) == 1
            assert sorted(e["args"]["i"] for e in mine) == list(range(n_each))

    def test_ring_overflow_drops_oldest_and_counts(self):
        tr = Tracer(ring_capacity=16)
        for i in range(40):
            tr.instant("e", i=i)
        evs = tr.events()
        assert len(evs) == 16
        assert tr.dropped == 24
        # newest survive, oldest overwritten
        assert [e["args"]["i"] for e in evs] == list(range(24, 40))

    def test_clear(self):
        tr = Tracer()
        tr.instant("x")
        tr.clear()
        assert tr.events() == []

    def test_disabled_tracer_is_noop(self):
        tr = Tracer(enabled=False)
        assert tr.span("s") is NOOP_SPAN
        with tr.span("s") as sp:
            sp.set(a=1)
        tr.instant("i")
        tr.counter("c", 1)
        tr.complete("x", 0.0, 1.0)
        tr.async_begin("r", 1)
        tr.async_end("r", 1)
        assert tr.events() == []

    def test_trace_session_scopes_current_tracer(self):
        assert get_tracer() is _DISABLED
        with trace_session() as tr:
            assert get_tracer() is tr
            get_tracer().instant("inside")
        assert get_tracer() is _DISABLED
        assert [e["name"] for e in tr.events()] == ["inside"]

    def test_disabled_span_per_call_cost_is_submicrosecond(self):
        """Micro-benchmark of the no-op fast path: a disabled span —
        kwargs construction included — must stay well under a µs per
        call. (The <1% claim on the real fig19-shaped workload is
        asserted in ``TestEndToEnd``, where the actual instrumentation
        call count and wall time are both measured.)"""
        assert _disabled_span_cost_s() < 2e-6


# ---------------------------------------------------------------------------
# Export schema + TraceAnalysis interval math
# ---------------------------------------------------------------------------

def _x(name, ts_s, dur_s, tid=1, **args):
    ev = {"name": name, "ph": "X", "pid": 1, "tid": tid,
          "ts": ts_s * 1e6, "dur": dur_s * 1e6}
    if args:
        ev["args"] = args
    return ev


class TestExport:
    def test_export_roundtrip_schema_valid(self, tmp_path):
        tr = Tracer()
        with tr.span("a"):
            tr.instant("m")
        tr.async_begin("r", 1)
        tr.async_end("r", 1)
        path = tr.export(str(tmp_path / "t.json"))
        n = validate_chrome_trace(path)
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        # span + instant + async pair + thread_name metadata
        assert n == len(doc["traceEvents"]) >= 5
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in doc["traceEvents"])

    @pytest.mark.parametrize("bad", [
        [{"ph": "X", "pid": 1, "tid": 1, "ts": 0}],           # no name
        [{"name": "a", "ph": "?", "pid": 1, "tid": 1, "ts": 0}],
        [{"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": "z"}],
        [{"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0}],  # no dur
        [{"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0,
          "dur": -1}],
        [{"name": "a", "ph": "b", "pid": 1, "tid": 1, "ts": 0}],  # no id
        [{"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": 0,
          "args": 3}],
        "not-a-trace",
    ])
    def test_validate_rejects(self, bad):
        if isinstance(bad, str):
            with pytest.raises((ValueError, OSError)):
                validate_chrome_trace({"traceEvents": bad})
        else:
            with pytest.raises(ValueError):
                validate_chrome_trace(bad)

    def test_overlap_exact_on_synthetic_spans(self):
        an = TraceAnalysis([
            _x("read", 0.0, 1.0), _x("read", 2.0, 1.0),
            _x("verify", 0.5, 2.0),
        ])
        assert an.total_seconds("read") == pytest.approx(2.0)
        assert an.busy_seconds("read") == pytest.approx(2.0)
        # read∩verify = [0.5,1.0] + [2.0,2.5] = 1.0
        assert an.overlap_seconds("read", "verify") == pytest.approx(1.0)
        assert an.overlap_fraction("read", "verify") == pytest.approx(0.5)

    def test_hidden_fraction_union_semantics(self):
        # two concurrent reads (thread-seconds 2.0), one wait covering
        # [0.25, 0.75]: visible covers 0.5s of EACH read's interval on the
        # union timeline → hidden = (2.0 − 0.5) / 2.0... union(read) is
        # [0,1] so vis∩union = 0.5, hidden = (2.0 − 0.5)/2.0 = 0.75
        an = TraceAnalysis([
            _x("io.read", 0.0, 1.0, tid=1), _x("io.read", 0.0, 1.0, tid=2),
            _x("io.wait", 0.25, 0.5),
        ])
        assert an.hidden_fraction("io.read", "io.wait") == \
            pytest.approx(0.75)
        # nothing recorded → 1.0 (matches stats convention for read_s==0)
        assert an.hidden_fraction("absent", "io.wait") == 1.0

    def test_prefix_and_union_specs(self):
        an = TraceAnalysis([
            _x("verify.dispatch", 0.0, 1.0), _x("verify.collect", 2.0, 1.0),
            _x("join.run", 0.0, 4.0),
        ])
        assert an.total_seconds("verify.*") == pytest.approx(2.0)
        assert an.overlap_seconds(("verify.*", "join.run"), "join.run") \
            == pytest.approx(4.0)

    def test_critical_path_sums_to_extent_no_double_count(self):
        an = TraceAnalysis([
            _x("a", 0.0, 2.0), _x("b", 1.0, 2.0),  # overlap [1,2]
        ])
        cp = an.critical_path(priorities=["a", "b"])
        assert cp["a"] == pytest.approx(2.0)   # owns its full extent
        assert cp["b"] == pytest.approx(1.0)   # only its exclusive tail
        assert cp["idle"] == pytest.approx(0.0)
        assert sum(cp.values()) == pytest.approx(3.0)  # span extent

    def test_wall_breakdown_and_summary(self):
        an = TraceAnalysis([
            _x("io.read", 0.0, 1.0), _x("io.read", 0.5, 1.0),
            _x("io.wait", 0.2, 0.1),
        ])
        bd = an.wall_breakdown()
        assert bd["io.read"]["count"] == 2
        assert bd["io.read"]["total_s"] == pytest.approx(2.0)
        assert bd["io.read"]["busy_s"] == pytest.approx(1.5)
        s = an.summary()
        assert s["read_hidden_fraction"] == pytest.approx(1.9 / 2.0)

    def test_async_pairs(self):
        an = TraceAnalysis([
            {"name": "req", "ph": "b", "pid": 1, "tid": 1, "ts": 0.0,
             "id": 5},
            {"name": "req", "ph": "e", "pid": 1, "tid": 2, "ts": 2e6,
             "id": 5, "args": {"wave": 3}},
            {"name": "req", "ph": "b", "pid": 1, "tid": 1, "ts": 1e6,
             "id": 6},   # unterminated — skipped
        ])
        pairs = an.async_pairs("req")
        assert len(pairs) == 1
        assert pairs[0]["id"] == 5
        assert pairs[0]["duration_s"] == pytest.approx(2.0)
        assert pairs[0]["args"]["wave"] == 3


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        reg.counter("io.reads").inc()
        reg.counter("io.reads").inc(4)        # get-or-create: same object
        reg.gauge("pool.slabs").set(7)
        reg.gauge("pool.slabs").max(3)        # high-watermark keeps 7
        snap = reg.snapshot()
        assert snap["counters"]["io.reads"] == 5
        assert snap["gauges"]["pool.slabs"] == 7

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_log_bounds_validation(self):
        with pytest.raises(ValueError):
            log_bounds(0, 1, 2)
        with pytest.raises(ValueError):
            log_bounds(1, 2, 1.0)
        b = log_bounds(1.0, 8.0, 2.0)
        assert b == [1.0, 2.0, 4.0, 8.0]

    def test_histogram_percentiles_within_bucket_factor(self):
        h = Histogram("lat", lo=1e-4, hi=10.0, factor=2.0)
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=-4, sigma=1.0, size=5000)
        for v in vals:
            h.observe(v)
        for q in (50, 95, 99):
            exact = float(np.percentile(vals, q))
            est = h.percentile(q)
            assert exact / 2.0 <= est <= exact * 2.0, \
                f"p{q}: est {est} vs exact {exact}"
        s = h.snapshot()
        assert s["count"] == 5000
        assert s["min"] == pytest.approx(vals.min())
        assert s["max"] == pytest.approx(vals.max())

    def test_histogram_overflow_bucket(self):
        h = Histogram("x", lo=1.0, hi=4.0, factor=2.0)
        h.observe(1e9)
        assert h.counts[-1] == 1
        assert h.percentile(50) == h.bounds[-1]

    def test_provider_suffix_and_unregister(self):
        reg = MetricsRegistry()
        k1 = reg.register_provider("svc", lambda: {"a": 1})
        k2 = reg.register_provider("svc", lambda: {"a": 2})
        assert k1 == "svc" and k2 == "svc#2"
        snap = reg.snapshot()
        assert snap["svc"] == {"a": 1} and snap["svc#2"] == {"a": 2}
        reg.unregister_provider(k2)
        assert "svc#2" not in reg.snapshot()

    def test_raising_provider_isolated(self):
        reg = MetricsRegistry()
        reg.register_provider("bad", lambda: 1 / 0)
        reg.counter("ok").inc()
        snap = reg.snapshot()
        assert "error" in snap["bad"]
        assert snap["counters"]["ok"] == 1

    def test_to_json_roundtrips(self):
        reg = MetricsRegistry()
        reg.histogram("h").observe(0.5)
        doc = json.loads(reg.to_json())
        assert doc["histograms"]["h"]["count"] == 1

    def test_merge_exact_histogram_rollup(self):
        shards = []
        all_vals = []
        rng = np.random.default_rng(1)
        for s in range(3):
            reg = MetricsRegistry()
            reg.counter("reads").inc(10 * (s + 1))
            reg.gauge("depth").set(s)
            vals = rng.lognormal(-3, 1, 1000)
            h = reg.histogram("lat", lo=1e-4, hi=10.0)
            for v in vals:
                h.observe(v)
            all_vals.append(vals)
            shards.append(reg.snapshot())
        merged = MetricsRegistry.merge(shards)
        assert merged["counters"]["reads"] == 60
        assert merged["gauges"]["depth"] == 2
        mh = merged["histograms"]["lat"]
        assert mh["count"] == 3000
        # exact rollup: merged percentile == one histogram over all values
        ref = Histogram("ref", lo=1e-4, hi=10.0)
        for v in np.concatenate(all_vals):
            ref.observe(v)
        assert mh["p95"] == pytest.approx(ref.percentile(95))
        assert mh["buckets"] == ref.counts

    def test_merge_incompatible_bounds_degrades(self):
        a = MetricsRegistry()
        a.histogram("h", lo=1e-3, hi=1.0).observe(0.1)
        b = MetricsRegistry()
        b.histogram("h", lo=1e-6, hi=1.0).observe(0.2)
        m = MetricsRegistry.merge([a.snapshot(), b.snapshot()])
        mh = m["histograms"]["h"]
        assert mh["count"] == 2
        assert mh["sum"] == pytest.approx(0.3)
        assert "p95" not in mh and "buckets" not in mh

    def test_merge_collects_provider_sections(self):
        a = MetricsRegistry()
        a.register_provider("pipeline", lambda: {"read_s": 1.0})
        b = MetricsRegistry()
        b.register_provider("pipeline", lambda: {"read_s": 2.0})
        m = MetricsRegistry.merge([a.snapshot(), b.snapshot()])
        assert m["pipeline"] == [{"read_s": 1.0}, {"read_s": 2.0}]


# ---------------------------------------------------------------------------
# PipelineStats.merge regression (satellite: list-valued fields)
# ---------------------------------------------------------------------------

class TestPipelineStatsMerge:
    def test_merge_list_fields_concatenate(self):
        a, b = PipelineStats(), PipelineStats()
        a.init_devices(2)
        a.count_device_loads(0, 5)
        a.count_device_loads(1, 3)
        b.init_devices(3)          # unequal lengths — the old failure mode
        b.count_device_loads(2, 7)
        a.add("read_s", 1.0)
        a.add("io_wait_s", 0.25)
        b.add("read_s", 3.0)
        b.add("io_wait_s", 0.75)
        a.observe_depth(4)
        b.observe_depth(9)
        m = PipelineStats.merge([a.snapshot(), b.snapshot()])
        assert m["device_loads"] == [5, 3, 0, 0, 7]
        assert m["device_depth_max"] == [0, 0, 0, 0, 0]
        assert m["num_devices"] == 5
        assert m["read_s"] == pytest.approx(4.0)
        assert m["max_queue_depth"] == 9
        # derived ratio recomputed from merged totals, not summed/maxed
        assert m["overlap_efficiency"] == pytest.approx(3.0 / 4.0)

    def test_snapshot_since_survives_device_list_reset(self):
        """Regression: a base captured BEFORE a prefetcher re-attached
        (init_devices resets the per-device lists) must not be subtracted
        from the fresh lists — that undercounted whichever devices the
        earlier (e.g. build/layout) pass had used."""
        s = PipelineStats()
        s.init_devices(4)
        s.count_device_loads(0, 4)         # layout pass activity
        s.count_device_loads(1, 2)
        base = s.snapshot()
        s.init_devices(4)                  # the measured run's prefetcher
        for dev, n in enumerate((8, 8, 5, 4)):
            s.count_device_loads(dev, n)
        s.add("loads", 25)
        out = s.snapshot_since(base)
        assert out["device_loads"] == [8, 8, 5, 4]
        assert sum(out["device_loads"]) == out["loads"]

    def test_merge_empty_and_single(self):
        assert PipelineStats.merge([])["read_s"] == 0
        s = PipelineStats()
        s.add("read_s", 2.0)
        m = PipelineStats.merge([s.snapshot()])
        assert m["read_s"] == pytest.approx(2.0)
        assert m["overlap_efficiency"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# End-to-end: instrumented pipeline + metrics surface
# ---------------------------------------------------------------------------

def _build_index(tmp_path, n=6000, dim=24, seed=7, **cfg_kw):
    x = clustered_vectors(n, dim, seed=seed)
    store = FlatVectorStore.from_array(str(tmp_path / "x.bin"), x)
    base = dict(epsilon=0.35, recall_target=0.9, pad_align=64,
                num_buckets=max(24, n // 150),
                memory_budget_bytes=max(1 << 20, x.nbytes // 10))
    base.update(cfg_kw)
    return DiskJoinIndex.build(store, JoinConfig(**base),
                               str(tmp_path / "idx")), x


class TestEndToEnd:
    def test_traced_join_agrees_with_pipeline_stats(self, tmp_path):
        """Acceptance: prefetch+device self-join exports a valid Chrome
        trace whose hidden_fraction("io.read","io.wait") agrees with the
        PipelineStats-derived overlap_efficiency within 10%."""
        index, x = _build_index(
            tmp_path, io_mode="prefetch", io_threads=8, io_lookahead=16,
            compute_mode="device", emulate_read_latency_s=1e-3)
        index.self_join()                      # warm jit outside the trace
        index.drop_warm_cache()
        base = index.pipeline_snapshot()
        with trace_session() as tr:
            t0 = time.perf_counter()
            res = index.self_join()
            traced_wall_s = time.perf_counter() - t0
        snap = index.pipeline_snapshot()
        assert res.pairs.shape[0] > 0

        path = tr.export(str(tmp_path / "join.json"))
        assert validate_chrome_trace(path) > 0
        an = tr.analysis()
        assert {"io.read", "io.wait", "join.run", "join.plan",
                "verify.dispatch", "verify.collect"} <= set(an.names())
        # the trace must show reads proceeding under the verify walk
        assert an.overlap_seconds("io.read", ("verify.*", "join.run")) > 0

        read_s = snap["read_s"] - base["read_s"]
        io_wait = snap["io_wait_s"] - base["io_wait_s"]
        stats_eff = (max(0.0, read_s - io_wait) / read_s
                     if read_s > 0 else 1.0)
        hidden = an.hidden_fraction("io.read", "io.wait")
        assert abs(hidden - stats_eff) <= 0.10, \
            f"trace hidden={hidden:.3f} vs stats overlap={stats_eff:.3f}"
        # trace and stats see the SAME measurements (tracer.complete):
        # summed span durations equal the accumulated counters
        assert an.total_seconds("io.read") == pytest.approx(read_s,
                                                            rel=1e-6)
        assert an.total_seconds("io.wait") == pytest.approx(io_wait,
                                                            rel=1e-6)

        # disabled-tracing overhead on THIS workload: every event above
        # is one instrumentation call; when tracing is off each such call
        # costs the measured no-op fast path — must be <1% of the
        # workload's wall time
        n_calls = len(tr.events())
        overhead = _disabled_span_cost_s() * n_calls
        assert overhead < 0.01 * traced_wall_s, \
            f"disabled tracing would cost {overhead * 1e3:.3f}ms over " \
            f"{n_calls} sites on a {traced_wall_s * 1e3:.0f}ms workload " \
            f"({overhead / traced_wall_s:.2%})"
        index.close()

    def test_tracing_disabled_records_nothing(self, tmp_path):
        index, _ = _build_index(tmp_path, n=2000)
        assert get_tracer() is _DISABLED
        index.self_join()
        assert get_tracer().events() == []
        index.close()

    def test_scheduler_wave_request_linkage(self, tmp_path):
        index, x = _build_index(tmp_path, n=2500)
        rng = np.random.default_rng(3)
        queries = x[rng.choice(x.shape[0], 12)]
        with trace_session() as tr:
            with QueryScheduler(index, wave_size=4,
                                max_wait_s=0.002) as sched:
                futs = [sched.submit(q) for q in queries]
                for f in futs:
                    f.result(timeout=120)
        an = tr.analysis()
        assert an.count("serve.wave") >= 1
        pairs = an.async_pairs("serve.request")
        assert len(pairs) == len(queries)
        wave_ids = {p["args"]["wave"] for p in pairs}
        assert all(w >= 1 for w in wave_ids)
        # every request's wave id names a traced wave span
        wave_spans = [e for e in tr.events()
                      if e["ph"] == "X" and e["name"] == "serve.wave"]
        assert wave_ids <= {e["args"]["wave"] for e in wave_spans}
        index.close()

    def test_index_metrics_surface_and_service_provider(self, tmp_path):
        index, x = _build_index(tmp_path, n=2000)
        svc = VectorQueryService(index)
        svc.query(x[0])
        svc.query(x[1])
        snap = index.metrics_snapshot()
        assert {"counters", "gauges", "histograms", "pipeline",
                "io", "jit"} <= set(snap)
        assert {"compiles", "cache_hits"} <= set(snap["jit"])
        assert snap["service"]["requests"] == 2
        assert snap["service"]["latency_p95_ms"] > 0
        svc.close()
        assert "service" not in index.metrics_snapshot()
        index.close()

    def test_two_services_do_not_shadow(self, tmp_path):
        index, x = _build_index(tmp_path, n=2000)
        s1 = VectorQueryService(index)
        s2 = VectorQueryService(index)
        s1.query(x[0])
        snap = index.metrics_snapshot()
        assert snap["service"]["requests"] == 1
        assert snap["service#2"]["requests"] == 0
        s2.close()
        s1.close()
        index.close()

    def test_router_metrics_rollup(self, tmp_path):
        from repro.serve import IndexRouter
        rng = np.random.default_rng(11)
        shards = []
        for si in range(2):
            x = clustered_vectors(1500, 16, seed=20 + si)
            store = FlatVectorStore.from_array(
                str(tmp_path / f"s{si}.bin"), x)
            cfg = JoinConfig(epsilon=0.35, recall_target=0.9,
                             pad_align=64, num_buckets=12,
                             memory_budget_bytes=1 << 20)
            shards.append(DiskJoinIndex.build(
                store, cfg, str(tmp_path / f"idx{si}")))
        router = IndexRouter(shards, close_shards=True)
        Q = clustered_vectors(1500, 16, seed=20)[rng.choice(1500, 4)]
        for qv in Q:
            router.query(qv, timeout=120)
        m = router.metrics_snapshot()
        # the per-shard pipeline sections re-merged domain-aware: one
        # dict, not a per-shard list
        assert isinstance(m["pipeline"], dict)
        assert m["pipeline"]["read_s"] >= 0
        p = router.pipeline_snapshot()
        assert p["num_devices"] == sum(
            s.stats.snapshot()["num_devices"] for s in shards)
        router.close()
