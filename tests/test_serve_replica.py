"""Replicated self-healing serving: HealthTracker state transitions,
replica routing/failover/hedging, degraded-mode coverage accounting,
supervised restart, queue checkpointing across restarts, admission
re-pricing, alert webhooks, and FlakyStore on the scheduler/router path."""
import http.server
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import DiskJoinIndex, JoinConfig
from repro.data import clustered_vectors
from repro.ft import FaultInjector, FlakyStore, InjectedKill
from repro.obs import WebhookSink
from repro.plan import predict_replica_service_s
from repro.serve import (DEGRADED, DOWN, HEALTHY, AdmissionRejected,
                         DeadlineExceeded, HealthTracker, IndexRouter,
                         QueryScheduler, ReplicaSet, ReplicaSupervisor,
                         SchedulerClosed, ShardUnavailable)
from repro.store.vector_store import FlatVectorStore

EPS = 0.35


@pytest.fixture(scope="module")
def data():
    return clustered_vectors(2200, 24, seed=9)


@pytest.fixture(scope="module")
def workdirs(data, tmp_path_factory):
    """Two shard manifests built once per module (replica tests reopen
    them freely — open() does no dataset rescan)."""
    root = tmp_path_factory.mktemp("replica_shards")
    x = data
    cfg = JoinConfig(epsilon=EPS, recall_target=0.9, pad_align=64,
                     num_buckets=20, memory_budget_bytes=1 << 20)
    parts = [x[:1100], x[1100:]]
    dirs = []
    for i, part in enumerate(parts):
        store = FlatVectorStore.from_array(str(root / f"x{i}.bin"), part)
        DiskJoinIndex.build(store, cfg, str(root / f"shard{i}")).close()
        dirs.append(str(root / f"shard{i}"))
    return dirs, parts


def _open(d):
    return DiskJoinIndex.open(d)


def _truth(part, q, eps=EPS):
    return set(np.where(
        np.linalg.norm(part - q[None, :], axis=1) <= eps)[0].tolist())


def _equalize(rset):
    """Pin every replica's service EWMA to one value so the near-equal
    rotation in ``_pick`` is deterministic — seed queries measure OS
    page-cache noise (first toucher pays the cold read), which can park
    one replica 30x above its sibling and exclude it from rotation."""
    for r in rset.replicas:
        r.service_ewma = 0.001


# ---------------------------------------------------------------------------
# HealthTracker
# ---------------------------------------------------------------------------
class TestHealthTracker:
    def test_state_transitions_from_outcomes(self):
        h = HealthTracker(window=8, min_events=4)
        assert h.state == HEALTHY
        for _ in range(6):
            h.record_ok()
        assert h.state == HEALTHY
        h.record_error(IOError("x"))
        assert h.state == DEGRADED          # 1/7 >= 0.1 error rate
        for _ in range(5):
            h.record_error(IOError("x"))
        assert h.state == DOWN              # 6/8 >= 0.5 in window
        h.reset()
        assert h.state == HEALTHY

    def test_injected_kill_is_immediate_down(self):
        h = HealthTracker()
        h.record_error(InjectedKill("dead"))
        assert h.state == DOWN              # no min_events grace
        assert h.snapshot()["down_reason"]
        h.reset()
        assert h.state == HEALTHY

    def test_drop_rate_degrades(self):
        h = HealthTracker(window=8, min_events=4, degraded_drop_rate=0.25)
        for _ in range(3):
            h.record_ok()
        h.record_drop()
        assert h.state == DEGRADED

    def test_slo_burn_state_folds_in(self):
        firing = [0]
        h = HealthTracker(slo_source=lambda: firing[0])
        assert h.state == HEALTHY
        firing[0] = 2
        assert h.state == DEGRADED
        firing[0] = 0
        assert h.state == HEALTHY

    def test_io_read_errors_fold_in(self):
        counters = {"io_read_errors": 0}
        h = HealthTracker(pipeline_source=lambda: dict(counters),
                          io_error_limit=4)
        assert h.state == HEALTHY
        counters["io_read_errors"] = 5
        assert h.state == DEGRADED
        h.reset()                           # new baseline
        assert h.state == HEALTHY

    def test_mark_down_and_snapshot(self):
        h = HealthTracker()
        h.mark_down("operator said so")
        assert h.state == DOWN
        snap = h.snapshot()
        assert snap["state"] == DOWN
        assert snap["down_reason"] == "operator said so"


def test_predict_replica_service_s():
    # no backlog: the request's own service
    assert predict_replica_service_s(0.01, 0) == pytest.approx(0.01)
    # backlog drains at the modeled rate absent an observation
    assert predict_replica_service_s(0.01, 3) == pytest.approx(0.04)
    # an observed EWMA overrides the modeled per-request rate
    assert predict_replica_service_s(0.01, 3, observed_s=0.002) \
        == pytest.approx(0.016)


# ---------------------------------------------------------------------------
# replica sets: routing, parity, failover
# ---------------------------------------------------------------------------
class TestReplicaSet:
    def test_replicated_router_byte_parity_with_single(self, data,
                                                       workdirs):
        dirs, _ = workdirs
        single = IndexRouter([_open(d) for d in dirs], epsilon=EPS,
                             close_shards=True,
                             scheduler=dict(max_wait_s=0.001))
        repl = IndexRouter([[_open(d), _open(d)] for d in dirs],
                           epsilon=EPS, close_shards=True,
                           scheduler=dict(max_wait_s=0.001))
        try:
            for q in data[::150]:
                i1, d1 = single.query(q + 0.001, timeout=120)
                i2, d2 = repl.query(q + 0.001, timeout=120)
                assert np.array_equal(i1, i2)
                assert np.array_equal(d1, d2)
        finally:
            single.close()
            repl.close()

    def test_kill_fails_over_without_request_loss(self, data, workdirs):
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=0.001))
        try:
            for q in parts[0][:4]:          # warm + seed estimates
                rset.query(q + 0.001, timeout=120)
            FaultInjector().kill_replica(rset.replicas[0])
            _equalize(rset)
            for i in range(20):
                q = parts[0][i * 3] + 0.001
                ids, _ = rset.query(q, timeout=120)
                assert set(ids.tolist()) == _truth(parts[0], q)
            snap = rset.snapshot()
            assert snap["counters"]["failovers"] >= 1
            assert snap["replicas"][0]["health"]["state"] == DOWN
            assert snap["replicas"][1]["health"]["state"] == HEALTHY
            # the DOWN replica is ejected: subsequent picks skip it
            assert rset._pick([]) is rset.replicas[1]
        finally:
            rset.close(close_indexes=True)

    def test_degraded_replica_deprioritized(self, workdirs):
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS)
        try:
            for _ in range(4):
                rset.replicas[0].health.record_drop()
            assert rset.replicas[0].health.state == DEGRADED
            # healthy sibling takes every pick while it can
            picks = {rset._pick([]) for _ in range(6)}
            assert picks == {rset.replicas[1]}
            # ... but a degraded replica still serves as last resort
            assert rset._pick([rset.replicas[1]]) is rset.replicas[0]
        finally:
            rset.close(close_indexes=True)

    def test_round_robin_policy_spreads(self, workdirs):
        dirs, _ = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS,
                          policy="round_robin")
        try:
            picks = [rset._pick([]) for _ in range(4)]
            assert set(picks) == set(rset.replicas)
        finally:
            rset.close(close_indexes=True)

    def test_least_loaded_avoids_backlogged_replica(self, workdirs):
        dirs, _ = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS)
        try:
            r0, r1 = rset.replicas
            r0.service_ewma = r1.service_ewma = 0.01
            r0.inflight = 64                # deep backlog on replica 0
            picks = {rset._pick([]) for _ in range(6)}
            assert picks == {r1}
        finally:
            rset.close(close_indexes=True)

    def test_policy_validation(self, workdirs):
        dirs, _ = workdirs
        idx = _open(dirs[0])
        try:
            with pytest.raises(ValueError, match="policy"):
                ReplicaSet([idx], epsilon=EPS, policy="darts")
            with pytest.raises(ValueError, match="hedge"):
                ReplicaSet([idx], epsilon=EPS, hedge=-1.0)
        finally:
            idx.close()

    def test_hedged_probe_rescues_browned_out_replica(self, data,
                                                      workdirs):
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=0.001), hedge="plan")
        try:
            for q in parts[0][:6]:          # seed service estimates
                rset.query(q + 0.001, timeout=120)
            inj = FaultInjector()
            inj.brownout(rset.replicas[0], extra_latency_s=0.05)
            rset.replicas[0].index.drop_warm_cache()
            _equalize(rset)
            for i in range(12):
                q = parts[0][5 + i * 7] + 0.002
                ids, _ = rset.query(q, timeout=120)
                assert set(ids.tolist()) == _truth(parts[0], q)
            c = rset.snapshot()["counters"]
            assert c["hedges"] >= 1         # slow replica tripped hedging
        finally:
            rset.close(close_indexes=True)


# ---------------------------------------------------------------------------
# degraded-mode coverage contract
# ---------------------------------------------------------------------------
class TestCoverage:
    def _dead_router(self, dirs, **kw):
        router = IndexRouter([[_open(dirs[0]), _open(dirs[0])],
                              [_open(dirs[1])]], epsilon=EPS,
                             close_shards=True,
                             scheduler=dict(max_wait_s=0.001), **kw)
        inj = FaultInjector()
        for r in router.replica_sets[1].replicas:
            inj.kill_replica(r)
            r.health.mark_down("killed for coverage test")
        return router

    def test_strict_mode_raises_on_dead_shard(self, data, workdirs):
        dirs, _ = workdirs
        router = self._dead_router(dirs)
        try:
            # epsilon large enough that the fan-out must include the
            # dead shard — strict mode cannot answer
            q = data[0] + 0.001
            assert router.route(q, epsilon=1e3) == [0, 1]
            with pytest.raises(ShardUnavailable):
                router.query(q, epsilon=1e3, timeout=120)
        finally:
            router.close()

    def test_partial_result_with_coverage(self, data, workdirs):
        dirs, parts = workdirs
        router = self._dead_router(dirs, require_full_coverage=False)
        try:
            # epsilon large enough that every query fans to both shards
            q = data[0] + 0.001
            fut = router.submit(q, epsilon=1e3)
            assert fut.coverage is None        # set at gather, not submit
            ids, dists = fut.result(timeout=120)
            cov = fut.coverage
            assert cov is not None and not cov.complete
            assert cov.total == 2 and cov.answered == 1
            by_shard = {s.shard: s for s in cov.statuses}
            assert by_shard[0].status == "ok"
            assert by_shard[1].status == "unavailable"
            assert "ShardUnavailable" in by_shard[1].error
            # the surviving shard's answer is complete and correctly
            # offset into the global id space (shard 0 owns [0, 1100))
            assert set(ids.tolist()) == _truth(parts[0], q, eps=1e3)
            d = cov.to_dict()
            assert d["complete"] is False and len(d["statuses"]) == 2
        finally:
            router.close()

    def test_per_request_override_beats_router_default(self, data,
                                                       workdirs):
        dirs, _ = workdirs
        router = self._dead_router(dirs)     # strict default
        try:
            q = data[0] + 0.001
            fut = router.submit(q, epsilon=1e3,
                                require_full_coverage=False)
            fut.result(timeout=120)
            assert fut.coverage.answered == 1
        finally:
            router.close()

    def test_full_coverage_reported_when_healthy(self, data, workdirs):
        dirs, _ = workdirs
        router = IndexRouter([_open(d) for d in dirs], epsilon=EPS,
                             close_shards=True,
                             require_full_coverage=False,
                             scheduler=dict(max_wait_s=0.001))
        try:
            fut = router.submit(data[0] + 0.001, epsilon=1e3)
            fut.result(timeout=120)
            assert fut.coverage.complete
            assert fut.coverage.answered == fut.coverage.total == 2
        finally:
            router.close()


# ---------------------------------------------------------------------------
# supervised restart
# ---------------------------------------------------------------------------
class TestReplicaSupervisor:
    def test_restart_reopens_probes_and_readmits(self, workdirs):
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=0.001))
        events = []
        sup = ReplicaSupervisor(rset, poll_s=0.02, backoff_s=0.05,
                                on_event=events.append)
        try:
            for q in parts[0][:4]:
                rset.query(q + 0.001, timeout=120)
            dead_index = rset.replicas[0].index
            FaultInjector().kill_replica(rset.replicas[0])
            _equalize(rset)
            # the kill surfaces organically: failover records the
            # InjectedKill into health, which latches DOWN
            for q in parts[0][4:8]:
                rset.query(q + 0.001, timeout=120)
            assert rset.replicas[0].health.state == DOWN
            assert sup.poll_once() == 1
            assert sup.restarts == 1
            assert rset.replicas[0].health.state == HEALTHY
            assert rset.replicas[0].index is not dead_index
            assert rset.replicas[0].restarts == 1
            assert [e["event"] for e in events].count("restart_ok") == 1
            # the restarted replica serves real traffic again
            q = parts[0][9] + 0.001
            ids, _ = rset.replicas[0].scheduler.query(q, timeout=120)
            assert set(ids.tolist()) == _truth(parts[0], q)
            assert rset.snapshot()["counters"]["restarts"] == 1
        finally:
            sup.close()
            rset.close(close_indexes=True)

    def test_restart_resumes_spilled_queue(self, workdirs):
        dirs, parts = workdirs
        # wide wave window: submitted requests sit in the queue long
        # enough that the kill catches them pending and the spill path
        # carries them over (but narrow enough for the restart probe)
        rset = ReplicaSet([_open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=2.0, wave_size=64))
        sup = ReplicaSupervisor(rset, poll_s=0.02, backoff_s=0.05)
        try:
            replica = rset.replicas[0]
            futs = [replica.scheduler.submit(parts[0][i] + 0.001,
                                             deadline_s=300.0)
                    for i in range(5)]
            replica.health.mark_down("test kill with queued work")
            assert sup.poll_once() == 1
            # spilled futures failed fast (a replica-set caller would
            # fail over); the resumed copies complete on the fresh one
            for f in futs:
                assert isinstance(f.exception(timeout=30),
                                  SchedulerClosed)
            sched = replica.scheduler
            assert len(sched.resumed) == 5
            for i, f in enumerate(sched.resumed):
                ids, _ = f.result(timeout=120)
                assert set(ids.tolist()) \
                    == _truth(parts[0], parts[0][i] + 0.001)
        finally:
            sup.close()
            rset.close(close_indexes=True)

    def test_failed_restart_backs_off(self, workdirs, monkeypatch):
        dirs, _ = workdirs
        rset = ReplicaSet([_open(dirs[0])], epsilon=EPS)
        sup = ReplicaSupervisor(rset, poll_s=0.02, backoff_s=0.2,
                                backoff_cap_s=0.4)
        try:
            replica = rset.replicas[0]
            replica.health.mark_down("test")
            monkeypatch.setattr(DiskJoinIndex, "open",
                                classmethod(lambda *a, **k: (_ for _ in ())
                                            .throw(OSError("disk gone"))))
            assert sup.poll_once() == 0
            assert sup.failed_restarts == 1
            assert replica.backoff_s == pytest.approx(0.2)
            assert replica.health.state == DOWN
            # within the backoff window nothing is attempted
            assert sup.poll_once() == 0
            assert sup.failed_restarts == 1
            time.sleep(0.25)
            assert sup.poll_once() == 0     # still failing
            assert sup.failed_restarts == 2
            assert replica.backoff_s == pytest.approx(0.4)
        finally:
            monkeypatch.undo()
            sup.close()
            rset.close(close_indexes=True)

    def test_background_thread_restarts(self, workdirs):
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=0.001))
        with ReplicaSupervisor(rset, poll_s=0.02, backoff_s=0.05):
            rset.replicas[0].health.mark_down("bg test")
            deadline = time.time() + 30
            while (rset.replicas[0].health.state != HEALTHY
                   and time.time() < deadline):
                time.sleep(0.02)
            assert rset.replicas[0].health.state == HEALTHY
        q = parts[0][2] + 0.001
        ids, _ = rset.query(q, timeout=120)
        assert set(ids.tolist()) == _truth(parts[0], q)
        rset.close(close_indexes=True)


# ---------------------------------------------------------------------------
# queue checkpoint across scheduler restarts (ft follow-on)
# ---------------------------------------------------------------------------
class TestQueueCheckpoint:
    def test_spill_and_resume_preserves_requests(self, workdirs,
                                                 tmp_path):
        dirs, parts = workdirs
        idx = _open(dirs[0])
        path = str(tmp_path / "queue.json")
        try:
            s1 = QueryScheduler(idx, epsilon=EPS, max_wait_s=30.0,
                                wave_size=64)
            futs = [s1.submit(parts[0][i] + 0.001, k=7,
                              deadline_s=300.0) for i in range(4)]
            futs.append(s1.submit(parts[0][4] + 0.001))   # no deadline
            s1.close(persist_queue=path)
            assert os.path.exists(path)
            spill = json.load(open(path))
            assert spill["format"] == "diskjoin-queue/v1"
            assert len(spill["requests"]) == 5
            assert spill["requests"][0]["k"] == 7
            assert 0 < spill["requests"][0]["remaining_s"] <= 300.0
            assert spill["requests"][4]["remaining_s"] is None
            for f in futs:
                assert isinstance(f.exception(timeout=30),
                                  SchedulerClosed)
            s2 = QueryScheduler(idx, epsilon=EPS, max_wait_s=0.001,
                                resume_queue=path)
            assert not os.path.exists(path)   # consumed, no double-resume
            assert len(s2.resumed) == 5
            for i, f in enumerate(s2.resumed):
                ids, _ = f.result(timeout=120)
                expect = _truth(parts[0], parts[0][i] + 0.001)
                if i < 4:
                    assert len(ids) == min(7, len(expect))
                else:
                    assert set(ids.tolist()) == expect
            assert s2.snapshot()["resumed"] == 5
            s2.close()
        finally:
            idx.close()

    def test_expired_deadline_resumes_as_honest_drop(self, workdirs,
                                                     tmp_path):
        dirs, parts = workdirs
        idx = _open(dirs[0])
        path = str(tmp_path / "queue.json")
        try:
            s1 = QueryScheduler(idx, epsilon=EPS, max_wait_s=30.0,
                                wave_size=64)
            s1.submit(parts[0][0] + 0.001, deadline_s=0.05)
            s1.close(persist_queue=path)
            time.sleep(0.1)                   # deadline expires off-line
            s2 = QueryScheduler(idx, epsilon=EPS, max_wait_s=0.001,
                                resume_queue=path)
            assert len(s2.resumed) == 1
            with pytest.raises(DeadlineExceeded):
                s2.resumed[0].result(timeout=30)
            s2.close()
        finally:
            idx.close()

    def test_plain_close_still_drains(self, workdirs):
        dirs, parts = workdirs
        idx = _open(dirs[0])
        try:
            s = QueryScheduler(idx, epsilon=EPS, max_wait_s=5.0,
                               wave_size=64)
            fut = s.submit(parts[0][0] + 0.001)
            s.close()                         # no persist: executes
            ids, _ = fut.result(timeout=0)
            assert set(ids.tolist()) == _truth(parts[0],
                                               parts[0][0] + 0.001)
        finally:
            idx.close()

    def test_resume_rejects_foreign_file(self, workdirs, tmp_path):
        dirs, _ = workdirs
        idx = _open(dirs[0])
        path = str(tmp_path / "bogus.json")
        json.dump({"format": "something/else"}, open(path, "w"))
        try:
            with pytest.raises(ValueError, match="diskjoin-queue"):
                QueryScheduler(idx, epsilon=EPS, resume_queue=path)
        finally:
            idx.close()


# ---------------------------------------------------------------------------
# admission re-pricing (planner follow-on)
# ---------------------------------------------------------------------------
class TestAdmissionRepricing:
    def test_rejection_carries_feasible_deadline(self, workdirs):
        dirs, parts = workdirs
        idx = _open(dirs[0])
        try:
            s = QueryScheduler(idx, epsilon=EPS, admission="estimate",
                               max_wait_s=0.0,
                               emulate_read_latency_s=0.05)
            with pytest.raises(AdmissionRejected) as ei:
                s.submit(parts[0][0] + 0.001, deadline_s=0.001)
            exc = ei.value
            assert exc.suggested_deadline_s is not None
            assert exc.suggested_deadline_s > exc.predicted_s
            assert "feasible deadline" in str(exc)
            # re-pricing works: the suggested deadline is admitted
            fut = s.submit(parts[0][0] + 0.001,
                           deadline_s=exc.suggested_deadline_s)
            ids, _ = fut.result(timeout=120)
            assert set(ids.tolist()) == _truth(parts[0],
                                               parts[0][0] + 0.001)
            s.close()
        finally:
            idx.close()


# ---------------------------------------------------------------------------
# alert webhooks (obs follow-on)
# ---------------------------------------------------------------------------
class _Hook(http.server.BaseHTTPRequestHandler):
    received: list = []

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        _Hook.received.append(json.loads(self.rfile.read(n)))
        self.send_response(200)
        self.end_headers()

    def log_message(self, *a):
        pass


class TestWebhookSink:
    def test_delivers_alert_payloads(self):
        _Hook.received = []
        srv = http.server.HTTPServer(("127.0.0.1", 0), _Hook)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            sink = WebhookSink(f"http://127.0.0.1:{srv.server_port}/h")
            sink({"slo": "latency", "state": "firing", "fast_burn": 9.0})
            deadline = time.time() + 10
            while not _Hook.received and time.time() < deadline:
                time.sleep(0.01)
            assert _Hook.received == [{"slo": "latency",
                                       "state": "firing",
                                       "fast_burn": 9.0}]
            # the server records the payload before the sink reads the
            # response and counts it: flush the sink first
            sink.close()
            assert sink.snapshot()["delivered"] == 1
        finally:
            srv.shutdown()

    def test_wired_into_slo_monitor(self):
        from repro.obs.live import Alert

        _Hook.received = []
        srv = http.server.HTTPServer(("127.0.0.1", 0), _Hook)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            sink = WebhookSink(f"http://127.0.0.1:{srv.server_port}/h")
            # the sink is a plain on_alert callback: Alert objects
            # serialize through to_dict()
            alert = Alert("goodput", "firing", 1.0, 15.0, 6.0, 0.5, "m")
            sink(alert)
            deadline = time.time() + 10
            while not _Hook.received and time.time() < deadline:
                time.sleep(0.01)
            assert _Hook.received[0]["slo"] == "goodput"
            assert _Hook.received[0]["state"] == "firing"
            sink.close()
        finally:
            srv.shutdown()

    def test_failures_counted_never_raised(self):
        # nothing listens on this port: delivery fails, the fold path
        # (the __call__) never sees it
        sink = WebhookSink("http://127.0.0.1:9/h", timeout_s=0.2)
        sink({"slo": "x", "state": "firing"})
        deadline = time.time() + 10
        while sink.snapshot()["failures"] == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert sink.snapshot()["failures"] == 1
        assert sink.snapshot()["delivered"] == 0
        sink.close()

    def test_full_queue_drops_without_blocking(self):
        sink = WebhookSink("http://127.0.0.1:9/h", queue_size=1,
                           timeout_s=5.0)
        sink._post = lambda payload: time.sleep(0.3)   # slow delivery
        t0 = time.perf_counter()
        for i in range(50):
            sink({"i": i})
        assert time.perf_counter() - t0 < 1.0   # never blocked the caller
        assert sink.snapshot()["dropped"] >= 1
        sink.close(timeout=0.5)


# ---------------------------------------------------------------------------
# FlakyStore on the scheduler/router path
# ---------------------------------------------------------------------------
class TestFlakyServing:
    def test_transient_errors_retry_in_place(self, workdirs):
        """Wave execution under transient read errors: the capped-backoff
        retry absorbs them inside the wave — no failover, no loss."""
        dirs, parts = workdirs
        idx = _open(dirs[0])
        try:
            idx.store = FlakyStore(idx.store, read_error_every=3)
            s = QueryScheduler(idx, epsilon=EPS, max_wait_s=0.001)
            for i in range(10):
                q = parts[0][i * 5] + 0.001
                ids, _ = s.query(q, timeout=120)
                assert set(ids.tolist()) == _truth(parts[0], q)
            snap = idx.stats.snapshot()
            assert idx.store.errors_injected >= 1
            assert snap["io_retries"] >= 1
            assert snap["io_read_errors"] >= 1
            s.close()
        finally:
            idx.close()

    def test_permanent_failure_fails_over_not_loses(self, workdirs):
        """A replica whose store dies permanently (retries exhausted)
        triggers failover to the sibling — every request still answers."""
        dirs, parts = workdirs
        rset = ReplicaSet([_open(dirs[0]), _open(dirs[0])], epsilon=EPS,
                          scheduler=dict(max_wait_s=0.001,
                                         io_retries=1))
        try:
            for q in parts[0][:4]:
                rset.query(q + 0.001, timeout=120)
            # every read fails: retries can never absorb it
            FaultInjector().flaky_replica(rset.replicas[0], every=1)
            rset.replicas[0].index.drop_warm_cache()
            _equalize(rset)
            for i in range(16):
                q = parts[0][i * 4] + 0.001
                ids, _ = rset.query(q, timeout=120)
                assert set(ids.tolist()) == _truth(parts[0], q)
            snap = rset.snapshot()
            assert snap["counters"]["failovers"] >= 1
            assert snap["replicas"][0]["health"]["state"] in (DEGRADED,
                                                              DOWN)
        finally:
            rset.close(close_indexes=True)

    def test_brownout_verb_scales_latency(self, workdirs):
        dirs, _ = workdirs
        idx = _open(dirs[0])
        try:
            idx.store.read_latency_s = 0.01
            store = FaultInjector().brownout(idx, latency_x=4.0)
            assert store.extra_latency_s == pytest.approx(0.03)
        finally:
            idx.close()
