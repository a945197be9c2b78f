"""Elastic serving control plane tests: router discovery/ejection/
hedging, replica heartbeats + graceful drain, batcher deadline/liveness
hardening, the replica autoscaler on the pod-aware driver machinery, and
the new serving fault kinds.

Everything in-process and CPU except the final multiprocess acceptance
scenario (real RendezvousServer, real `hvdtrun serve --replicas` control
plane, replicas as subprocesses, synthetic client load, a serve_crash
fault plan) — that one is ``slow`` and runs in the test-smoke compose
service.
"""

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from horovod_tpu.models.mlp import mlp_apply, mlp_init
from horovod_tpu.resilience import faults
from horovod_tpu.resilience.faults import FaultInjector, parse_plan
from horovod_tpu.resilience.preempt import PREEMPT_EXIT_CODE
from horovod_tpu.runner.http_kv import KVClient, RendezvousServer
from horovod_tpu.serve import (DispatcherDied, DynamicBatcher,
                               InferenceEngine, ModelServer,
                               RequestDeadlineExceeded)
from horovod_tpu.serve.autoscale import (AutoscalePolicy, ServeDriver,
                                         TARGET_KV_KEY,
                                         localhost_host_manager)
from horovod_tpu.serve.replica import (DRAIN_KV_PREFIX, REPLICA_KV_PREFIX,
                                       ReplicaRegistrar)
from horovod_tpu.serve.router import Router

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = (6, 16, 3)


@pytest.fixture(scope="module")
def params():
    return mlp_init(jax.random.PRNGKey(0), SIZES)


@pytest.fixture()
def kv_server():
    server = RendezvousServer()
    server.start()
    yield server
    server.stop()


def _kv_client(server: RendezvousServer) -> KVClient:
    return KVClient("127.0.0.1", server.port, server.secret, timeout=5.0)


def _post(port, doc, timeout=30, path="/predict", headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(doc),
                     {"Content-Type": "application/json", **(headers or {})})
        r = conn.getresponse()
        return r.status, json.loads(r.read()), dict(r.getheaders())
    finally:
        conn.close()


def _get(port, route, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", route)
        r = conn.getresponse()
        return r.status, r.read().decode()
    finally:
        conn.close()


def _row():
    return [0.5] * SIZES[0]


def _wait_until(cond, why, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    pytest.fail(why)


# ---------------------------------------------------------------------------
# Fault grammar: serve_crash / slow_replica
# ---------------------------------------------------------------------------

class TestServeFaultKinds:
    def test_parse_defaults_to_serve_predict_point(self):
        specs = parse_plan("serve_crash@step=40:rank=2,"
                           "slow_replica@p=0.1:secs=2")
        assert specs[0].kind == "serve_crash"
        assert specs[0].point == "serve.predict"
        assert specs[0].step == 40 and specs[0].rank == 2
        assert specs[1].kind == "slow_replica"
        assert specs[1].point == "serve.predict"
        assert specs[1].p == 0.1 and specs[1].secs == 2.0

    def test_point_override_targets_router_dispatch(self):
        (spec,) = parse_plan("slow_replica@p=1.0:secs=1:"
                             "point=serve.dispatch")
        assert spec.point == "serve.dispatch"

    def test_unknown_kind_lists_serve_kinds(self):
        with pytest.raises(ValueError, match="serve_crash"):
            parse_plan("banana@step=1")

    def test_serve_crash_exits_at_nth_request(self):
        exits = []
        inj = FaultInjector(parse_plan("serve_crash@step=3:rank=1"),
                            exit_fn=exits.append)
        for seq in range(1, 6):
            inj.fire("serve.predict", step=seq, rank=0)
        assert exits == []          # wrong rank never dies
        for seq in range(1, 6):
            inj.fire("serve.predict", step=seq, rank=1)
        assert exits == [1]         # fired once, at step >= 3

    def test_slow_replica_sleeps_deterministically(self):
        naps = []
        inj = FaultInjector(parse_plan("slow_replica@p=0.5:secs=2"),
                            seed=7, sleep_fn=naps.append)
        for seq in range(40):
            inj.fire("serve.predict", step=seq, rank=0)
        assert naps and all(n == 2.0 for n in naps)
        assert 5 < len(naps) < 35   # probabilistic but seeded
        naps2 = []
        inj2 = FaultInjector(parse_plan("slow_replica@p=0.5:secs=2"),
                             seed=7, sleep_fn=naps2.append)
        for seq in range(40):
            inj2.fire("serve.predict", step=seq, rank=0)
        assert len(naps2) == len(naps)   # same seed, same schedule

    def test_predict_path_fires_injection_point(self, params,
                                                monkeypatch):
        monkeypatch.setenv("HVDT_FAULT_PLAN",
                           "slow_replica@p=1.0:secs=0.0")
        try:
            inj = faults.get_injector()
            assert inj is not None
            engine = InferenceEngine(mlp_apply, params, buckets=(1, 4))
            server = ModelServer(engine, port=0)
            port = server.start()
            try:
                status, doc, _ = _post(port, {"inputs": [_row()]})
                assert status == 200
                assert inj.counters.get("slow_replica", 0) >= 1
            finally:
                server.stop()
        finally:
            monkeypatch.delenv("HVDT_FAULT_PLAN")
            faults.get_injector()   # rebuild cache off the cleared env


# ---------------------------------------------------------------------------
# Batcher hardening: deadlines + dispatcher liveness
# ---------------------------------------------------------------------------

class TestBatcherRobustness:
    def test_queued_request_fails_fast_when_dispatch_wedges(self):
        release = threading.Event()

        def wedged_infer(x):
            release.wait(10.0)
            return x

        b = DynamicBatcher(wedged_infer, max_batch_size=1,
                           max_delay_ms=0.0, max_queue_depth=64,
                           deadline_s=0.3)
        try:
            f1 = b.submit(np.zeros((1, 4), np.float32))
            time.sleep(0.05)        # dispatch thread now wedged on f1
            f2 = b.submit(np.zeros((1, 4), np.float32))
            with pytest.raises(RequestDeadlineExceeded):
                f2.result(timeout=2.0)   # watchdog, not the engine
            assert b.metrics.get(
                "serve_deadline_expired_total").total() >= 1
        finally:
            release.set()
            f1.result(timeout=5.0)
            b.close()

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_dispatch_thread_death_fails_future_and_submit(self):
        def lethal_infer(x):
            raise SystemExit("engine took the thread down")

        b = DynamicBatcher(lethal_infer, max_batch_size=4,
                           max_delay_ms=0.0, max_queue_depth=64)
        try:
            f = b.submit(np.zeros((1, 4), np.float32))
            with pytest.raises(DispatcherDied):
                f.result(timeout=5.0)
            _wait_until(lambda: not b._thread.is_alive(),
                        "dispatch thread survived SystemExit")
            with pytest.raises(DispatcherDied):
                b.submit(np.zeros((1, 4), np.float32))
        finally:
            b.close()       # the deadline watchdog is still running

    def test_fail_pending_abandonment_is_typed(self):
        release = threading.Event()

        def slow_infer(x):
            release.wait(10.0)
            return x

        b = DynamicBatcher(slow_infer, max_batch_size=1,
                           max_delay_ms=0.0, max_queue_depth=64,
                           deadline_s=30.0)
        try:
            f1 = b.submit(np.zeros((1, 4), np.float32))
            time.sleep(0.05)
            f2 = b.submit(np.zeros((1, 4), np.float32))
            # The replica-ejection path: the owner walks away from the
            # batcher wholesale; parked futures must fail typed, now.
            assert b.fail_pending() == 1
            with pytest.raises(DispatcherDied):
                f2.result(timeout=1.0)
        finally:
            release.set()
            f1.result(timeout=5.0)
            b.close()

    def test_normal_path_unchanged(self):
        b = DynamicBatcher(lambda x: x * 2, max_batch_size=8,
                           max_delay_ms=1.0, max_queue_depth=64)
        try:
            out = b.infer(np.ones((2, 3), np.float32), timeout=5.0)
            assert np.array_equal(out, np.full((2, 3), 2.0))
        finally:
            b.close()


# ---------------------------------------------------------------------------
# Graceful drain (SIGTERM -> 503 -> in-flight completes -> close)
# ---------------------------------------------------------------------------

class TestGracefulDrain:
    def _server(self, params, **kw):
        engine = InferenceEngine(mlp_apply, params, buckets=(1, 4))
        server = ModelServer(engine, port=0, **kw)
        server.engine.warmup((SIZES[0],))
        return server

    def test_healthz_flips_and_predict_sheds_503(self, params):
        server = self._server(params)
        port = server.start()
        try:
            status, body = _get(port, "/healthz")
            assert json.loads(body)["status"] == "ok"
            server._draining.set()
            status, body = _get(port, "/healthz")
            assert json.loads(body)["status"] == "draining"
            status, doc, headers = _post(port, {"inputs": [_row()]})
            assert status == 503
            assert headers.get("Retry-After") == "1"
        finally:
            server.stop()

    def test_sigterm_installs_drain_flag(self, params):
        server = self._server(params)
        server.start()
        try:
            server.install_drain_handlers()
            assert not server.draining
            signal.raise_signal(signal.SIGTERM)
            _wait_until(lambda: server.draining,
                        "SIGTERM did not set the drain flag")
        finally:
            server.uninstall_drain_handlers()
            server.stop()

    def test_inflight_completes_before_socket_close(self, params):
        server = self._server(params)
        orig = server.batcher._infer

        def slow_infer(x):
            time.sleep(0.4)
            return orig(x)

        server.batcher._infer = slow_infer
        port = server.start()
        result = {}

        def client():
            result["resp"] = _post(port, {"inputs": [_row()]})

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.1)             # request is in flight
        t0 = time.monotonic()
        server.stop()               # drain: must wait for the response
        t.join(timeout=10)
        assert result["resp"][0] == 200
        assert time.monotonic() - t0 >= 0.15

    def test_zero_connection_resets_during_drain(self, params):
        """The regression the satellite demands: sustained client fire
        across a drain sees only 200s and 503+Retry-After — never a
        reset/disconnect."""
        server = self._server(params)
        port = server.start()
        stop = threading.Event()
        statuses, resets = [], []

        def client():
            while not stop.is_set():
                try:
                    status, _doc, headers = _post(
                        port, {"inputs": [_row()]}, timeout=10)
                    statuses.append(status)
                    if status == 503:
                        assert headers.get("Retry-After") == "1"
                except (ConnectionResetError, BrokenPipeError,
                        http.client.RemoteDisconnected) as e:
                    resets.append(repr(e))
                    return
                except (ConnectionRefusedError, OSError):
                    return          # listener closed after drain: clean

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)             # traffic flowing
        assert server.drain(timeout=10.0) is True
        time.sleep(0.3)             # drained; listener still open, so
        stop.set()                  # clients keep seeing clean 503s
        for t in threads:
            t.join(timeout=10)
        server.stop()               # socket closes only after the fire
        for t in threads:
            t.join(timeout=10)
        assert not resets, f"connection resets during drain: {resets}"
        assert statuses.count(200) > 0
        assert set(statuses) <= {200, 503}
        assert 503 in statuses      # the drain window actually shed


# ---------------------------------------------------------------------------
# Replica registrar: heartbeats, drain key, deregistration
# ---------------------------------------------------------------------------

class TestReplicaRegistrar:
    def test_heartbeat_carries_load_and_latency(self, params, kv_server):
        engine = InferenceEngine(mlp_apply, params, buckets=(1, 4))
        server = ModelServer(engine, port=0)
        port = server.start()
        reg = ReplicaRegistrar(_kv_client(kv_server), 7, "127.0.0.1",
                               port, server=server, heartbeat_s=0.3)
        try:
            reg.start()
            _post(port, {"inputs": [_row()]})
            _wait_until(lambda: reg.beats >= 3, "no heartbeats")
            raw = kv_server.get_local(f"{REPLICA_KV_PREFIX}7")
            doc = json.loads(raw.decode())
            assert doc["id"] == 7 and doc["port"] == port
            assert doc["draining"] is False
            assert doc["requests_total"] >= 1
            assert "queue_depth" in doc and "ts" in doc
            assert doc.get("p99_ms") is not None
        finally:
            reg.deregister()
            server.stop()
        assert kv_server.get_local(f"{REPLICA_KV_PREFIX}7") is None

    def test_drain_key_fires_callback_once(self, kv_server):
        fired = []
        reg = ReplicaRegistrar(_kv_client(kv_server), 3, "127.0.0.1", 1,
                               heartbeat_s=0.2,
                               on_drain=lambda: fired.append(1))
        reg.start()
        try:
            assert not reg.drain_requested()
            kv_server.put_local(f"{DRAIN_KV_PREFIX}3", b"drain")
            _wait_until(lambda: fired, "drain callback never fired")
            time.sleep(0.5)
            assert fired == [1]
        finally:
            reg.deregister()


# ---------------------------------------------------------------------------
# Router: discovery, routing, retries, ejection, hedging
# ---------------------------------------------------------------------------

class _InProcReplica:
    """A real ModelServer + registrar, in-process — one serving replica
    the router can discover, route to, and watch die."""

    def __init__(self, kv_server, rid, params, heartbeat_s=0.3):
        self.engine = InferenceEngine(mlp_apply, params, buckets=(1, 4))
        self.server = ModelServer(self.engine, port=0)
        self.server.engine.warmup((SIZES[0],))
        self.port = self.server.start()
        self.reg = ReplicaRegistrar(_kv_client(kv_server), rid,
                                    "127.0.0.1", self.port,
                                    server=self.server,
                                    heartbeat_s=heartbeat_s)
        self.reg.start()

    def crash(self):
        """Abrupt death: socket gone, heartbeats stop, no goodbye."""
        self.reg._stop.set()
        if self.server._httpd is not None:
            self.server._httpd.shutdown()
            self.server._httpd.server_close()
            self.server._httpd = None

    def stop(self):
        self.reg.deregister()
        self.server.stop()


class TestRouter:
    def test_discovers_routes_and_tags_replica(self, params, kv_server):
        rep = _InProcReplica(kv_server, 0, params)
        router = Router(kv_server, port=0, heartbeat_s=0.3, probe=False)
        try:
            rport = router.start()
            _wait_until(lambda: router._routable(), "no routable replica")
            status, doc, headers = _post(rport, {"inputs": [_row()]})
            assert status == 200
            assert len(doc["outputs"]) == 1
            assert headers.get("X-HVDT-Replica") == "0"
            status, body = _get(rport, "/healthz")
            assert json.loads(body)["routable"] == [0]
            status, body = _get(rport, "/metrics")
            assert "hvdt_router_requests_total" in body
        finally:
            router.stop()
            rep.stop()

    def test_no_replica_is_clean_503(self, kv_server):
        router = Router(kv_server, port=0, heartbeat_s=0.2,
                        request_timeout_s=0.5, probe=False)
        try:
            rport = router.start()
            status, doc, headers = _post(rport, {"inputs": [_row()]},
                                         timeout=10)
            assert status == 503
            assert headers.get("Retry-After") == "1"
        finally:
            router.stop()

    def test_replica_crash_mid_load_drops_zero_requests(self, params,
                                                        kv_server):
        """The tentpole claim in miniature: a replica dies under fire;
        the router ejects it on the failed dispatch, retries elsewhere,
        and every client request still answers 200."""
        reps = [_InProcReplica(kv_server, i, params) for i in (0, 1)]
        router = Router(kv_server, port=0, heartbeat_s=0.3,
                        eject_cooldown_s=5.0, hedge_ms=-1.0, probe=False)
        statuses = []
        lock = threading.Lock()
        try:
            rport = router.start()
            _wait_until(lambda: len(router._routable()) == 2,
                        "both replicas never became routable")

            def client(n):
                for _ in range(40):
                    status, _d, _h = _post(rport, {"inputs": [_row()]},
                                           timeout=30)
                    with lock:
                        statuses.append(status)
                    time.sleep(0.005)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            time.sleep(0.2)
            reps[0].crash()          # mid-load, no goodbye
            for t in threads:
                t.join(timeout=60)
            assert len(statuses) == 160
            assert statuses.count(200) == 160, (
                f"dropped/failed requests: "
                f"{[s for s in statuses if s != 200]}")
            m = router.metrics
            assert m.get("hvdt_router_ejections_total").total() >= 1
            # The stale heartbeat ages out within the liveness window.
            _wait_until(lambda: [v.id for v in router._routable()] == [1],
                        "dead replica never aged out of routing",
                        timeout=5.0)
        finally:
            router.stop()
            reps[0].server.stop()    # the crash left its batcher running
            for rep in reps[1:]:
                rep.stop()

    def test_slo_breach_ejects_and_cooldown_readmits(self, kv_server):
        router = Router(kv_server, port=0, heartbeat_s=30.0,
                        slo_p99_ms=100.0, eject_cooldown_s=0.4,
                        probe=False)

        def beat(p99):
            kv_server.put_local(f"{REPLICA_KV_PREFIX}5", json.dumps({
                "id": 5, "host": "127.0.0.1", "port": 1, "ts": time.time(),
                "p99_ms": p99, "queue_depth": 0}).encode())

        beat(20.0)
        router.refresh()
        assert [v.id for v in router._routable()] == [5]
        beat(500.0)                 # p99 blows through the SLO
        router.refresh()
        assert router._routable() == []
        assert router.metrics.get(
            "hvdt_router_ejections_total").value(
            reason="slo", tenant="control") == 1
        time.sleep(0.5)             # cooldown expires
        beat(20.0)                  # and the replica reports healthy
        router.refresh()
        assert [v.id for v in router._routable()] == [5]
        assert router.metrics.get(
            "hvdt_router_readmissions_total").total() == 1

    def test_missed_heartbeat_removes_within_liveness_window(
            self, kv_server):
        router = Router(kv_server, port=0, heartbeat_s=0.2, probe=False)
        kv_server.put_local(f"{REPLICA_KV_PREFIX}9", json.dumps({
            "id": 9, "host": "127.0.0.1", "port": 1,
            "ts": time.time()}).encode())
        router.refresh()
        assert [v.id for v in router._routable()] == [9]
        # No further beats: the doc ts goes stale past 2x heartbeat.
        time.sleep(0.5)
        router.refresh()
        assert router._routable() == []
        assert router.metrics.get(
            "hvdt_router_ejections_total").value(
            reason="heartbeat", tenant="control") == 1

    def test_draining_replica_leaves_without_ejection_event(
            self, kv_server):
        router = Router(kv_server, port=0, heartbeat_s=0.2, probe=False)
        key = f"{REPLICA_KV_PREFIX}4"
        kv_server.put_local(key, json.dumps({
            "id": 4, "host": "127.0.0.1", "port": 1, "ts": time.time(),
            "draining": True}).encode())
        router.refresh()
        assert router._routable() == []   # draining: not routable
        with kv_server.lock:              # clean deregistration
            kv_server.store.pop(key)
        router.refresh()
        assert router.metrics.get(
            "hvdt_router_ejections_total").total() == 0

    def test_hedge_duplicates_slow_primary(self, params, kv_server):
        slow = _InProcReplica(kv_server, 0, params)
        fast = _InProcReplica(kv_server, 1, params)
        orig = slow.server.batcher._infer

        def molasses(x):
            time.sleep(0.8)
            return orig(x)

        slow.server.batcher._infer = molasses
        router = Router(kv_server, port=0, heartbeat_s=0.3,
                        hedge_ms=100.0, probe=False)
        try:
            router.start()
            _wait_until(lambda: len(router._routable()) == 2,
                        "replicas never routable")
            view = next(v for v in router._routable() if v.id == 0)
            body = json.dumps({"inputs": [_row()]}).encode()
            t0 = time.perf_counter()
            status, payload, rid = router._forward_hedged(view, body, 10.0)
            elapsed = time.perf_counter() - t0
            assert status == 200
            assert rid == 1          # the hedge won
            assert elapsed < 0.7     # did not wait out the slow primary
            m = router.metrics
            assert m.get("hvdt_router_hedges_total").total() == 1
            assert m.get("hvdt_router_hedge_wins_total").total() == 1
        finally:
            router.stop()
            fast.stop()
            slow.server.batcher._infer = orig
            slow.stop()


# ---------------------------------------------------------------------------
# Autoscale policy
# ---------------------------------------------------------------------------

def _snap(rid, queue=0.0, p99=None, draining=False):
    d = {"id": rid, "queue_depth": queue, "draining": draining}
    if p99 is not None:
        d["p99_ms"] = p99
    return rid, d


class TestAutoscalePolicy:
    def _policy(self, now, **kw):
        kw.setdefault("max_replicas", 4)
        kw.setdefault("queue_hi", 8.0)
        kw.setdefault("queue_lo", 1.0)
        kw.setdefault("cooldown_s", 10.0)
        return AutoscalePolicy(clock=lambda: now[0], **kw)

    def test_scale_up_on_queue_depth(self):
        now = [0.0]
        p = self._policy(now)
        snaps = dict([_snap(0, queue=20.0)])
        assert p.decide(1, snaps) == 2
        assert "queue" in p.last_reason

    def test_scale_up_on_p99_breach(self):
        now = [0.0]
        p = self._policy(now, slo_p99_ms=250.0)
        snaps = dict([_snap(0, queue=0.0, p99=900.0)])
        assert p.decide(1, snaps) == 2
        assert "SLO" in p.last_reason

    def test_scale_down_when_idle_and_healthy(self):
        now = [0.0]
        p = self._policy(now, slo_p99_ms=250.0)
        snaps = dict([_snap(0, queue=0.0, p99=10.0),
                      _snap(1, queue=0.0, p99=12.0)])
        assert p.decide(3, snaps) == 2

    def test_no_scale_down_while_p99_warm(self):
        now = [0.0]
        p = self._policy(now, slo_p99_ms=250.0)
        snaps = dict([_snap(0, queue=0.0, p99=200.0)])
        assert p.decide(2, snaps) == 2

    def test_cooldown_holds_between_events(self):
        now = [0.0]
        p = self._policy(now)
        snaps = dict([_snap(0, queue=20.0)])
        assert p.decide(1, snaps) == 2
        now[0] = 5.0                 # inside the 10s cooldown
        assert p.decide(2, snaps) == 2
        now[0] = 11.0
        assert p.decide(2, snaps) == 3

    def test_clamped_to_bounds(self):
        now = [0.0]
        p = self._policy(now, max_replicas=2)
        snaps = dict([_snap(0, queue=100.0)])
        assert p.decide(2, snaps) == 2      # ceiling
        assert p.decide(7, snaps) == 2      # clamp down
        idle = dict([_snap(0, queue=0.0)])
        now[0] = 100.0
        assert p.decide(1, idle) == 1       # floor

    def test_draining_replicas_ignored(self):
        now = [0.0]
        p = self._policy(now)
        snaps = dict([_snap(0, queue=50.0, draining=True),
                      _snap(1, queue=2.0)])
        assert p.decide(2, snaps) == 2      # drained load doesn't count


# ---------------------------------------------------------------------------
# ServeDriver: lifecycle on the elastic machinery
# ---------------------------------------------------------------------------

class _FakeFleet:
    """In-process replica processes: each spawn publishes heartbeats and
    polls its drain key, exactly like run_replica, without the HTTP or
    jax weight."""

    def __init__(self, kv_server):
        self.kv = kv_server
        self.stops = {}
        self.exit_codes = {}
        self.queue_depth = 0.0
        self.spawned = []

    def spawn(self, slot, rid):
        self.spawned.append((rid, slot.hostname))
        ev = threading.Event()
        self.stops[rid] = ev
        key = f"{REPLICA_KV_PREFIX}{rid}"
        while True:
            self.kv.put_local(key, json.dumps({
                "id": rid, "host": slot.hostname, "port": 1,
                "ts": time.time(), "queue_depth": self.queue_depth,
                "p99_ms": 10.0, "draining": False}).encode())
            if self.kv.get_local(f"{DRAIN_KV_PREFIX}{rid}") is not None:
                return PREEMPT_EXIT_CODE
            if ev.wait(0.05):
                return self.exit_codes.get(rid, 1)

    def kill(self, rid, code=1):
        self.exit_codes[rid] = code
        self.stops[rid].set()


class TestServeDriver:
    def _driver(self, kv_server, fleet, **kw):
        kw.setdefault("replicas", 1)
        kw.setdefault("max_replicas", 3)
        kw.setdefault("interval", 0.05)
        return ServeDriver(kv_server, fleet.spawn, **kw)

    def test_scale_up_and_graceful_scale_down(self, kv_server):
        fleet = _FakeFleet(kv_server)
        driver = self._driver(kv_server, fleet)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "initial replica never spawned")
            driver.set_target(3, reason="test")
            _wait_until(lambda: len(driver.live_replicas()) == 3,
                        "scale-up to 3 never converged")
            driver.set_target(2, reason="test")
            _wait_until(lambda: len(driver.live_replicas()) == 2,
                        "scale-down to 2 never converged")
            # Graceful: drained exits are clean — zero removal events.
            assert driver.removal_events == 0
            assert any("scaling 1 -> 3" in e for e in driver.scale_events)
            assert any("scaling 3 -> 2" in e for e in driver.scale_events)
        finally:
            driver.stop(drain=True, timeout=5)

    def test_crash_is_one_removal_event_and_respawn_after_cooldown(
            self, kv_server, monkeypatch):
        monkeypatch.setenv("HVDT_ELASTIC_BLACKLIST_COOLDOWN_S", "0.3")
        fleet = _FakeFleet(kv_server)
        driver = self._driver(kv_server, fleet, replicas=2)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 2,
                        "fleet never reached 2")
            victim = driver.live_replicas()[0]
            fleet.kill(victim, code=1)
            _wait_until(lambda: driver.removal_events == 1,
                        "crash never became a removal event")
            # The host sat out its cooldown, then a replacement spawned.
            _wait_until(lambda: len(driver.live_replicas()) == 2,
                        "replacement never spawned after cooldown",
                        timeout=10.0)
            assert victim not in driver.live_replicas()
            assert driver.removal_events == 1   # exactly one event
            # The crashed replica's stale KV records were scrubbed.
            assert kv_server.get_local(
                f"{REPLICA_KV_PREFIX}{victim}") is None
        finally:
            driver.stop(drain=True, timeout=5)

    def test_crash_tombstones_replica_id(self, kv_server, monkeypatch):
        """A worker that outlives its wrapper process keeps beating; the
        drain tombstone left by record_exit makes it fence itself out
        instead of re-entering routing as untracked capacity."""
        monkeypatch.setenv("HVDT_ELASTIC_BLACKLIST_COOLDOWN_S", "0.2")
        fleet = _FakeFleet(kv_server)
        driver = self._driver(kv_server, fleet, replicas=1)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "replica never spawned")
            victim = driver.live_replicas()[0]
            fleet.kill(victim, code=1)
            _wait_until(lambda: driver.removal_events == 1,
                        "crash never became a removal event")
            assert kv_server.get_local(
                f"{DRAIN_KV_PREFIX}{victim}") == b"fence"
        finally:
            driver.stop(drain=True, timeout=5)

    def test_preempt_exit_drains_pod_from_placement(self, kv_server):
        fleet = _FakeFleet(kv_server)
        driver = self._driver(kv_server, fleet, replicas=1)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "replica never spawned")
            rid = driver.live_replicas()[0]
            fleet.kill(rid, code=PREEMPT_EXIT_CODE)   # host preempted
            _wait_until(lambda: rid not in driver.live_replicas(),
                        "preempted replica never removed")
            assert driver.removal_events == 0         # clean removal
            # The pod is drained: no respawn while the grace holds.
            time.sleep(0.3)
            assert driver._free_slot() is None
        finally:
            driver.stop(drain=False)

    def test_kv_target_override_wins(self, kv_server):
        fleet = _FakeFleet(kv_server)
        driver = self._driver(kv_server, fleet, replicas=1)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "initial replica never spawned")
            kv_server.put_local(TARGET_KV_KEY, b"3")
            _wait_until(lambda: len(driver.live_replicas()) == 3,
                        "KV override never adopted")
        finally:
            driver.stop(drain=True, timeout=5)

    def test_target_file_override(self, kv_server, tmp_path):
        fleet = _FakeFleet(kv_server)
        target = os.path.join(tmp_path, "target")
        driver = self._driver(kv_server, fleet, replicas=1,
                              target_file=target)
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "initial replica never spawned")
            with open(target, "w") as f:
                f.write("2\n")
            _wait_until(lambda: len(driver.live_replicas()) == 2,
                        "target file never adopted")
        finally:
            driver.stop(drain=True, timeout=5)

    def test_autoscale_loop_scales_on_queue_then_idles_down(
            self, kv_server):
        fleet = _FakeFleet(kv_server)
        fleet.queue_depth = 50.0
        driver = self._driver(
            kv_server, fleet, replicas=1, autoscale=True,
            policy=AutoscalePolicy(max_replicas=3, queue_hi=8.0,
                                   queue_lo=1.0, cooldown_s=0.1))
        try:
            driver.start()
            _wait_until(lambda: len(driver.live_replicas()) == 3,
                        "autoscaler never scaled to max under load",
                        timeout=10.0)
            fleet.queue_depth = 0.0
            _wait_until(lambda: len(driver.live_replicas()) == 1,
                        "autoscaler never idled back down", timeout=10.0)
            assert driver.removal_events == 0   # every resize graceful
        finally:
            driver.stop(drain=True, timeout=5)


# ---------------------------------------------------------------------------
# ElasticDriver scale hook
# ---------------------------------------------------------------------------

class TestElasticDriverResize:
    def test_resize_updates_bounds_and_notifies(self):
        from horovod_tpu.runner.elastic.driver import ElasticDriver
        from horovod_tpu.runner.elastic.discovery import HostManager
        from horovod_tpu.runner.hosts import HostInfo

        hm = HostManager(lambda: [HostInfo("localhost", 8)])
        hm.update_available_hosts()
        pings = []
        driver = ElasticDriver(hm, min_np=2, max_np=2,
                               spawn_fn=lambda s, g: 0,
                               hosts_updated_cb=pings.append)
        driver.resize(min_np=4, max_np=6)
        assert driver._min_np == 4 and driver._max_np == 6
        assert pings == [1]          # live workers get nudged
        driver.resize(max_np=3)      # max clamps to min
        assert driver._max_np == 4


# ---------------------------------------------------------------------------
# CLI / config wiring
# ---------------------------------------------------------------------------

class TestCliWiring:
    def test_serve_knobs_registered(self):
        from horovod_tpu.common import config

        for name in ("HVDT_SERVE_HEARTBEAT_S", "HVDT_SERVE_SLO_P99_MS",
                     "HVDT_SERVE_REPLICAS", "HVDT_SERVE_MAX_REPLICAS",
                     "HVDT_SERVE_AUTOSCALE", "HVDT_SERVE_SCALE_COOLDOWN_S",
                     "HVDT_SERVE_QUEUE_HI", "HVDT_SERVE_QUEUE_LO",
                     "HVDT_SERVE_ROUTER_PORT",
                     "HVDT_SERVE_EJECT_COOLDOWN_S", "HVDT_SERVE_HEDGE_MS"):
            assert name in config.KNOBS

    def test_serve_cli_flags_parse(self):
        from horovod_tpu.serve.__main__ import parse_args

        args = parse_args(["--checkpoint", "/c", "--replicas", "3",
                           "--autoscale", "--slo-p99-ms", "250",
                           "--max-replicas", "5", "--router-port", "0"])
        assert args.replicas == 3 and args.autoscale
        assert args.slo_p99_ms == 250.0 and args.max_replicas == 5

    def test_strip_control_flags_keeps_model_args(self):
        from horovod_tpu.serve.__main__ import strip_control_flags

        argv = ["--checkpoint", "/c", "--replicas", "3", "--autoscale",
                "--slo-p99-ms", "250", "--model", "mlp",
                "--mlp-sizes", "6,16,3", "--target-file", "/t"]
        assert strip_control_flags(argv) == [
            "--checkpoint", "/c", "--model", "mlp",
            "--mlp-sizes", "6,16,3"]

    def test_yaml_serve_section_forwards_as_env(self, tmp_path):
        from horovod_tpu.runner.config_parser import (apply_config_file,
                                                      env_from_args)
        from horovod_tpu.runner.launch import parse_args

        cfg = os.path.join(tmp_path, "c.yaml")
        with open(cfg, "w") as f:
            f.write("serve:\n  replicas: 2\n  max_replicas: 4\n"
                    "  autoscale: true\n  slo_p99_ms: 250\n"
                    "  heartbeat_s: 1.5\n")
        args = parse_args(["--config-file", cfg, "--", "python", "t.py"])
        file_values = apply_config_file(args, cfg)
        env = env_from_args(args, file_values, base_env={})
        assert env["HVDT_SERVE_REPLICAS"] == "2"
        assert env["HVDT_SERVE_MAX_REPLICAS"] == "4"
        assert env["HVDT_SERVE_AUTOSCALE"] == "1"
        assert float(env["HVDT_SERVE_SLO_P99_MS"]) == 250.0
        assert float(env["HVDT_SERVE_HEARTBEAT_S"]) == 1.5

    def test_localhost_host_manager_slots(self):
        hm = localhost_host_manager(3)
        hm.update_available_hosts()
        assert hm.current.available_slots == 3


# ---------------------------------------------------------------------------
# Multiprocess acceptance: 1 -> 3 -> 2 with a serve_crash mid-run
# ---------------------------------------------------------------------------

# Marked slow: ~15 s alone, but tier-1 already runs near its 870 s
# budget ceiling — this scenario runs in the test-smoke compose service
# (ci/gen-matrix.sh --smoke), which does not filter the slow marker.
@pytest.mark.slow
@pytest.mark.integration
def test_serve_elastic_resize_and_crash_zero_dropped(tmp_path, spawn):
    """The acceptance scenario: a real `hvdtrun serve --replicas`
    control plane (RendezvousServer + ServeDriver + Router, replica
    subprocesses) scales 1 -> 3 -> 2 under synthetic client load while
    ``serve_crash@step=25:rank=1`` kills replica 1 mid-request.
    Client-side id accounting proves zero dropped/duplicated requests,
    p99 outside the ejection window holds the SLO, and the kill is
    exactly one replica-removal control-plane event."""
    target_file = os.path.join(tmp_path, "target")
    ckpt_dir = os.path.join(tmp_path, "ckpts")
    os.makedirs(ckpt_dir, exist_ok=True)
    slo_ms = 2000.0
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "HVDT_SERVE_HEARTBEAT_S": "1.0",
        "HVDT_SERVE_EJECT_COOLDOWN_S": "2",
        "HVDT_ELASTIC_BLACKLIST_COOLDOWN_S": "2",
        "HVDT_FAULT_PLAN": "serve_crash@step=25:rank=1",
    })
    proc = spawn(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "serve",
         "--checkpoint", ckpt_dir, "--model", "mlp",
         "--mlp-sizes", ",".join(map(str, SIZES)),
         "--buckets", "1,4", "--replicas", "1", "--max-replicas", "3",
         "--autoscale", "--slo-p99-ms", str(slo_ms),
         "--target-file", target_file],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)

    lines = []
    marks = {}

    def _reader():
        for raw in proc.stdout:
            ln = raw.decode(errors="replace")
            lines.append(ln)
            if "replica-removal event" in ln and "kill" not in marks:
                marks["kill"] = time.monotonic()

    reader = threading.Thread(target=_reader, daemon=True)
    reader.start()

    def _fail(why):
        proc.kill()
        pytest.fail(f"{why}:\n{''.join(lines)[-4000:]}")

    def _wait(cond, why, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        _fail(why)

    try:
        # Router endpoint from the control-plane log.
        _wait(lambda: any("serve: router on http://" in ln
                          for ln in lines),
              "router never came up", 120)
        rline = next(ln for ln in lines if "serve: router on http://" in ln)
        rport = int(rline.split("http://", 1)[1].split()[0]
                    .rsplit(":", 1)[1])

        def routable():
            try:
                _s, body = _get(rport, "/healthz", timeout=5)
                return json.loads(body)["routable"]
            except (OSError, ValueError):
                return []

        _wait(lambda: len(routable()) >= 1,
              "first replica never became routable", 120)
        # Scale 1 -> 3 (operator override; the autoscaler is live too).
        with open(target_file, "w") as f:
            f.write("3")
        _wait(lambda: len(routable()) >= 3,
              "fleet never scaled to 3", 180)

        # Synthetic client load with id accounting.  The fault plan
        # kills replica 1 at its 25th admitted request — mid-load.
        results = {}
        latencies = []
        lock = threading.Lock()

        def client(cid, n):
            for i in range(n):
                rid = f"{cid}-{i}"
                t0 = time.perf_counter()
                try:
                    status, _d, _h = _post(rport, {"inputs": [_row()]},
                                           timeout=30)
                except OSError as e:
                    status = f"exc:{e!r}"
                ms = (time.perf_counter() - t0) * 1000.0
                with lock:
                    results[rid] = results.get(rid, []) + [status]
                    latencies.append((time.monotonic(), ms))
                time.sleep(0.02)

        threads = [threading.Thread(target=client, args=(c, 200))
                   for c in range(4)]
        t_load = time.monotonic()
        for t in threads:
            t.start()
        # The kill lands while the load runs.
        _wait(lambda: "kill" in marks, "serve_crash never killed a "
              "replica (removal event missing)", 120)
        for t in threads:
            t.join(timeout=180)
        assert all(not t.is_alive() for t in threads), \
            "client threads hung"

        # Zero dropped, zero duplicated: every id answered exactly once,
        # every answer a 200 — through a replica crash.
        assert len(results) == 800
        bad = {k: v for k, v in results.items() if v != [200]}
        assert not bad, f"dropped/failed/duplicated: {bad}"

        # Exactly ONE removal event for the killed replica.
        text = "".join(lines)
        assert text.count("replica-removal event") == 1
        assert "replica-removal event for replica 1" in text

        # p99 holds the SLO outside a bounded ejection window around
        # the kill (the router's detect-eject-retry happens inside it).
        kill_t = marks["kill"]
        outside = [ms for (ts, ms) in latencies
                   if not (kill_t - 0.5 <= ts <= kill_t + 2.0)]
        assert len(outside) >= 100
        outside.sort()
        p99 = outside[min(len(outside) - 1,
                          int(0.99 * len(outside)))]
        assert p99 < slo_ms, f"p99 {p99:.0f}ms breached SLO {slo_ms}ms"

        # Scale 3 -> 2: one replica drains gracefully (exit 83, clean).
        with open(target_file, "w") as f:
            f.write("2")
        _wait(lambda: len(routable()) == 2,
              "fleet never scaled down to 2", 120)
        _wait(lambda: "".join(lines).count("exited clean (drained)") >= 1,
              "scale-down drain never completed cleanly", 60)

        # A few post-resize requests still answer.
        for i in range(5):
            status, _d, _h = _post(rport, {"inputs": [_row()]},
                                   timeout=30)
            assert status == 200

        # The whole trajectory is in the control-plane audit log.
        text = "".join(lines)
        assert "serve: scaling 1 -> 3" in text
        assert "serve: scaling 3 -> 2" in text
        assert t_load is not None
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
        reader.join(timeout=10)
