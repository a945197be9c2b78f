"""Fleet scheduler tests: the shared pod inventory, the seq-guarded
replica-target doc (one key, many writers), the bin-packing scheduler's
pricing + guardrail battery + never-worse rollback, the traffic-trace
builders, the trace-driven CPU chaos simulation, the ``traffic_spike``
fault kind, and the CLI/config/metrics/report wiring.

Everything in-process and CPU except the final day-in-the-life scenario
(real RendezvousServer, real ServeDriver spawning replica worker
subprocesses, real router + client load, the fleet scheduler moving
pods between the two workloads through the seq-guarded target doc) —
that one is ``slow`` and runs in the test-smoke compose service.
"""

import http.client
import json
import os
import sys
import threading
import time

import pytest

from horovod_tpu.fleet import (FleetConfig, FleetInventory, FleetScheduler,
                               Move, TrafficTrace, load_trace, read_target,
                               write_target)
from horovod_tpu.fleet import get_scheduler, install, reset
from horovod_tpu.fleet.simulate import simulate_trace
from horovod_tpu.fleet.traces import (BUILTIN_TRACES, diurnal, flash_crowd,
                                      step_function)
from horovod_tpu.resilience.faults import FaultInjector, parse_plan
from horovod_tpu.runner.elastic.discovery import HostManager
from horovod_tpu.runner.hosts import HostInfo
from horovod_tpu.runner.http_kv import RendezvousServer
from horovod_tpu.serve.autoscale import TARGET_KV_KEY, ServeDriver
from horovod_tpu.telemetry.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def kv_server():
    server = RendezvousServer()
    server.start()
    yield server
    server.stop()


def _inventory(n=5, serve_units=1, clock=None):
    names = [f"pod{i}" for i in range(n)]
    hm = HostManager(lambda: [HostInfo(p, 4, pod=p) for p in names])
    inv = FleetInventory(names, host_manager=hm,
                         **({"clock": clock} if clock else {}))
    for p in names[:serve_units]:
        inv.acquire(p, "serve")
    for p in names[serve_units:]:
        inv.acquire(p, "train")
    return inv


def _scheduler(inv, clock=None, event_log=None, **cfg_kw):
    cfg_kw.setdefault("cooldown_s", 0.0)
    cfg_kw.setdefault("enter_ratio", 1.2)
    cfg_kw.setdefault("exit_ratio", 1.05)
    cfg_kw.setdefault("backfill_ratio", 0.5)
    cfg_kw.setdefault("recovery_window", 2)
    cfg_kw.setdefault("queue_hi", 8.0)
    kw = {"registry": MetricsRegistry(), "event_log": event_log}
    if clock is not None:
        kw["clock"] = clock
    return FleetScheduler(inv, cfg=FleetConfig(**cfg_kw), **kw)


# ---------------------------------------------------------------------------
# Inventory: leases over shared failure state
# ---------------------------------------------------------------------------

class TestInventory:
    def test_acquire_release_and_kinds(self):
        inv = _inventory(3)
        assert inv.leased("serve") == ["pod0"]
        assert inv.leased("train") == ["pod1", "pod2"]
        assert inv.available() == []
        assert not inv.acquire("pod1", "serve")     # already leased
        assert not inv.acquire("podX", "train")     # unknown
        with pytest.raises(ValueError):
            inv.acquire("pod1", "gpu")              # unknown kind
        assert inv.release("pod1")
        assert inv.available() == ["pod1"]
        assert inv.acquire("pod1", "serve")
        assert inv.lease_of("pod1").kind == "serve"

    def test_release_is_exactly_once(self):
        inv = _inventory(3)
        assert inv.release("pod2")
        assert not inv.release("pod2")              # double-release: no-op
        assert inv.release_events == 1

    def test_failure_is_one_event_shared_by_both_workloads(self):
        inv = _inventory(4)
        assert inv.record_failure("pod2", now=0.0)
        # The slice's remaining rank exits fold into the SAME event.
        assert not inv.record_failure("pod2", now=0.5)
        assert inv.tracker.removal_events == 1
        assert inv.release_events == 1
        # Blacklisted for BOTH workloads: neither can lease it again.
        assert not inv.acquire("pod2", "train")
        assert not inv.acquire("pod2", "serve")
        assert "pod2" not in inv.available()

    def test_drain_releases_and_excludes(self):
        inv = _inventory(3)
        assert inv.drain("pod1")
        assert inv.lease_of("pod1") is None
        assert "pod1" not in inv.available()
        d = inv.describe()
        assert d["release_events"] == 1
        assert d["removal_events"] == 0


# ---------------------------------------------------------------------------
# The seq-guarded /serve/target_replicas doc (satellite: two writers race)
# ---------------------------------------------------------------------------

class TestTargetDoc:
    def test_read_target_three_forms(self):
        assert read_target(None) is None
        assert read_target(b"3") == {"target": 3, "seq": None,
                                     "writer": "operator"}
        doc = read_target(json.dumps(
            {"target": 2, "seq": 5, "writer": "fleet"}).encode())
        assert doc["target"] == 2 and doc["seq"] == 5
        assert read_target(b"banana") is None
        assert read_target(b"[1,2]") is None
        assert read_target(b'{"seq": 1}') is None

    def test_write_target_bumps_seq_and_stamps_writer(self, kv_server):
        d1 = write_target(kv_server, 2, writer="fleet", reason="spike")
        assert d1["seq"] == 1 and d1["writer"] == "fleet"
        d2 = write_target(kv_server, 3, writer="controller")
        assert d2["seq"] == 2
        cur = read_target(kv_server.get_local(TARGET_KV_KEY))
        assert cur["target"] == 3 and cur["writer"] == "controller"

    def test_operator_raw_int_owns_the_key(self, kv_server):
        with kv_server.lock:
            kv_server.store[TARGET_KV_KEY] = b"4"
        assert write_target(kv_server, 2, writer="fleet") is None
        cur = read_target(kv_server.get_local(TARGET_KV_KEY))
        assert cur["target"] == 4 and cur["seq"] is None

    def test_expect_seq_cas_refuses_stale_writer(self, kv_server):
        write_target(kv_server, 2, writer="fleet")          # seq 1
        # Two writers read seq=1; the first CAS wins, the second is
        # refused instead of clobbering — the race this satellite pins.
        assert write_target(kv_server, 3, writer="fleet",
                            expect_seq=1) is not None
        assert write_target(kv_server, 9, writer="controller",
                            expect_seq=1) is None
        cur = read_target(kv_server.get_local(TARGET_KV_KEY))
        assert cur["target"] == 3 and cur["seq"] == 2

    def test_concurrent_writers_serialize(self, kv_server):
        def writer(name):
            for _ in range(10):
                write_target(kv_server, 2, writer=name)

        threads = [threading.Thread(target=writer, args=(f"w{i}",))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        cur = read_target(kv_server.get_local(TARGET_KV_KEY))
        assert cur["seq"] == 80      # every write bumped exactly once

    def test_driver_adopts_fleet_doc_with_audit_trail(self, kv_server):
        driver = ServeDriver(kv_server, lambda slot, rid: 0,
                             replicas=1, max_replicas=4)
        write_target(kv_server, 3, writer="fleet", reason="spike")
        driver.reconcile()
        try:
            assert driver.target == 3
            assert driver.last_target_writer == "fleet"
            assert driver.last_target_seq == 1
            # The raw-int operator channel still beats the fleet doc.
            with kv_server.lock:
                kv_server.store[TARGET_KV_KEY] = b"2"
            driver.reconcile()
            assert driver.target == 2
            assert driver.last_target_writer == "operator"
            assert driver.last_target_seq is None
        finally:
            driver.stop(drain=False, timeout=2)


# ---------------------------------------------------------------------------
# Pricing + ranking (the shared sim/live ranking)
# ---------------------------------------------------------------------------

class TestPricing:
    def test_train_step_seconds_monotone_in_pods(self):
        sched = _scheduler(_inventory(5))
        s2 = sched.train_step_seconds(2)
        s4 = sched.train_step_seconds(4)
        assert s2 > 0 and s4 > 0
        assert sched.train_throughput(4) > sched.train_throughput(2)

    def test_pressure_is_max_of_queue_and_p99_terms(self):
        sched = _scheduler(_inventory(5))
        assert sched.pressure(16.0, None, 0.0) == pytest.approx(2.0)
        assert sched.pressure(0.0, 500.0, 250.0) == pytest.approx(2.0)
        assert sched.pressure(16.0, 750.0, 250.0) == pytest.approx(3.0)

    def test_rank_reclaims_prefers_straggler_pod(self):
        sched = _scheduler(_inventory(5))
        medians = {"pod1": 1.0, "pod2": 1.0, "pod3": 1.0, "pod4": 2.5}
        ranked = sched.rank_reclaims(serve_units=1, pressure=2.0,
                                     pod_step_medians=medians)
        assert ranked[0].move.pod == "pod4"   # slowest costs least
        gains = [pm.predicted_gain for pm in ranked]
        assert gains == sorted(gains, reverse=True)

    def test_rank_reclaims_respects_min_train_pods_floor(self):
        inv = _inventory(3, serve_units=1)     # 2 train pods
        sched = _scheduler(inv, min_train_pods=2)
        assert sched.rank_reclaims(serve_units=1, pressure=3.0) == []

    def test_sim_and_live_ranking_agree_on_same_inputs(self):
        """The acceptance pin: the CPU simulator's reclaim ranking and
        the live scheduler's decision ranking are the same function on
        the same inputs — build one scheduler on a virtual clock and
        one on the real clock and compare."""
        medians = {"pod1": 1.1, "pod2": 0.9, "pod3": 1.8, "pod4": 1.0}
        now = [0.0]
        sim = _scheduler(_inventory(5), clock=lambda: now[0])
        live = _scheduler(_inventory(5))
        kw = dict(serve_units=2, pressure=1.9, pod_step_medians=medians)
        sim_rank = [pm.move.pod for pm in sim.rank_reclaims(**kw)]
        live_rank = [pm.move.pod for pm in live.rank_reclaims(**kw)]
        assert sim_rank == live_rank
        for a, b in zip(sim.rank_reclaims(**kw), live.rank_reclaims(**kw)):
            assert a.predicted_gain == pytest.approx(b.predicted_gain)


# ---------------------------------------------------------------------------
# Scheduler: guardrails, hysteresis, rollback
# ---------------------------------------------------------------------------

def _bind_counters(sched, inv=None, fail_kinds=()):
    applied = []

    def applier(move):
        if move.kind in fail_kinds:
            return False
        applied.append(move)
        return True

    sched.bind("reclaim", applier)
    sched.bind("backfill", applier)
    return applied


class TestScheduler:
    def test_quiet_pressure_no_moves(self):
        sched = _scheduler(_inventory(5))
        applied = _bind_counters(sched)
        assert sched.tick(queue_per_replica=2.0) == []
        assert applied == []

    def test_reclaim_applies_and_relabels_lease(self):
        inv = _inventory(5)
        sched = _scheduler(inv)
        applied = _bind_counters(sched)
        (d,) = sched.tick(queue_per_replica=16.0, step=1)
        assert d.outcome == "applied"
        assert d.chosen.move.kind == "reclaim"
        assert len(applied) == 1
        assert inv.lease_of(applied[0].pod).kind == "serve"
        assert len(inv.leased("serve")) == 2
        assert len(inv.leased("train")) == 3

    def test_hysteresis_disarms_trigger_until_recovery(self):
        now = [0.0]
        inv = _inventory(5, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0])
        _bind_counters(sched)
        (d1,) = sched.tick(queue_per_replica=16.0, step=1)
        assert d1.outcome == "applied"
        now[0] = 10.0
        (d2,) = sched.tick(queue_per_replica=16.0, step=2)
        assert d2.outcome == "suppressed:hysteresis"
        # Recovery (pressure under the exit band, above the trough
        # band) re-arms the trigger without looking like a backfill.
        now[0] = 20.0
        assert sched.tick(queue_per_replica=5.0, step=3) == []
        now[0] = 30.0
        (d3,) = sched.tick(queue_per_replica=16.0, step=4)
        assert d3.outcome == "applied"

    def test_cooldown_suppresses_next_move_of_kind(self):
        now = [0.0]
        inv = _inventory(5, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0], cooldown_s=60.0,
                           recovery_window=1)
        _bind_counters(sched)
        sched.tick(queue_per_replica=16.0, step=1)
        now[0] = 5.0
        sched.tick(queue_per_replica=5.0, step=2)   # recover + re-arm
        now[0] = 10.0                                # inside cooldown
        (d,) = sched.tick(queue_per_replica=16.0, step=3)
        assert d.outcome == "suppressed:cooldown"
        now[0] = 120.0                               # cooldown expired
        (d2,) = sched.tick(queue_per_replica=16.0, step=4)
        assert d2.outcome == "applied"

    def test_budget_caps_lifetime_moves(self):
        now = [0.0]
        inv = _inventory(6, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0], max_moves=1,
                           recovery_window=1)
        _bind_counters(sched)
        sched.tick(queue_per_replica=16.0, step=1)
        now[0] = 10.0
        sched.tick(queue_per_replica=5.0, step=2)   # recover + re-arm
        now[0] = 20.0
        (d,) = sched.tick(queue_per_replica=16.0, step=3)
        assert d.outcome == "suppressed:budget"
        assert sched.moves_applied["reclaim"] == 1

    def test_observe_mode_decides_without_moving(self):
        inv = _inventory(5)
        sched = _scheduler(inv, mode="observe")
        applied = _bind_counters(sched)
        (d,) = sched.tick(queue_per_replica=16.0, step=1)
        assert d.outcome == "observed"
        assert applied == []
        assert inv.leased("serve") == ["pod0"]      # nothing moved

    def test_apply_failure_is_suppressed_not_fatal(self):
        inv = _inventory(5)
        sched = _scheduler(inv)
        _bind_counters(sched, fail_kinds=("reclaim",))
        (d,) = sched.tick(queue_per_replica=16.0, step=1)
        assert d.outcome == "suppressed:apply_failed"
        assert inv.leased("serve") == ["pod0"]      # lease untouched

    def test_backfill_on_trough_returns_newest_serve_pod(self):
        inv = _inventory(5, serve_units=3)           # pod0..2 serve
        sched = _scheduler(inv)
        applied = _bind_counters(sched)
        (d,) = sched.tick(queue_per_replica=0.5, step=1)
        assert d.outcome == "applied"
        assert d.chosen.move.kind == "backfill"
        assert applied[0].pod == "pod2"              # newest serve pod
        assert inv.lease_of("pod2").kind == "train"

    def test_reclaim_rolls_back_when_pressure_got_worse(self):
        now = [0.0]
        inv = _inventory(5, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0], cooldown_s=10.0,
                           recovery_window=2)
        applied = _bind_counters(sched)
        (d,) = sched.tick(queue_per_replica=16.0, step=1)
        pod = d.chosen.move.pod
        # Pressure gets WORSE through the window: the move hurt.
        now[0] = 1.0
        sched.tick(queue_per_replica=20.0, step=2)
        now[0] = 2.0
        sched.tick(queue_per_replica=24.0, step=3)
        assert sched.rollbacks == 1
        assert inv.lease_of(pod).kind == "train"     # inverse applied
        assert applied[-1].kind == "backfill"
        assert applied[-1].pod == pod
        # Doubled cooldown: the next reclaim sits out 2x the base.
        now[0] = 15.0
        (d2,) = sched.tick(queue_per_replica=16.0, step=4)
        assert d2.outcome == "suppressed:hysteresis"

    def test_sustained_pressure_drives_successive_reclaims(self):
        """Never-worse means "roll back moves that HURT": a reclaim
        that merely wasn't singly sufficient (pressure flat, not worse)
        recovers at window expiry, so a sustained flash crowd ratchets
        through several reclaims instead of wedging after one."""
        now = [0.0]
        inv = _inventory(6, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0], recovery_window=2)
        _bind_counters(sched)
        reclaims = 0
        for i in range(12):
            now[0] = float(i)
            for d in sched.tick(queue_per_replica=16.0, step=i):
                if d.outcome == "applied":
                    reclaims += 1
        assert reclaims >= 3
        assert sched.rollbacks == 0

    def test_backfill_rolls_back_fast_when_it_tips_serving(self):
        now = [0.0]
        inv = _inventory(5, serve_units=3, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0])
        _bind_counters(sched)
        (d,) = sched.tick(queue_per_replica=0.5, step=1)
        assert d.chosen.move.kind == "backfill"
        pod = d.chosen.move.pod
        now[0] = 1.0
        sched.tick(queue_per_replica=16.0, step=2)   # tipped over
        assert sched.rollbacks == 1
        assert inv.lease_of(pod).kind == "serve"

    def test_hint_scale_routes_controller_through_guardrails(self):
        inv = _inventory(5)
        sched = _scheduler(inv)
        applied = _bind_counters(sched)
        sched.tick(queue_per_replica=5.0, step=1)    # seed signals
        # A non-growth hint is recorded and dropped.
        assert sched.hint_scale(1, source="controller")
        assert applied == []
        # A growth hint becomes a reclaim under the full battery.
        assert sched.hint_scale(2, source="controller", reason="slo")
        assert len(applied) == 1
        assert applied[0].kind == "reclaim"
        assert len(inv.leased("serve")) == 2

    def test_decisions_land_in_event_log(self, tmp_path):
        from horovod_tpu.telemetry.anomaly import EventLog, read_event_log

        path = os.path.join(tmp_path, "events.jsonl")
        inv = _inventory(5)
        sched = _scheduler(inv, event_log=EventLog(path))
        _bind_counters(sched)
        sched.tick(queue_per_replica=16.0, step=7)
        recs = read_event_log(path)
        assert recs and recs[0]["kind"] == "fleet_decision"
        assert recs[0]["outcome"] == "applied"
        assert recs[0]["chosen"]["move"]["kind"] == "reclaim"
        assert recs[0]["step"] == 7


# ---------------------------------------------------------------------------
# Drain under failure (satellite: pod_crash DURING a reclaim)
# ---------------------------------------------------------------------------

class TestDrainUnderFailure:
    def test_crash_mid_reclaim_one_event_one_release_then_retry(self):
        """A pod_crash landing DURING a reclaim's drain must cost one
        removal event and one lease release — and the scheduler's next
        tick retries the reclaim on a DIFFERENT pod."""
        now = [0.0]
        inv = _inventory(5, clock=lambda: now[0])
        sched = _scheduler(inv, clock=lambda: now[0])
        crashed = []

        def reclaim(move):
            if not crashed:
                # The drained pod dies mid-reclaim: correlated rank
                # exits arrive through the shared inventory...
                crashed.append(move.pod)
                assert inv.record_failure(move.pod, now=now[0])
                # ...and fold into ONE event; the applier reports the
                # move failed (its pod is gone).
                assert not inv.record_failure(move.pod, now=now[0])
                return False
            return True

        sched.bind("reclaim", reclaim)
        (d1,) = sched.tick(queue_per_replica=16.0, step=1)
        assert d1.outcome == "suppressed:apply_failed"
        assert inv.tracker.removal_events == 1
        assert inv.release_events == 1               # exactly once
        victim = crashed[0]
        assert inv.lease_of(victim) is None
        # Retry lands elsewhere: the crashed pod is blacklisted out of
        # the candidate set, not double-counted.
        now[0] = 1.0
        (d2,) = sched.tick(queue_per_replica=16.0, step=2)
        assert d2.outcome == "applied"
        assert d2.chosen.move.pod != victim
        assert inv.tracker.removal_events == 1       # still one event

    def test_simulated_pod_crash_is_one_removal_event(self):
        trace = TrafficTrace("steady", ((0.0, 80.0), (600.0, 80.0)))
        report = simulate_trace(trace, pods=4, tick_s=10.0,
                                fault_plan="pod_crash@step=5:pod=pod2",
                                cfg=FleetConfig(queue_hi=8.0))
        assert report["faults"].get("pod_crash", 0) >= 1
        assert report["removal_events"] == 1
        assert "pod2" not in (report["final"]["train_pods"],)


# ---------------------------------------------------------------------------
# Traffic traces
# ---------------------------------------------------------------------------

class TestTraces:
    def test_rps_at_interpolates_and_clamps(self):
        t = TrafficTrace("t", ((0.0, 10.0), (100.0, 110.0)))
        assert t.rps_at(-5) == 10.0
        assert t.rps_at(0) == 10.0
        assert t.rps_at(50) == pytest.approx(60.0)
        assert t.rps_at(100) == 110.0
        assert t.rps_at(1e9) == 110.0
        assert t.duration_s == 100.0

    def test_points_must_ascend(self):
        with pytest.raises(ValueError):
            TrafficTrace("bad", ((10.0, 1.0), (5.0, 2.0)))
        with pytest.raises(ValueError):
            TrafficTrace("empty", ())

    def test_builtin_traces_shape(self):
        for name, builder in BUILTIN_TRACES.items():
            t = builder()
            assert t.duration_s > 0
            assert max(r for _, r in t.points) > min(r for _, r in t.points)
        assert diurnal().rps_at(0) < diurnal().rps_at(
            diurnal().duration_s / 2)
        assert flash_crowd().rps_at(0) < max(
            r for _, r in flash_crowd().points)
        assert len(step_function().points) > 4

    def test_save_load_roundtrip(self, tmp_path):
        path = os.path.join(tmp_path, "t.json")
        t = flash_crowd(base_rps=10, spike_rps=99)
        t.save(path)
        back = load_trace(path)
        assert back.points == t.points
        assert back.slo_p99_ms == t.slo_p99_ms
        assert load_trace("diurnal").name == "diurnal"
        with pytest.raises((ValueError, OSError)):
            load_trace("no_such_trace")

    def test_checked_in_diurnal_trace_loads(self):
        path = os.path.join(REPO, "tools", "traces", "diurnal.json")
        t = load_trace(path)
        assert t.name == "diurnal"
        assert t.duration_s == 3600.0


# ---------------------------------------------------------------------------
# traffic_spike fault kind (satellite)
# ---------------------------------------------------------------------------

class TestTrafficSpike:
    def test_grammar_and_default_point(self):
        (spec,) = parse_plan("traffic_spike@step=20:rps=300:secs=120")
        assert spec.kind == "traffic_spike"
        assert spec.point == "serve.traffic"
        assert spec.step == 20 and spec.rps == 300.0 and spec.secs == 120.0

    def test_unknown_key_error_mentions_rps(self):
        with pytest.raises(ValueError, match="rps"):
            parse_plan("traffic_spike@step=1:bananas=2")

    def test_window_opens_sums_and_expires(self):
        inj = FaultInjector(parse_plan(
            "traffic_spike@step=2:rps=100:secs=50,"
            "traffic_spike@step=4:rps=40:secs=200"))
        assert inj.extra_rps(now=0.0) == 0.0
        inj.fire("serve.traffic", step=2, rank=0, now=10.0)
        assert inj.extra_rps(now=11.0) == 100.0
        inj.fire("serve.traffic", step=4, rank=0, now=20.0)
        assert inj.extra_rps(now=21.0) == 140.0      # overlapping windows
        assert inj.extra_rps(now=70.0) == 40.0       # first expired
        assert inj.extra_rps(now=500.0) == 0.0       # all pruned

    def test_router_accounts_spike_as_synthetic_load(self, kv_server,
                                                     monkeypatch):
        from horovod_tpu.resilience import faults
        from horovod_tpu.serve.router import Router

        monkeypatch.setenv("HVDT_FAULT_PLAN",
                           "traffic_spike@step=0:rps=250:secs=60")
        router = Router(kv_server, port=0, probe=False)
        router._check_traffic_faults()
        assert router.synthetic_rps == 250.0
        assert router.describe()["synthetic_rps"] == 250.0
        monkeypatch.setenv("HVDT_FAULT_PLAN", "")
        router._check_traffic_faults()
        assert router.synthetic_rps == 0.0
        assert faults.get_injector() is None

    def test_spike_drives_the_simulated_fleet(self):
        trace = TrafficTrace("calm", ((0.0, 40.0), (1200.0, 40.0)))
        calm = simulate_trace(trace, pods=5, tick_s=10.0,
                              cfg=FleetConfig(queue_hi=8.0))
        spiked = simulate_trace(
            trace, pods=5, tick_s=10.0,
            fault_plan="traffic_spike@step=20:rps=400:secs=300",
            cfg=FleetConfig(queue_hi=8.0, cooldown_s=30.0))
        assert calm["reclaims"] == 0
        assert spiked["faults"].get("traffic_spike", 0) == 1
        assert spiked["reclaims"] >= 1               # the spike forced it
        assert spiked["max_p99_ms"] > calm["max_p99_ms"]


# ---------------------------------------------------------------------------
# CPU chaos simulation (the no-devices acceptance)
# ---------------------------------------------------------------------------

class TestSimulate:
    def test_prices_a_four_pod_fleet_with_no_devices(self):
        report = simulate_trace(flash_crowd(total_s=1200), pods=4,
                                cfg=FleetConfig(queue_hi=8.0))
        assert report["pods"] == 4
        for key in ("goodput_fraction", "slo_compliance", "reclaims",
                    "backfills", "drains", "dropped_requests",
                    "rollbacks", "decisions"):
            assert key in report
        assert 0.0 < report["goodput_fraction"] <= 1.0
        assert 0.0 <= report["slo_compliance"] <= 1.0
        assert report["reclaims"] >= 1
        assert report["drains"] == report["reclaims"] + report["backfills"]
        assert report["decisions"]    # every move is an audit record
        applied = [d for d in report["decisions"]
                   if d["outcome"] == "applied"]
        assert applied and all(d["chosen"]["predicted_gain"] is not None
                               for d in applied)

    def test_deterministic_for_same_inputs(self):
        kw = dict(pods=5, fault_plan="pod_crash@step=30:pod=pod4",
                  cfg=FleetConfig(queue_hi=8.0))
        a = simulate_trace(step_function(), **kw)
        b = simulate_trace(step_function(), **kw)
        assert a == b

    def test_needs_two_pods(self):
        with pytest.raises(ValueError):
            simulate_trace(diurnal(), pods=1)

    def test_observe_mode_never_moves_a_pod(self):
        cfg = FleetConfig(mode="observe", queue_hi=8.0)
        report = simulate_trace(flash_crowd(total_s=900), pods=5, cfg=cfg)
        assert report["reclaims"] == 0 and report["backfills"] == 0
        assert any(d["outcome"] == "observed" for d in report["decisions"])

    def test_cli_prints_summary_json(self, capsys):
        from horovod_tpu.fleet.simulate import main

        rc = main(["step_function", "--pods", "4", "--tick-s", "20"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip())
        assert doc["trace"] == "step_function"
        assert "goodput_fraction" in doc and "decisions" not in doc


# ---------------------------------------------------------------------------
# Engagement + CLI/config/metrics/report wiring
# ---------------------------------------------------------------------------

class TestWiring:
    def test_get_scheduler_gated_on_env(self, monkeypatch):
        sched = _scheduler(_inventory(3))
        install(sched)
        try:
            monkeypatch.delenv("HVDT_FLEET", raising=False)
            assert get_scheduler() is None           # env off: invisible
            monkeypatch.setenv("HVDT_FLEET", "0")
            assert get_scheduler() is None
            monkeypatch.setenv("HVDT_FLEET", "on")
            assert get_scheduler() is sched
        finally:
            reset()
        monkeypatch.setenv("HVDT_FLEET", "on")
        assert get_scheduler() is None               # reset dropped it

    def test_fleet_knobs_registered(self):
        from horovod_tpu.common import config

        for name in ("HVDT_FLEET", "HVDT_FLEET_COOLDOWN_S",
                     "HVDT_FLEET_ENTER_RATIO", "HVDT_FLEET_EXIT_RATIO",
                     "HVDT_FLEET_BACKFILL_RATIO",
                     "HVDT_FLEET_RECOVERY_WINDOW", "HVDT_FLEET_MIN_GAIN",
                     "HVDT_FLEET_MAX_MOVES", "HVDT_FLEET_MIN_TRAIN_PODS"):
            assert name in config.KNOBS

    def test_config_from_env_reads_knobs(self, monkeypatch):
        monkeypatch.setenv("HVDT_FLEET", "observe")
        monkeypatch.setenv("HVDT_FLEET_ENTER_RATIO", "1.5")
        cfg = FleetConfig.from_env()
        assert cfg.mode == "observe"
        assert cfg.enter_ratio == 1.5

    def test_cli_flags_forward_as_env(self):
        import argparse

        from horovod_tpu.runner.config_parser import (add_knob_arguments,
                                                      env_from_args)

        p = argparse.ArgumentParser()
        add_knob_arguments(p)
        args = p.parse_args(["--fleet", "on", "--fleet-enter-ratio", "1.3",
                             "--fleet-min-train-pods", "2"])
        env = env_from_args(args, {})
        assert env["HVDT_FLEET"] == "on"
        assert env["HVDT_FLEET_ENTER_RATIO"] == "1.3"
        assert env["HVDT_FLEET_MIN_TRAIN_PODS"] == "2"

    def test_yaml_fleet_section_forwards_as_env(self, tmp_path):
        from horovod_tpu.runner.config_parser import (apply_config_file,
                                                      env_from_args)
        from horovod_tpu.runner.launch import parse_args

        cfg = os.path.join(tmp_path, "c.yaml")
        with open(cfg, "w") as f:
            f.write("fleet:\n  enabled: on\n  enter_ratio: 1.4\n"
                    "  min_train_pods: 2\n")
        args = parse_args(["--config-file", cfg, "--", "python", "t.py"])
        file_values = apply_config_file(args, cfg)
        env = env_from_args(args, file_values, base_env={})
        assert env["HVDT_FLEET"]
        assert float(env["HVDT_FLEET_ENTER_RATIO"]) == 1.4
        assert env["HVDT_FLEET_MIN_TRAIN_PODS"] == "2"

    def test_fleet_metrics_in_catalog(self):
        from horovod_tpu.telemetry.metrics import CATALOG

        names = set(CATALOG)
        for n in ("hvdt_fleet_decisions_total",
                  "hvdt_fleet_suppressed_total",
                  "hvdt_fleet_rollbacks_total", "hvdt_fleet_pending",
                  "hvdt_fleet_pressure", "hvdt_fleet_train_pods",
                  "hvdt_fleet_serve_units"):
            assert n in names

    def test_top_renders_fleet_panel(self):
        from horovod_tpu.telemetry.top import fleet_lines, render_frame

        events = [
            {"kind": "fleet_decision", "step": 12,
             "trigger": {"kind": "serve_pressure", "ratio": 1.8},
             "chosen": {"move": {"kind": "reclaim", "pod": "pod3"},
                        "predicted_gain": 0.42},
             "outcome": "applied"},
            {"kind": "fleet_outcome", "step": 15,
             "move": {"kind": "reclaim", "pod": "pod3"},
             "outcome": "recovered",
             "pressure_before": 1.8, "pressure_after": 0.9},
        ]
        lines = fleet_lines(events)
        assert len(lines) == 2
        assert "reclaim(pod3)" in lines[0] and "applied" in lines[0]
        assert "recovered" in lines[1] and "1.80->0.90" in lines[1]
        frame = render_frame({}, events=events)
        assert "fleet:" in frame
        assert "anomalies:" not in frame     # fleet records aren't noise

    def test_report_renders_fleet_section(self, tmp_path):
        from horovod_tpu.analysis.report import render_report
        from horovod_tpu.telemetry.anomaly import EventLog

        path = os.path.join(tmp_path, "events.jsonl")
        inv = _inventory(5)
        sched = _scheduler(inv, event_log=EventLog(path))
        _bind_counters(sched)
        sched.tick(queue_per_replica=16.0, step=3)
        md = render_report(path)
        assert "## Fleet scheduler" in md
        assert "reclaim(" in md
        assert "applied" in md

    def test_hvdtrun_dispatches_fleet_subcommand(self, capsys):
        from horovod_tpu.runner.launch import main

        rc = main(["fleet", "step_function", "--pods", "4",
                   "--tick-s", "20"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip())
        assert doc["trace"] == "step_function"


# ---------------------------------------------------------------------------
# Day in the life: the multiprocess acceptance scenario
# ---------------------------------------------------------------------------

# Marked slow: replica workers are real subprocesses (jax import each) —
# this runs in the test-smoke compose service (ci/gen-matrix.sh --smoke),
# which does not filter the slow marker.
@pytest.mark.slow
@pytest.mark.integration
def test_fleet_day_in_the_life(tmp_path, kv_server, spawn):
    """One fleet, two workloads, one simulated day: a real ServeDriver
    spawns replica *subprocesses* against the shared RendezvousServer, a
    real Router carries client load, and the fleet scheduler moves pods
    between a (ledger-simulated) training world and the serving fleet
    through the seq-guarded target doc.

    * the traffic ramp reclaims training 4 -> 2 pods while serving grows
      1 -> 3 replicas with ZERO dropped client requests and p99 held;
    * the trough backfills a pod home with goodput above the floor;
    * a pod_crash landing mid-reclaim is one removal event, one lease
      release, and a sub-30s retry on a different pod;
    * every decision is an auditable record that renders in
      ``analysis --report`` and ``hvdtrun top``.
    """
    from horovod_tpu.analysis.report import render_report
    from horovod_tpu.telemetry.anomaly import EventLog, read_event_log
    from horovod_tpu.telemetry.top import fleet_lines
    from horovod_tpu.serve.router import Router

    ckpt_dir = os.path.join(tmp_path, "ckpts")
    os.makedirs(ckpt_dir, exist_ok=True)
    slo_ms = 2000.0

    def spawn_replica(slot, rid):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "HVDT_RENDEZVOUS_ADDR": "127.0.0.1",
            "HVDT_RENDEZVOUS_PORT": str(kv_server.port),
            "HVDT_SECRET": kv_server.secret.hex(),
            "HVDT_SERVE_REPLICA_ID": str(rid),
            "HVDT_RANK": str(rid),
        })
        proc = spawn(
            [sys.executable, "-m", "horovod_tpu.serve",
             "--checkpoint", ckpt_dir, "--model", "mlp",
             "--mlp-sizes", "6,16,3", "--buckets", "1,4",
             "--replica-worker"],
            env=env, cwd=REPO)
        return proc.wait()

    driver = ServeDriver(kv_server, spawn_replica, replicas=1,
                         max_replicas=3, interval=0.3)
    router = Router(kv_server, port=0, heartbeat_s=0.5, probe=False,
                    slo_p99_ms=slo_ms)

    # The fleet: pod0 serves, pod1..pod4 train.  Training is a chip-time
    # ledger here (the real elastic driver is exercised elsewhere); the
    # serving side is entirely real — subprocess replicas, real router.
    inv = _inventory(5, serve_units=1)
    log_path = os.path.join(tmp_path, "fleet.jsonl")
    sched = _scheduler(inv, event_log=EventLog(log_path),
                       cooldown_s=0.1, recovery_window=2,
                       min_train_pods=1)

    ledger = {"alloc": 0.0, "charged": 0.0, "restart_s": 2.0}
    crash = {"arm": False, "victim": None, "at": None, "recovered_at": None}

    def world_changed():
        ledger["charged"] += ledger["restart_s"] * max(
            1, len(inv.leased("train")))

    def reclaim(move):
        if crash["arm"]:
            # The victim pod dies DURING the drain: one correlated
            # removal event through the shared inventory; the move
            # itself fails and the scheduler retries elsewhere.
            crash.update(arm=False, victim=move.pod, at=time.monotonic())
            assert inv.record_failure(move.pod)
            world_changed()
            return False
        doc = write_target(kv_server, len(inv.leased("serve")) + 1,
                           writer="fleet-scheduler", reason=move.reason)
        world_changed()
        if crash["victim"] and crash["recovered_at"] is None:
            crash["recovered_at"] = time.monotonic()
        return doc is not None

    def backfill(move):
        doc = write_target(kv_server, len(inv.leased("serve")) - 1,
                           writer="fleet-scheduler", reason=move.reason)
        world_changed()
        return doc is not None

    sched.bind("reclaim", reclaim)
    sched.bind("backfill", backfill)

    results = {}
    latencies = []
    res_lock = threading.Lock()
    stop_load = threading.Event()

    def client(cid):
        i = 0
        while not stop_load.is_set():
            rid = f"{cid}-{i}"
            i += 1
            t0 = time.perf_counter()
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", rport, timeout=30)
                conn.request("POST", "/predict",
                             json.dumps({"inputs": [[0.5] * 6]}),
                             {"Content-Type": "application/json"})
                status = conn.getresponse().status
                conn.close()
            except OSError as e:
                status = f"exc:{e!r}"
            with res_lock:
                results.setdefault(rid, []).append(status)
                latencies.append((time.perf_counter() - t0) * 1000.0)
            time.sleep(0.05)

    def wait_for(cond, why, timeout=120.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.2)
        pytest.fail(why)

    def pump(queue_per_replica, ticks=1, step=[0]):
        """Advance the scheduler with a synthetic pressure signal and
        keep the chip-time ledger honest."""
        out = []
        for _ in range(ticks):
            ledger["alloc"] += 10.0 * max(1, len(inv.leased("train")))
            goodput = max(0.0, 1.0 - ledger["charged"]
                          / max(ledger["alloc"], 1e-9))
            out.extend(sched.tick(queue_per_replica=queue_per_replica,
                                  goodput_fraction=goodput,
                                  step=step[0]))
            step[0] += 1
            time.sleep(0.15)
        return out

    try:
        driver.start()
        rport = router.start()
        wait_for(lambda: len(router._routable()) >= 1,
                 "first replica never became routable", 180)
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(3)]
        for t in threads:
            t.start()

        # -- the ramp: training 4 -> 2, serving 1 -> 3 -----------------
        assert len(inv.leased("train")) == 4
        pump(queue_per_replica=16.0)                 # reclaim #1
        wait_for(lambda: len(router._routable()) >= 2,
                 "serving never grew to 2 replicas", 180)
        pump(queue_per_replica=16.0, ticks=2)        # window: not worse
        pump(queue_per_replica=16.0)                 # reclaim #2
        wait_for(lambda: len(router._routable()) >= 3,
                 "serving never grew to 3 replicas", 180)
        assert len(inv.leased("train")) == 2         # the 4 -> 2 drain
        assert len(inv.leased("serve")) == 3
        assert driver.last_target_writer == "fleet-scheduler"
        pump(queue_per_replica=5.0)                  # recovered: re-arm

        # -- the trough: a pod comes home, goodput holds ----------------
        pump(queue_per_replica=0.5)                  # backfill
        wait_for(lambda: len(driver.live_replicas()) == 2,
                 "trough never drained a replica", 120)
        assert len(inv.leased("train")) == 3
        pump(queue_per_replica=5.0, ticks=3)         # backfill survives
        assert sched.rollbacks == 0
        goodput = 1.0 - ledger["charged"] / ledger["alloc"]
        assert goodput > 0.5, f"goodput {goodput:.2f} under the floor"

        # -- pod_crash mid-reclaim: one event, sub-30s retry ------------
        crash["arm"] = True
        pump(queue_per_replica=16.0)                 # fails mid-drain
        pump(queue_per_replica=16.0)                 # retries elsewhere
        wait_for(lambda: crash["recovered_at"] is not None,
                 "reclaim never retried after the crash", 60)
        wait_for(lambda: len(router._routable()) >= 3,
                 "serving never recovered to 3 after the crash", 180)
        assert inv.tracker.removal_events == 1
        assert crash["recovered_at"] - crash["at"] < 30.0
        assert inv.lease_of(crash["victim"]) is None
        reclaimed = [p for p in inv.leased("serve") if p != "pod0"]
        assert crash["victim"] not in reclaimed

        # -- zero dropped requests, p99 held ----------------------------
        stop_load.set()
        for t in threads:
            t.join(timeout=60)
        with res_lock:
            assert len(results) >= 50
            bad = {k: v for k, v in results.items() if v != [200]}
            assert not bad, f"dropped/failed/duplicated: {bad}"
            lats = sorted(latencies)
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))]
        assert p99 < slo_ms, f"p99 {p99:.0f}ms breached SLO {slo_ms}ms"

        # -- serving exits stayed clean through every move --------------
        assert driver.removal_events == 0

        # -- every decision is an audit record that renders -------------
        events = read_event_log(log_path)
        applied = [e for e in events if e.get("kind") == "fleet_decision"
                   and e.get("outcome") == "applied"]
        assert len(applied) >= 4     # 3 reclaims + 1 backfill
        assert any(e.get("outcome") == "suppressed:apply_failed"
                   for e in events)
        assert any(e.get("kind") == "fleet_outcome"
                   and e.get("outcome") == "recovered" for e in events)
        md = render_report(log_path)
        assert "## Fleet scheduler" in md and "reclaim(" in md
        assert fleet_lines(events)
    finally:
        stop_load.set()
        router.stop()
        driver.stop(drain=True, timeout=60)
