"""Pod-aware elastic control plane: two-level rendezvous, pod-granular
resize, whole-pod failure recovery.

Unit tier: pod parsing/grouping/assignment, the (dcn, ici) mesh
contract, the extended fault-plan grammar (rank sets/ranges, pod
faults), KV-client counters, driver pod semantics (exit correlation,
preemption drain, straggler eviction), plus the previously untested
``wait_for_available_slots`` timeout and rendezvous-server port-rebind
paths.

Integration tier: ``pod_crash`` kills every rank of one pod mid-run
over a real RendezvousServer; the driver collapses the exits into ONE
pod-removal (one blacklist entry, one re-rendezvous), survivors resize
to a pod-multiple world with checkpoint + ``reshard_state`` continuity,
and the evicted pod rejoins after cooldown for a pod-granular scale-up.
"""

import json
import os
import stat
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu.runner.hosts import HostInfo, SlotInfo
from horovod_tpu.runner.elastic import pods
from horovod_tpu.runner.elastic.discovery import HostManager
from horovod_tpu.runner.elastic.driver import ElasticDriver
from horovod_tpu.runner.http_kv import KVClient, RendezvousServer
from horovod_tpu.resilience import faults
from horovod_tpu.resilience.faults import (FaultInjector, parse_plan,
                                           parse_rank_set)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Discovery grammar + pod grouping
# ---------------------------------------------------------------------------

class TestPodParsing:
    def test_host_string_with_pod(self):
        h = HostInfo.from_string("tpu-0:4@slice-a")
        assert (h.hostname, h.slots, h.pod) == ("tpu-0", 4, "slice-a")

    def test_pod_without_slots(self):
        h = HostInfo.from_string("tpu-1@slice-b")
        assert (h.hostname, h.slots, h.pod) == ("tpu-1", 1, "slice-b")

    def test_no_pod_stays_none(self):
        assert HostInfo.from_string("tpu-2:2").pod is None

    def test_bad_string_raises(self):
        with pytest.raises(ValueError):
            HostInfo.from_string("host:x@p")

    def test_discovery_script_pod_column(self, tmp_path):
        script = os.path.join(tmp_path, "d.sh")
        with open(script, "w") as f:
            f.write("#!/bin/sh\necho a:2@podA\necho b@podB\n")
        os.chmod(script, 0o755)
        hm = HostManager.from_script(script, default_slots=2)
        hm.update_available_hosts()
        hosts = hm.current.hosts
        # default_slots fill must preserve the declared pod.
        assert hosts == [HostInfo("a", 2, "podA"), HostInfo("b", 2, "podB")]
        assert hm.pod_of("a") == "podA" and hm.pod_of("b") == "podB"
        assert hm.pod_of("unknown") == "unknown"

    def test_group_declared_pods(self):
        ps = pods.group_pods([HostInfo("a", 2, "A"), HostInfo("b", 2, "A"),
                              HostInfo("c", 2, "B")])
        assert [(p.name, p.slots) for p in ps] == [("A", 4), ("B", 2)]

    def test_group_chunked_by_pod_slots(self):
        hosts = [HostInfo(f"h{i}", 2) for i in range(5)]
        ps = pods.group_pods(hosts, pod_slots=4)
        assert [(p.name, p.slots) for p in ps] == [
            ("pod0", 4), ("pod1", 4), ("pod2", 2)]

    def test_group_default_per_host(self):
        ps = pods.group_pods([HostInfo("a", 2), HostInfo("b", 3)])
        assert [(p.name, p.slots) for p in ps] == [("a", 2), ("b", 3)]


class TestPlanAssignments:
    HOSTS = [HostInfo("a", 2, "A"), HostInfo("b", 2, "A"),
             HostInfo("c", 2, "B"), HostInfo("d", 2, "B")]

    def test_contiguous_ranks_within_pods(self):
        slots = pods.plan_assignments(self.HOSTS, 4, 8)
        assert len(slots) == 8
        assert [s.pod for s in slots] == ["A"] * 4 + ["B"] * 4
        assert [s.pod_rank for s in slots] == [0, 1, 2, 3] * 2
        assert all(s.num_pods == 2 and s.pod_size == 4 for s in slots)
        env = slots[5].to_env()
        assert env["HVDT_POD"] == "B"
        assert env["HVDT_POD_INDEX"] == "1"
        assert env["HVDT_POD_RANK"] == "1"
        assert env["HVDT_NUM_PODS"] == "2"
        assert env["HVDT_POD_SIZE"] == "4"

    def test_world_is_pod_multiple(self):
        # max_np 6 with pod size 4: only one whole pod fits.
        slots = pods.plan_assignments(self.HOSTS, 2, 6)
        assert len(slots) == 4
        assert {s.pod for s in slots} == {"A"}

    def test_incomplete_pod_skipped(self):
        # Pod B has only half its hosts discovered: not placeable.
        hosts = self.HOSTS[:3]
        slots = pods.plan_assignments(hosts, 4, 8)
        assert {s.pod for s in slots} == {"A"}
        assert pods.usable_slots(hosts) == 4

    def test_excluded_pod_not_assigned(self):
        slots = pods.plan_assignments(self.HOSTS, 4, 8, exclude={"B"})
        assert {s.pod for s in slots} == {"A"}
        assert pods.usable_slots(self.HOSTS, exclude={"B"}) == 4

    def test_insufficient_whole_pods_raise(self):
        with pytest.raises(ValueError):
            pods.plan_assignments(self.HOSTS[:3], 6, 8)

    def test_flat_fallback_annotates_per_host(self):
        slots = pods.plan_assignments(
            [HostInfo("a", 2), HostInfo("b", 1)], 3, 3)
        assert [s.pod for s in slots] == ["a", "a", "b"]
        assert [s.pod_rank for s in slots] == [0, 1, 0]

    def test_pod_layout_doc(self):
        layout = pods.pod_layout(pods.plan_assignments(self.HOSTS, 4, 8))
        assert layout["mesh"] == {"dcn": 2, "ici": 4}
        assert [p["name"] for p in layout["pods"]] == ["A", "B"]
        assert layout["pods"][1]["ranks"] == [4, 5, 6, 7]


class TestPodMesh:
    def test_pod_mesh_spec_explicit(self):
        from horovod_tpu.parallel import mesh

        spec = mesh.pod_mesh_spec(2, 4)
        assert spec.shape == {"dcn": 2, "ici": 4}
        slow, fast = mesh.split_transport_axes(spec.names)
        assert slow == ("dcn",) and fast == ("ici",)
        assert mesh.axis_transport_class("ici", spec.names) == \
            mesh.TRANSPORT_ICI
        assert mesh.axis_transport_class("dcn", spec.names) == \
            mesh.TRANSPORT_DCN

    def test_pod_mesh_spec_from_env(self, monkeypatch):
        from horovod_tpu.parallel import mesh

        monkeypatch.setenv("HVDT_NUM_PODS", "3")
        monkeypatch.setenv("HVDT_POD_SIZE", "2")
        assert mesh.pod_mesh_spec().shape == {"dcn": 3, "ici": 2}
        monkeypatch.delenv("HVDT_POD_SIZE")
        monkeypatch.setenv("HVDT_SIZE", "6")
        assert mesh.pod_mesh_spec().shape == {"dcn": 3, "ici": 2}

    def test_invalid_extents_raise(self):
        from horovod_tpu.parallel import mesh

        with pytest.raises(ValueError):
            mesh.pod_mesh_spec(0, 4)


# ---------------------------------------------------------------------------
# Fault-plan grammar: rank sets/ranges + pod faults
# ---------------------------------------------------------------------------

class TestFaultGrammar:
    def test_rank_set_forms(self):
        assert parse_rank_set(3) == frozenset({3})
        assert parse_rank_set("1,3") == frozenset({1, 3})
        assert parse_rank_set("0-3") == frozenset({0, 1, 2, 3})
        assert parse_rank_set("1,4-6") == frozenset({1, 4, 5, 6})
        with pytest.raises(ValueError):
            parse_rank_set("x")
        with pytest.raises(ValueError):
            parse_rank_set("3-1")

    def test_plan_with_rank_set_and_following_entry(self):
        specs = parse_plan("crash@step=12:rank=1,3-5,hang@step=30:secs=2")
        assert len(specs) == 2
        assert specs[0].kind == "crash"
        assert specs[0].ranks == frozenset({1, 3, 4, 5})
        assert specs[1].kind == "hang" and specs[1].secs == 2.0

    def test_single_rank_backwards_compatible(self):
        (spec,) = parse_plan("crash@step=5:rank=1")
        assert spec.ranks == frozenset({1})

    def test_pod_fault_kinds_parse(self):
        specs = parse_plan("pod_crash@step=10:pod=podB,"
                           "pod_partition@step=20:pod=podA:secs=7")
        assert specs[0].kind == "pod_crash" and specs[0].pod == "podB"
        assert specs[0].point == "step"
        assert specs[1].kind == "pod_partition" and specs[1].secs == 7.0

    def test_unknown_key_raises_with_vocabulary(self):
        with pytest.raises(ValueError, match="valid: step, rank, pod"):
            parse_plan("crash@step=5:banana=1")

    def test_unknown_kind_lists_pod_kinds(self):
        with pytest.raises(ValueError, match="pod_crash"):
            parse_plan("meteor@step=5")

    def test_rank_set_fires_for_each_member(self):
        exits = []
        inj = FaultInjector(parse_plan("crash@step=5:rank=0-1:times=2"),
                            exit_fn=lambda code: exits.append(code))
        inj.fire("step", step=6, rank=0)
        inj.fire("step", step=6, rank=2)   # not in the set
        inj.fire("step", step=6, rank=1)
        assert exits == [1, 1]

    def test_pod_crash_matches_env_pod(self, monkeypatch):
        monkeypatch.setenv("HVDT_POD", "podB")
        monkeypatch.setenv("HVDT_RANK", "2")
        exits = []
        inj = FaultInjector(parse_plan("pod_crash@step=10:pod=podB"),
                            exit_fn=lambda code: exits.append(code))
        inj.fire("step", step=9)      # before the step
        assert exits == []
        inj.fire("step", step=10)
        assert exits == [1]

    def test_pod_crash_spares_other_pods(self, monkeypatch):
        monkeypatch.setenv("HVDT_POD", "podA")
        exits = []
        inj = FaultInjector(parse_plan("pod_crash@step=10:pod=podB"),
                            exit_fn=lambda code: exits.append(code))
        inj.fire("step", step=99)
        assert exits == []

    def test_pod_partition_blocks(self, monkeypatch):
        monkeypatch.setenv("HVDT_POD", "podA")
        naps = []
        inj = FaultInjector(
            parse_plan("pod_partition@step=3:pod=podA:secs=11"),
            sleep_fn=naps.append)
        inj.fire("step", step=4)
        assert naps == [11.0]
        assert inj.counters["pod_partition"] == 1

    def test_no_pod_env_means_no_pod_match(self, monkeypatch):
        monkeypatch.delenv("HVDT_POD", raising=False)
        exits = []
        inj = FaultInjector(parse_plan("pod_crash@step=1:pod=podB"),
                            exit_fn=lambda code: exits.append(code))
        inj.fire("step", step=5)
        assert exits == []


# ---------------------------------------------------------------------------
# KV client counters (zero-overhead off, counted on)
# ---------------------------------------------------------------------------

class TestKVCounters:
    def test_zero_overhead_when_telemetry_off(self, monkeypatch):
        from horovod_tpu.runner import http_kv

        monkeypatch.delenv("HVDT_TELEMETRY", raising=False)
        assert http_kv._kv_metrics() is None

    def test_errors_and_retries_counted(self, monkeypatch):
        from horovod_tpu.runner import http_kv
        from horovod_tpu.telemetry.metrics import default_registry

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        server = RendezvousServer()
        port = server.start()
        client = KVClient("127.0.0.1", port, server.secret, timeout=2.0)
        client.put("/k", b"v")
        assert client.get("/k") == b"v"
        retries, errors = http_kv._kv_metrics()
        e0 = errors.value(op="get")
        r0 = retries.value()
        assert server.stop()
        with pytest.raises((ConnectionError, OSError)):
            client.get("/k")
        assert errors.value(op="get") == e0 + 1
        with pytest.raises(TimeoutError):
            client.wait("/never", timeout=0.3, poll=0.05)
        assert retries.value() > r0
        reg = default_registry()
        assert reg.get("hvdt_kv_errors_total") is errors

    def test_snapshot_surfaces_counters_and_pod(self, monkeypatch):
        from horovod_tpu.runner import http_kv
        from horovod_tpu.telemetry.exporter import snapshot_dict

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        monkeypatch.setenv("HVDT_POD", "slice-7")
        assert http_kv._kv_metrics() is not None   # ensure registered
        snap = snapshot_dict()
        assert "kv_retries_total" in snap
        assert "kv_errors_total" in snap
        assert snap["pod"] == "slice-7"


# ---------------------------------------------------------------------------
# PodTracker
# ---------------------------------------------------------------------------

class TestPodTracker:
    def test_failure_correlation_window(self):
        t = pods.PodTracker(exit_window_s=5.0)
        assert t.record_failure("B", now=100.0) is True
        assert t.record_failure("B", now=101.0) is False   # folded
        assert t.record_failure("B", now=104.9) is False
        assert t.record_failure("B", now=106.0) is True    # new event
        assert t.record_failure("A", now=106.0) is True    # other pod
        assert t.removal_events == 3

    def test_drain_expiry(self):
        t = pods.PodTracker(drain_grace_s=10.0)
        assert t.drain("B", now=0.0) is True
        assert t.drain("B", now=1.0) is False
        assert t.drained_pods(now=5.0) == {"B"}
        assert t.drained_pods(now=11.0) == set()

    def test_straggler_windows_and_eviction(self):
        t = pods.PodTracker(evict_windows=3, threshold=2.0)
        slow = {"A": 100.0, "B": 100.0, "C": 300.0}
        assert t.observe_step_medians(slow) == []
        assert t.observe_step_medians(slow) == []
        assert t.observe_step_medians(slow) == ["C"]
        # Evicted once per streak, not every later window.
        assert t.observe_step_medians(slow) == []

    def test_straggler_streak_resets_when_healthy(self):
        t = pods.PodTracker(evict_windows=2, threshold=2.0)
        slow = {"A": 100.0, "B": 300.0}
        ok = {"A": 100.0, "B": 110.0}
        assert t.observe_step_medians(slow) == []
        assert t.observe_step_medians(ok) == []
        assert t.observe_step_medians(slow) == []   # streak restarted
        assert t.observe_step_medians(slow) == ["B"]

    def test_disabled_rung_never_evicts(self):
        t = pods.PodTracker(evict_windows=0, threshold=2.0)
        assert t.observe_step_medians({"A": 1.0, "B": 99.0}) == []

    def test_fingerprint_gates_on_new_data(self):
        t = pods.PodTracker()
        snaps = {0: {"steps": 5}, 1: {"steps": 5}}
        assert t.snapshots_fingerprint(snaps) is True
        assert t.snapshots_fingerprint(snaps) is False
        assert t.snapshots_fingerprint({0: {"steps": 6},
                                        1: {"steps": 6}}) is True


# ---------------------------------------------------------------------------
# Worker-side straggler monitor: pod dimension
# ---------------------------------------------------------------------------

class TestStragglerPodDimension:
    def _monitor(self, means, pod_size, **kw):
        from horovod_tpu.telemetry.metrics import MetricsRegistry
        from horovod_tpu.telemetry.straggler import StragglerMonitor

        return StragglerMonitor(window=1, threshold=2.0,
                                registry=MetricsRegistry(),
                                allgather_fn=lambda m: means,
                                pod_size=pod_size, **kw)

    def test_pod_gauges_flag_slow_pod(self):
        flagged = []
        mon = self._monitor([0.1, 0.1, 0.5, 0.5], 2,
                            on_pod_straggler=lambda p, r: flagged.append(p))
        mon.check(0.1)
        assert mon.straggler_pod_gauge.value() == 1
        assert mon.pod_skew_gauge.value() == pytest.approx(5.0)
        assert flagged == [1]

    def test_no_pod_flag_below_threshold(self):
        mon = self._monitor([0.1, 0.1, 0.15, 0.15], 2)
        mon.check(0.1)
        assert mon.straggler_pod_gauge.value() == -1
        assert mon.pod_skew_gauge.value() == pytest.approx(1.5)

    def test_single_pod_world_skips_pod_check(self):
        mon = self._monitor([0.1, 0.5], 2)
        mon.check(0.1)
        assert mon.straggler_pod_gauge.value() == -1
        assert mon.pod_skew_gauge.value() == 1.0


# ---------------------------------------------------------------------------
# Driver pod semantics (fake clusters)
# ---------------------------------------------------------------------------

class _PodCluster:
    def __init__(self, hosts):
        # hosts: [(hostname, slots, pod)]
        self.hosts = {h: (s, p) for h, s, p in hosts}
        self.exited = {}
        self.stopped = threading.Event()

    def discover(self):
        return [HostInfo(h, s, p)
                for h, (s, p) in sorted(self.hosts.items())]

    def spawn(self, slot, gen):
        """A worker that runs until the test scripts its exit, or ends
        (``stop``), so no worker thread outlives its test."""
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not self.stopped.is_set():
            if (slot.rank, gen) in self.exited:
                return self.exited[(slot.rank, gen)]
            time.sleep(0.02)
        return 0

    def stop(self):
        self.stopped.set()


def _wait_for_generation(driver, gen, timeout=5.0):
    deadline = time.monotonic() + timeout
    while driver.generation < gen and time.monotonic() < deadline:
        time.sleep(0.05)
    assert driver.generation == gen


class TestDriverPodSemantics:
    def _driver(self, cluster, tracker=None, **kw):
        hm = HostManager(cluster.discover)
        driver = ElasticDriver(hm, min_np=2, max_np=8,
                               spawn_fn=cluster.spawn,
                               discovery_interval=0.05,
                               pod_tracker=tracker, **kw)
        return hm, driver

    def test_correlated_pod_exits_collapse_to_one_event(self):
        cluster = _PodCluster([("a", 2, "A"), ("b", 2, "A"),
                               ("c", 2, "B"), ("d", 2, "B")])
        hm, driver = self._driver(cluster)
        driver.start()
        try:
            assert len(driver.assignments) == 8
            pod_b = [s for s in driver.assignments if s.pod == "B"]
            # Every rank of pod B dies (the correlated slice loss)...
            for s in pod_b:
                cluster.exited[(s.rank, 1)] = 1
            time.sleep(0.4)
            # ...and the survivors request re-rendezvous.
            for s in driver.assignments:
                if s.pod == "A":
                    driver.record_ready(s.rank)
            _wait_for_generation(driver, 2)
            # ONE blacklist entry for the whole pod, one removal event.
            assert hm.pod_failures("B") == 1
            assert driver._pods.removal_events == 1
            assert hm.is_pod_blacklisted("B")
            assert not hm.is_pod_blacklisted("A")
            # Pod-granular resize: the new world is pod A only.
            assert {s.pod for s in driver.assignments} == {"A"}
            assert len(driver.assignments) == 4
        finally:
            driver.stop()
            cluster.stop()

    def test_preempt_exit_drains_whole_pod(self):
        cluster = _PodCluster([("a", 2, "A"), ("b", 2, "A"),
                               ("c", 2, "B"), ("d", 2, "B")])
        tracker = pods.PodTracker(drain_grace_s=30.0)
        hm, driver = self._driver(cluster, tracker=tracker)
        driver.start()
        try:
            assert len(driver.assignments) == 8
            # One rank of pod B takes the clean preemption exit (83);
            # the rest of its ranks and the survivors go READY.
            for s in driver.assignments:
                cluster.exited[(s.rank, 1)] = 83 if s.pod == "B" else 79
            _wait_for_generation(driver, 2)
            # No blacklist (clean removal), but the pod is drained out
            # of the new assignment even though discovery still lists it.
            assert hm.pod_failures("B") == 0
            assert tracker.drained_pods() == {"B"}
            assert {s.pod for s in driver.assignments} == {"A"}
        finally:
            driver.stop()
            cluster.stop()

    def test_straggler_eviction_resizes_down(self):
        cluster = _PodCluster([("a", 2, "A"), ("b", 2, "A"),
                               ("c", 2, "B"), ("d", 2, "B")])
        server = RendezvousServer()
        server.start()
        tracker = pods.PodTracker(evict_windows=2, threshold=2.0)
        hm = HostManager(cluster.discover)
        driver = ElasticDriver(hm, min_np=2, max_np=8,
                               spawn_fn=cluster.spawn,
                               discovery_interval=0.05,
                               kv_server=server, pod_tracker=tracker)
        driver.start()
        try:
            assert len(driver.assignments) == 8

            def publish(window):
                for s in driver.assignments:
                    ms = 400.0 if s.pod == "B" else 100.0
                    server.put_local(f"/telemetry/{s.rank}", json.dumps(
                        {"steps": 10 * (window + 1),
                         "step_time_p50_ms": ms,
                         "pod": s.pod}).encode())

            # Pod B is slow.  One window must NOT evict...
            publish(0)
            time.sleep(0.3)
            assert not hm.is_pod_blacklisted("B")
            # ...the second consecutive slow window does.
            publish(1)
            deadline = time.monotonic() + 3
            while not hm.is_pod_blacklisted("B") and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert hm.is_pod_blacklisted("B")
            # Workers notice the membership change and go READY.
            for s in driver.assignments:
                driver.record_ready(s.rank)
            _wait_for_generation(driver, 2)
            assert {s.pod for s in driver.assignments} == {"A"}
        finally:
            driver.stop()
            cluster.stop()
            server.stop()

    def test_wait_for_available_slots_timeout(self):
        """Satellite: the deadline path raises TimeoutError naming the
        shortfall instead of spinning forever."""
        hm = HostManager(lambda: [])
        driver = ElasticDriver(hm, min_np=2, spawn_fn=lambda s, g: 0,
                               discovery_interval=0.05)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="timed out waiting for 2"):
            driver.wait_for_available_slots(2, timeout=0.3)
        assert time.monotonic() - t0 < 5

    def test_wait_for_available_slots_shutdown_raises(self):
        hm = HostManager(lambda: [])
        driver = ElasticDriver(hm, min_np=2, spawn_fn=lambda s, g: 0)
        driver.stop()
        with pytest.raises(RuntimeError, match="shut down"):
            driver.wait_for_available_slots(2, timeout=5.0)

    def test_wait_counts_only_whole_pods(self):
        cluster = _PodCluster([("a", 2, "A"), ("c", 2, "B")])
        hm = HostManager(cluster.discover)
        hm.update_available_hosts()
        driver = ElasticDriver(hm, min_np=2, max_np=8,
                               spawn_fn=cluster.spawn, pod_slots=4)
        # Each pod is half-discovered (2 of 4 slots): nothing placeable.
        with pytest.raises(TimeoutError):
            driver.wait_for_available_slots(2, timeout=0.3)


class TestRendezvousServerRestart:
    def test_stop_closes_socket_and_port_is_rebindable(self):
        """Satellite: the PR-4 determinism fix — stop() must close the
        listen socket so the SAME port can host the next rendezvous
        immediately (the re-rendezvous-after-stop path)."""
        s1 = RendezvousServer()
        port = s1.start()
        s1.put_local("/gen1/key", b"old")
        assert s1.stop() is True
        # Same port, fresh server, fresh store: a client can bootstrap
        # against the new rendezvous right away.
        s2 = RendezvousServer(port=port)
        assert s2.start() == port
        try:
            client = KVClient("127.0.0.1", port, s2.secret, timeout=2.0)
            assert client.get("/gen1/key") is None   # no stale state
            client.put("/gen2/key", b"new")
            assert s2.get_local("/gen2/key") == b"new"
        finally:
            assert s2.stop() is True


# ---------------------------------------------------------------------------
# CLI / config wiring
# ---------------------------------------------------------------------------

class TestCliWiring:
    def test_pod_flags_forward_as_env(self):
        from horovod_tpu.runner.launch import knob_env_for, parse_args

        args = parse_args(["--pod-size", "4", "--pod-straggler-evict", "3",
                           "-np", "8", "--", "python", "train.py"])
        env = knob_env_for(args)
        assert env["HVDT_POD_SIZE"] == "4"
        assert env["HVDT_POD_STRAGGLER_EVICT"] == "3"

    def test_yaml_elastic_section(self, tmp_path):
        from horovod_tpu.runner.config_parser import (apply_config_file,
                                                      env_from_args)
        from horovod_tpu.runner.launch import parse_args

        cfg = os.path.join(tmp_path, "c.yaml")
        with open(cfg, "w") as f:
            f.write("elastic:\n  pod_size: 8\n  pod_straggler_evict: 5\n")
        args = parse_args(["--config-file", cfg, "--", "python", "t.py"])
        file_values = apply_config_file(args, cfg)
        env = env_from_args(args, file_values, base_env={})
        assert env["HVDT_POD_SIZE"] == "8"
        assert env["HVDT_POD_STRAGGLER_EVICT"] == "5"

    def test_pod_knobs_registered(self):
        from horovod_tpu.common import config

        for name in ("HVDT_POD", "HVDT_POD_SIZE", "HVDT_POD_EXIT_WINDOW_S",
                     "HVDT_POD_DRAIN_GRACE_S", "HVDT_POD_STRAGGLER_EVICT"):
            assert name in config.KNOBS


# ---------------------------------------------------------------------------
# Multiprocess acceptance: pod crash -> pod removal -> resize -> resume
# -> cooldown rejoin -> pod-granular scale-up
# ---------------------------------------------------------------------------

def _rows(path):
    out = []
    with open(path) as f:
        for ln in f:
            if ln.strip():
                r, s, pod, b, ts = ln.split()
                out.append((int(r), int(s), pod, int(b), int(ts)))
    return out


# ``slow``: 35 s alone, 47 s beside five busy workers: a launcher and three
# generations of four jax workers, so process start-up nearly all of it,
# and its 70 s windows are the first to go on a loaded machine.  The
# compose test-smoke service runs it (this file is on its list).  Tier-1
# keeps the driver's side in TestDriverPodSemantics (correlated exits fold
# into one pod-removal, the drain, the resize down and up) and the real
# launcher's crash-and-resume in tests/test_resilience.py
# (test_injected_crash_recovers_with_step_continuity) and
# tests/test_goodput.py (test_kill_rank1_recovers_from_peer_ram_within_budget).
@pytest.mark.slow
@pytest.mark.integration
def test_pod_crash_recovery_and_rejoin(tmp_path, spawn):
    """The acceptance scenario: ``pod_crash@step=10:pod=podB`` kills both
    ranks of pod B mid-training over a real RendezvousServer.  The
    driver must collapse the two exits into a single pod-removal (one
    blacklist entry, one extra rendezvous generation), resize the
    survivors to a pod-multiple world (4 -> 2) resuming from the disk
    commit with the ZeRO state resharded across the changed dcn extent,
    and scale back up (2 -> 4) when the evicted pod rejoins after its
    cooldown — with monotone batches and exact loss continuity
    throughout."""
    log_path = os.path.join(tmp_path, "progress.log")
    zero_log = os.path.join(tmp_path, "zero.log")
    control = os.path.join(tmp_path, "podB_up")
    open(control, "w").write("up")   # pod B present from the start
    env = dict(os.environ)
    env.update({
        "ELASTIC_TEST_LOG": log_path,
        "ELASTIC_TEST_STATE": os.path.join(tmp_path, "state.pkl"),
        "ELASTIC_TEST_BATCHES": "80",
        "ELASTIC_TEST_SLEEP": "0.1",
        # Steady-state dead-peer detection: must undercut the JAX
        # coordination service's ~20s dead-task fatal so survivors exit
        # cleanly for respawn (first waits after a boot run at 3x to
        # absorb this single-core box's worker-boot stagger).
        "ELASTIC_TEST_HB_TIMEOUT": "7",
        "MULTIPOD_ZERO_DIR": os.path.join(tmp_path, "zero"),
        "MULTIPOD_ZERO_LOG": zero_log,
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        # The pod chaos knobs under test:
        "HVDT_FAULT_PLAN": "pod_crash@step=10:pod=podB",
        "HVDT_FAULT_JOURNAL": os.path.join(tmp_path, "fault_journal"),
        "HVDT_ELASTIC_BLACKLIST_COOLDOWN_S": "2",
    })
    # Scripted schedule (the elastic_common.py idiom): pod B is listed
    # while the control file exists.  The test pulls it right after the
    # crash (the platform reclaiming the dead slice) and restores it
    # once the shrunk world is observed running, so the rejoin is
    # deterministic rather than a race against worker boot times.
    discover = os.path.join(tmp_path, "discover.sh")
    with open(discover, "w") as f:
        f.write(f"""#!/bin/sh
echo localhost:2@podA
if [ -f {control} ]; then
  echo 127.0.0.1:2@podB
fi
""")
    os.chmod(discover, 0o755)
    proc = spawn(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", "2", "--max-np", "4",
         "--host-discovery-script", discover,
         "--coordinator-port", "29781",
         "--", sys.executable, os.path.join(REPO, "tests", "data",
                                            "multipod_main.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)

    lines = []

    def _reader():
        for raw in proc.stdout:
            lines.append(raw.decode(errors="replace"))

    reader = threading.Thread(target=_reader, daemon=True)
    reader.start()

    def _wait_until(cond, why, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        proc.kill()
        pytest.fail(f"{why}:\n{''.join(lines)[-3000:]}")

    # 1. Pod B dies at its batch-10 commits; the driver opens exactly
    #    one pod-removal event.  Pull pod B from discovery (the platform
    #    reclaims the dead slice).
    _wait_until(lambda: any("pod-removal event for pod podB" in ln
                            for ln in lines),
                "pod crash never collapsed into a pod-removal", 70)
    os.remove(control)
    # 2. The survivors resize to the one remaining pod and make progress
    #    past the crash point...
    _wait_until(lambda: os.path.exists(log_path) and any(
        s == 2 and b >= 20 for _, s, _, b, _ in _rows(log_path)),
                "shrunk pod-multiple world never resumed", 70)
    # 3. ...then pod B comes back (cooldown long expired) and the run
    #    scales back up to both pods.
    open(control, "w").write("up")
    try:
        proc.wait(timeout=70)
    except subprocess.TimeoutExpired:
        proc.kill()
        pytest.fail(f"multipod chaos run hung:\n{''.join(lines)[-3000:]}")
    reader.join(timeout=10)
    text = "".join(lines)
    assert proc.returncode == 0, text[-3000:]

    rows = _rows(log_path)
    # Pod contract: size-4 worlds place ranks 0-1 on pod A, 2-3 on pod B.
    assert {(r, p) for r, s, p, _, _ in rows if s == 4} == {
        (0, "podA"), (1, "podA"), (2, "podB"), (3, "podB")}
    # The run saw 4 -> 2 -> 4: pod-granular resize down, then back up.
    sizes_in_order = []
    for _, s, _, _, _ in sorted(rows, key=lambda row: row[4]):
        if not sizes_in_order or sizes_in_order[-1] != s:
            sizes_in_order.append(s)
    assert sizes_in_order == [4, 2, 4], sizes_in_order
    # ONE pod-removal event (the two pod-B exits collapsed), and exactly
    # three rendezvous generations: initial, removal, rejoin scale-up.
    assert text.count("pod-removal event for pod podB") == 1
    assert text.count("elastic: rendezvous generation") == 3
    # The shrunk world resumed from the disk commit, not from scratch.
    two_world = [b for _, s, _, b, _ in rows if s == 2]
    assert min(two_world) >= 10, f"resize restarted at {min(two_world)}"
    # The scale-up world finished the job.
    assert max(b for _, s, _, b, _ in rows if s == 4) == 80
    # Monotone batches per rank: no rank ever went backwards past a
    # commit (replay window of at most one commit interval is allowed).
    by_ts = sorted(rows, key=lambda row: row[4])
    seen = {}
    for r, _, _, b, _ in by_ts:
        assert b >= seen.get(r, 0) - 5, f"rank {r} regressed to {b}"
        seen[r] = max(seen.get(r, 0), b)
    # Exact loss continuity: constant LR, every batch applied once.
    assert "final: batches=80 w0=8.0" in text
    # Recovery-time budget: from pod B's death (last size-4 batch-10
    # line) to the shrunk world making NEW progress (first size-2
    # batch-11 line) must stay under the 30 s SLO — whole-pod loss is
    # exactly the case the budget is for.
    t_kill = min(ts for _, s, _, b, ts in rows if b == 10)
    t_recovered = min(ts for _, s, _, b, ts in rows if s == 2 and b == 11)
    recovery_s = (t_recovered - t_kill) / 1000.0
    assert recovery_s < 30.0, (
        f"pod-loss recovery took {recovery_s:.1f}s (budget 30s)")
    # ZeRO resharding across the changed dcn extent, both directions.
    with open(zero_log) as f:
        zl = f.read()
    assert "zero init shards=4" in zl
    assert "zero 4 -> 2 ok" in zl
    assert "zero 2 -> 4 ok" in zl
    assert "BAD" not in zl
