"""Elastic driver: discovery loop, slot reassignment, worker lifecycle.

Re-conception of ref: runner/elastic/driver.py:1-314 (ElasticDriver:
discovery thread :181, host-assignment update + worker notify :203-265,
worker spawn :277, exit handling :297).  Differences for TPU: worker
notification rides the rendezvous KV (workers poll a version key at
commit points) instead of a per-worker RPC service, and re-rendezvous
re-initializes the JAX coordination service rather than re-bootstrapping
Gloo.
"""

from __future__ import annotations

import dataclasses
import os
import shlex
import socket
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import hosts as hosts_mod
from ..http_kv import RendezvousServer, new_secret
from ..safe_shell_exec import safe_execute
from . import pods as pods_mod
from .discovery import HostManager
from .registration import WorkerStateRegistry, READY, SUCCESS, FAILURE

__all__ = ["ElasticDriver", "run_elastic", "RESTART_EXIT_CODE"]

_DISCOVERY_INTERVAL_S = 1.0

# Worker exit code meaning "ready for the next rendezvous" — the TPU
# elastic model is process-restart (a compiled XLA world cannot resize
# in place): workers persist their committed state to disk and exit with
# this code; the driver respawns every slot under the new generation and
# the fresh processes resume from the disk commit (see
# horovod_tpu/elastic.py run()).
RESTART_EXIT_CODE = 79


@dataclasses.dataclass
class _WorkerProc:
    slot: hosts_mod.SlotInfo
    thread: threading.Thread
    generation: int


class ElasticDriver:
    """Drives elastic worker generations.

    ``spawn_fn(slot, generation)`` starts one worker and returns when it
    exits, reporting the exit code — injectable so unit tests can fake
    whole clusters (ref test strategy: test/single/test_elastic_driver.py,
    SURVEY.md §4 tier 2).
    """

    def __init__(self,
                 host_manager: HostManager,
                 min_np: int,
                 max_np: Optional[int] = None,
                 spawn_fn: Optional[Callable[..., int]] = None,
                 reset_limit: Optional[int] = None,
                 discovery_interval: float = _DISCOVERY_INTERVAL_S,
                 kv_server: Optional[RendezvousServer] = None,
                 hosts_updated_cb: Optional[Callable[[int], None]] = None,
                 elastic_timeout: float = 600.0,
                 pod_slots: int = 0,
                 pod_tracker: Optional[pods_mod.PodTracker] = None):
        self._hm = host_manager
        self._kv = kv_server
        self._hosts_updated_cb = hosts_updated_cb
        self._pending_updates = 0
        self._min_np = min_np
        self._max_np = max_np or min_np
        self._spawn_fn = spawn_fn or (lambda slot, gen: 0)
        self._interval = discovery_interval
        self._elastic_timeout = elastic_timeout
        # Pod-granular control plane (runner/elastic/pods.py): exit
        # correlation, preemption drains, straggler eviction.  With no
        # declared pods and pod_slots=0 everything degenerates to the
        # flat per-host semantics.
        self._pod_slots = pod_slots
        self._pods = pod_tracker or pods_mod.PodTracker()
        self.registry = WorkerStateRegistry(self._on_barrier,
                                            reset_limit=reset_limit)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._generation = 0
        self._assignments: List[hosts_mod.SlotInfo] = []
        self._workers: Dict[int, _WorkerProc] = {}
        self._shutdown = threading.Event()
        self._result: Optional[int] = None
        self._discovery_thread: Optional[threading.Thread] = None
        self._rendezvous_cb: Optional[Callable[[List[hosts_mod.SlotInfo],
                                                int], None]] = None
        # Cluster anomaly correlation (telemetry/anomaly.py): created
        # lazily on the first discovery tick that finds HVDT_EVENT_LOG
        # configured — cluster events (a pod-wide step-time shift is
        # ONE event) land in the driver's JSONL event log.
        self._cluster_anomalies = None
        # Online policy controller (horovod_tpu/control): bound lazily
        # on the first tick that finds HVDT_CONTROLLER set — the
        # zero-overhead contract (control.get_controller() is None
        # otherwise, and nothing below exists).
        self._controller = None
        self._controller_seq = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self, rendezvous_cb=None) -> None:
        """rendezvous_cb(assignments, generation) publishes the new cluster
        spec (KV) before workers of that generation spawn."""
        self._rendezvous_cb = rendezvous_cb
        self._hm.update_available_hosts()
        self._discovery_thread = threading.Thread(
            target=self._discovery_loop, daemon=True, name="hvdt-elastic")
        self._discovery_thread.start()
        self._rendezvous()

    def stop(self) -> None:
        self._shutdown.set()
        with self._cond:
            self._cond.notify_all()

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        """Block until the job finishes; returns the exit code."""
        deadline = (time.monotonic() + timeout) if timeout else None
        with self._cond:
            while self._result is None and not self._shutdown.is_set():
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None
                self._cond.wait(remaining if remaining else 1.0)
            return self._result

    # -- discovery ---------------------------------------------------------

    def _discovery_loop(self) -> None:
        while not self._shutdown.wait(self._interval):
            try:
                changed = self._hm.update_available_hosts()
            except Exception as e:   # discovery scripts may flake
                print(f"elastic: discovery failed: {e}", file=sys.stderr)
                continue
            if changed:
                self._notify_hosts_updated()
            self._poll_worker_registry()
            self._check_pod_stragglers()
            events = self._check_cluster_anomalies()
            self._check_controller(events)

    def _poll_worker_registry(self) -> None:
        """Feed KV-reported worker states (workers put
        /registry/<generation>/<rank> = READY|SUCCESS|FAILURE at commit
        points — the KV replaces the reference's in-worker RPC listener,
        ref: runner/elastic/worker.py WorkerNotificationService)."""
        if self._kv is None:
            return
        gen = self.generation
        prefix = f"/registry/{gen}/"
        with self._kv.lock:
            items = {k: v for k, v in self._kv.store.items()
                     if k.startswith(prefix)}
        for key, val in items.items():
            try:
                rank = int(key.rsplit("/", 1)[1])
            except ValueError:
                continue
            state = val.decode()
            if state == READY:
                self.registry.record_ready(rank)
            elif state == SUCCESS:
                self.registry.record_success(rank)
            elif state == FAILURE:
                self.registry.record_failure(rank)

    def record_ready(self, rank: int) -> None:
        """A live worker requests re-rendezvous (HostsUpdatedInterrupt or
        collective failure recovery in its training loop)."""
        self.registry.record_ready(rank)

    def resize(self, min_np: Optional[int] = None,
               max_np: Optional[int] = None) -> None:
        """Scale hook: adjust the world-size bounds mid-run.  The next
        rendezvous plans assignments against the new bounds; live
        workers are nudged through the hosts-updated channel so one
        lands at their next commit.  This is the driver-side seam the
        serving autoscaler's policy layer and the online controller
        (ROADMAP item 5) drive — resize decisions stay outside the
        rendezvous machinery itself."""
        with self._lock:
            if min_np is not None:
                self._min_np = max(1, int(min_np))
            if max_np is not None:
                self._max_np = max(self._min_np, int(max_np))
        self._notify_hosts_updated()

    def telemetry_snapshots(self):
        """Aggregate worker telemetry snapshots from the rendezvous KV
        (workers publish /telemetry/<rank> every
        HVDT_TELEMETRY_PUBLISH_S when HVDT_TELEMETRY is on).  Returns
        {rank: snapshot_dict}; empty when no KV or nothing published —
        the driver-side half of the observability subsystem
        (telemetry/exporter.collect_driver_snapshots).  Each snapshot
        carries the worker's pod id plus its kv_retries_total /
        kv_errors_total counters, so control-plane flakiness is visible
        fleet-wide from the driver; the snapshots also feed the
        pod-straggler eviction rung (_check_pod_stragglers)."""
        if self._kv is None:
            return {}
        from ...telemetry.exporter import collect_driver_snapshots

        return collect_driver_snapshots(self._kv)

    def trace_dumps(self):
        """Per-rank Chrome-trace dumps published to the rendezvous KV
        (workers publish /trace/<rank> when HVDT_TRACE_DIR is set —
        merged into one rank-as-pid trace by telemetry.trace.merge_dumps
        / write_merged; run_elastic writes trace_merged.json under
        --trace-dir).  Returns {rank: dump}; empty without a KV."""
        if self._kv is None:
            return {}
        from ...telemetry.trace import collect_server_dumps

        return collect_server_dumps(self._kv)

    def flight_recorder_events(self):
        """Per-rank collective flight-recorder event lists from the
        rendezvous KV (/flightrecorder/<rank>) — the raw material of
        telemetry.flight_recorder.analyze_desync."""
        if self._kv is None:
            return {}
        from ...telemetry.flight_recorder import collect_server_events

        return collect_server_events(self._kv)

    def telemetry_rollup(self):
        """Step-aligned fleet roll-up over the latest KV snapshots
        (telemetry/aggregate.rollup): per-pod median/p99 step time,
        cluster wire-bytes-by-axis, goodput series, worst pod.  Ranks
        publishing the old snapshot schema (no step id / time series)
        are skipped and counted, never failed."""
        snaps = self.telemetry_snapshots()
        if not snaps:
            return {}
        from ...telemetry import aggregate as _aggregate

        return _aggregate.rollup(snaps)

    def _check_cluster_anomalies(self):
        """Run the cluster anomaly rules over the fleet snapshots each
        discovery tick (active only when HVDT_EVENT_LOG names a driver-
        side event log — the zero-overhead gate).  Returns the events
        that newly fired this tick — the controller's input."""
        if self._kv is None:
            return []
        events = []
        try:
            from ...telemetry import anomaly as _anomaly

            if self._cluster_anomalies is None:
                if _anomaly.get_event_log() is None:
                    return []
                self._cluster_anomalies = _anomaly.ClusterAnomalyMonitor()
            snaps = self.telemetry_snapshots()
            if not snaps:
                return []
            events = self._cluster_anomalies.observe(snaps)
            for ev in events:
                print(f"elastic: anomaly {ev.get('kind')} "
                      f"({ev.get('scope')}): {ev.get('message')}",
                      file=sys.stderr)
        except Exception as e:   # detection must never sink the driver
            print(f"elastic: cluster anomaly check failed: {e}",
                  file=sys.stderr)
        return events

    # -- online policy controller (horovod_tpu/control) --------------------

    def _bind_controller(self, ctl) -> None:
        """Wire the controller's action kinds to the driver seams it
        acts through.  Comm-leg actions publish a KV override the
        workers' LegListener adopts at their next step boundary;
        membership actions ride the same paths the straggler rung and
        the serving autoscaler already use."""
        from ... import control as _control

        def _evict(action) -> bool:
            pod = str(action.param("pod") or "")
            if not pod:
                return False
            self._hm.blacklist_pod(pod)
            self._hm.update_available_hosts()
            self._notify_hosts_updated()
            return True

        def _resize(action) -> bool:
            self.resize(min_np=action.param("min_np"),
                        max_np=action.param("max_np"))
            return True

        def _scale(action) -> bool:
            if self._kv is None:
                return False
            from ... import fleet as _fleet

            target = int(action.param("target"))
            sched = _fleet.get_scheduler()
            if sched is not None:
                # A fleet scheduler owns /serve/target_replicas: the
                # controller's scale becomes a HINT through its
                # guardrails instead of a second writer on the key.
                return sched.hint_scale(target, source="controller",
                                        reason=action.reason)
            # No scheduler: write the seq-guarded doc directly — the
            # audited form, refused while a raw-int operator override
            # owns the key (the two-writers race regression).
            return _fleet.write_target(
                self._kv, target, writer="controller",
                reason=action.reason) is not None

        def _leg(action) -> bool:
            if self._kv is None:
                return False
            legs = _control.apply.legs_for_action(action)
            if not legs:
                return False
            self._controller_seq += 1
            return _control.apply.publish_legs(self._kv, legs,
                                               self._controller_seq)

        ctl.bind_appliers({
            "evict_pod": _evict, "resize": _resize,
            "scale_replicas": _scale, "flip_transport": _leg,
            "retune_bucket": _leg, "toggle_overlap": _leg,
            "toggle_zero": _leg,
        })

    def _check_controller(self, events) -> None:
        """One controller tick per discovery tick: feed the fresh
        anomaly events plus the fleet's deviation/step picture, let it
        verify pending actions and decide on the new ones."""
        try:
            from ... import control as _control

            ctl = _control.get_controller()
            if ctl is None:
                return
            if ctl is not self._controller:
                self._bind_controller(ctl)
                # Seed the geometry the pricer needs from the live
                # cluster picture.
                pods = {s.pod for s in self.assignments if s.pod}
                if pods:
                    ctl.state.pods = len(pods)
                if self._pod_slots:
                    ctl.state.pod_size = self._pod_slots
                    ctl.state.chips_per_pod = self._pod_slots
                self._controller = ctl
            snaps = self.telemetry_snapshots()
            deviation = None
            step = None
            step_s = None
            if snaps:
                ratios = [float(s.get("perf_deviation_ratio") or 0.0)
                          for s in snaps.values()]
                deviation = max(ratios) if any(ratios) else None
                steps = [int(s.get("step") or 0) for s in snaps.values()]
                step = max(steps) if steps else None
                from ...telemetry import aggregate as _aggregate

                means = _aggregate.recent_step_means(snaps)
                if means:
                    vals = sorted(means.values())
                    step_s = vals[(len(vals) - 1) // 2]
            ctl.tick(events or (), deviation_ratio=deviation,
                     observed_step_s=step_s, step=step)
        except Exception as e:   # the loop must never sink the driver
            print(f"elastic: controller tick failed: {e}",
                  file=sys.stderr)

    def _check_pod_stragglers(self) -> None:
        """The pod-granular escalation rung over the PR-5 straggler
        gauges: aggregate per-rank step-time medians from the telemetry
        snapshots into per-pod medians; a pod slower than threshold x
        the cross-pod median for HVDT_POD_STRAGGLER_EVICT consecutive
        windows is EVICTED — blacklisted (cooldown applies, so a
        recovered pod can rejoin) and the run resizes down to the
        remaining pod multiple instead of limping at the slow pod's
        pace."""
        if self._pods.evict_windows <= 0 or self._kv is None:
            return
        snaps = self.telemetry_snapshots()
        if not snaps or not self._pods.snapshots_fingerprint(snaps):
            return
        rank_pod = {s.rank: s.pod for s in self.assignments}
        by_pod: Dict[str, List[float]] = {}
        for rank, snap in snaps.items():
            ms = snap.get("step_time_p50_ms")
            pod = snap.get("pod") or rank_pod.get(rank)
            if ms and pod:
                by_pod.setdefault(pod, []).append(float(ms))
        medians = {p: sorted(v)[(len(v) - 1) // 2]
                   for p, v in by_pod.items()}
        for pod in self._pods.observe_step_medians(medians):
            print(f"elastic: pod {pod} evicted as straggler "
                  f"(median step {medians[pod]:.1f} ms over "
                  f"{self._pods.evict_windows} windows)", file=sys.stderr)
            self._hm.blacklist_pod(pod)
            self._hm.update_available_hosts()
            self._notify_hosts_updated()

    def _notify_hosts_updated(self) -> None:
        with self._cond:
            self._cond.notify_all()
            self._pending_updates += 1
            n = self._pending_updates
        # Publish so live workers see the membership change at their next
        # commit and exit for respawn (the KV replaces the reference's
        # in-worker notification RPC, runner/elastic/worker.py).
        if self._hosts_updated_cb is not None:
            self._hosts_updated_cb(n)

    def _usable_slots(self) -> int:
        """Slots assignable at pod granularity: whole same-size pods
        only, minus drained (preempted) pods — so the rendezvous wait
        doesn't end on a half-discovered pod it can't place."""
        return pods_mod.usable_slots(self._hm.current.hosts,
                                     self._pod_slots,
                                     self._pods.drained_pods())

    def wait_for_available_slots(self, min_np: int,
                                 timeout: float = 600.0) -> None:
        """(ref: driver.py:145) block until discovery reports >= min_np
        pod-assignable slots."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._usable_slots() < min_np:
                if self._shutdown.is_set():
                    raise RuntimeError("driver shut down while waiting")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"timed out waiting for {min_np} slots; discovered "
                        f"{self._usable_slots()}")
                self._cond.wait(min(remaining, self._interval))

    # -- rendezvous / spawn ------------------------------------------------

    def _rendezvous(self) -> None:
        # Recovery-budget attribution, driver side: the rendezvous phase
        # starts the moment a new generation is needed and ends when
        # every slot of the new world has been handed to a spawner.
        # Workers attribute their own boot restore/replay; the driver
        # owns the slot-wait + assignment + publish window.
        t0 = time.monotonic()
        self.wait_for_available_slots(self._min_np,
                                      timeout=self._elastic_timeout)
        with self._lock:
            self._generation += 1
            gen = self._generation
            self._assignments = pods_mod.plan_assignments(
                self._hm.current.hosts, self._min_np, self._max_np,
                pod_slots=self._pod_slots,
                exclude=self._pods.drained_pods())
            self.registry.reset(len(self._assignments))
        layout = pods_mod.pod_layout(self._assignments)
        print(f"elastic: rendezvous generation {gen}: "
              f"{len(self._assignments)} slots in {layout['num_pods']} "
              f"pod(s) x {layout['pod_size']} "
              f"(dcn={layout['mesh']['dcn']}, ici={layout['mesh']['ici']})",
              file=sys.stderr)
        if self._rendezvous_cb:
            self._rendezvous_cb(self._assignments, gen)
        for slot in self._assignments:
            self._start_worker(slot, gen)
        self.last_rendezvous_seconds = time.monotonic() - t0
        self.rendezvous_seconds_total = getattr(
            self, "rendezvous_seconds_total", 0.0) \
            + self.last_rendezvous_seconds
        if gen > 1:
            # Generation 1 is job boot, not recovery; later generations
            # are the rendezvous leg of a recovery and are printed so
            # scenario harnesses (and operators reading driver logs) can
            # audit the budget without scraping worker metrics.
            print(f"elastic: generation {gen} rendezvous took "
                  f"{self.last_rendezvous_seconds:.2f}s", file=sys.stderr)

    def _start_worker(self, slot: hosts_mod.SlotInfo, gen: int) -> None:
        def _run():
            try:
                code = self._spawn_fn(slot, gen)
            except Exception as e:
                print(f"elastic: worker {slot.rank} spawn error: {e}",
                      file=sys.stderr)
                code = 1
            self.record_exit(slot, gen, code)

        t = threading.Thread(target=_run, daemon=True,
                             name=f"hvdt-worker-{slot.rank}")
        with self._lock:
            self._workers[slot.rank] = _WorkerProc(slot, t, gen)
        t.start()

    def record_exit(self, slot: hosts_mod.SlotInfo, gen: int,
                    code: int) -> None:
        from ...resilience.preempt import PREEMPT_EXIT_CODE

        with self._lock:
            if gen != self._generation:
                return   # stale worker from a previous generation
        pod = slot.pod or self._hm.pod_of(slot.hostname)
        if code == RESTART_EXIT_CODE:
            # Worker observed a membership change and exited for respawn:
            # it is READY for the next rendezvous, not failed.
            self.registry.record_ready(slot.rank)
            return
        if code == PREEMPT_EXIT_CODE:
            # Clean preemption exit (resilience/preempt.py): the worker
            # checkpointed and its host is going away.  Preemption
            # reclaims whole slices, so ONE rank's grace-window exit
            # drains its entire pod: the next rendezvous won't place
            # workers on the pod's other hosts even while discovery
            # still lists them.  No blacklist, no failure count.
            if self._pods.drain(pod):
                print(f"elastic: pod {pod} draining (rank {slot.rank} "
                      f"preempted on {slot.hostname}, clean removal)",
                      file=sys.stderr)
            self.registry.record_ready(slot.rank)
            return
        if code == 0:
            self.registry.record_success(slot.rank)
        else:
            # Failed worker ⇒ suspect POD (ref: driver.py:297 exit
            # handling + discovery blacklist).  Exits of one pod's ranks
            # within HVDT_POD_EXIT_WINDOW_S are one correlated loss:
            # the first opens the pod-removal event and blacklists the
            # pod ONCE; the rest fold into it (no cooldown doubling, no
            # N independent recovery decisions).
            if self._pods.record_failure(pod):
                print(f"elastic: pod-removal event for pod {pod} "
                      f"(rank {slot.rank} on {slot.hostname} exited "
                      f"{code}); correlated exits within the window "
                      f"fold into this event", file=sys.stderr)
                self._hm.blacklist_pod(pod)
                self._hm.update_available_hosts()
            self.registry.record_failure(slot.rank)

    # -- barrier -----------------------------------------------------------

    def _on_barrier(self, states: Dict[str, set]) -> None:
        if states[READY]:
            if self.registry.reset_limit_reached():
                self._finish(1)
                return
            threading.Thread(target=self._safe_rerendezvous,
                             daemon=True).start()
        elif states[FAILURE] and not states[READY]:
            if len(states[FAILURE]) >= len(self._assignments):
                self._finish(1)
            else:
                # Partial failure: survivors need a new, smaller rendezvous.
                threading.Thread(target=self._safe_rerendezvous,
                                 daemon=True).start()
        else:
            self._finish(0)

    def _safe_rerendezvous(self) -> None:
        try:
            self._rendezvous()
        except (TimeoutError, RuntimeError) as e:
            print(f"elastic: cannot re-rendezvous: {e}", file=sys.stderr)
            self._finish(1)

    def _finish(self, code: int) -> None:
        with self._cond:
            if self._result is None:
                self._result = code
            self._cond.notify_all()

    # -- introspection (tests) --------------------------------------------

    @property
    def generation(self) -> int:
        with self._lock:
            return self._generation

    @property
    def assignments(self) -> List[hosts_mod.SlotInfo]:
        with self._lock:
            return list(self._assignments)


def run_elastic(args) -> int:
    """CLI entry for ``hvdtrun --host-discovery-script ...``
    (ref: launch.py:621 _run_elastic → gloo_run.py:340)."""
    from ..launch import knob_env_for

    knob_env = knob_env_for(args)
    # The policy controller lives in THIS process (discovery loop), not
    # in the workers, so its knobs must reach the driver's own env —
    # knob_env is only forwarded into worker processes.
    for _k, _v in knob_env.items():
        if _k.startswith("HVDT_CONTROLLER") or _k == "HVDT_EVENT_LOG":
            os.environ[_k] = _v
    if knob_env.get("HVDT_CPU_OPERATIONS", "").lower() == "tcp":
        # The static rank->addr contract HVDT_TCP_ADDRS encodes cannot
        # survive elastic membership changes; reject up front instead of
        # letting workers crash on an empty address list mid-bootstrap.
        raise RuntimeError(
            "--cpu-operations tcp is not supported with elastic launch: "
            "the TCP socket mesh needs a static rank->host:port mapping. "
            "Use the default 'xla' host data plane for elastic jobs.")

    hm = HostManager.from_script(args.host_discovery_script,
                                 default_slots=args.slots_per_host)
    min_np = args.min_np or args.num_proc or 1
    max_np = args.max_np or args.num_proc or min_np

    server = RendezvousServer(secret=new_secret())
    port = server.start()
    addr = socket.gethostbyname(socket.gethostname())
    if getattr(args, "nics", None):
        from ..launch import _nic_addr

        addr = _nic_addr(args.nics.split(",")) or addr
    coordinator_port = args.coordinator_port

    pending_state = {"n": 0}

    def rendezvous_cb(slots: List[hosts_mod.SlotInfo], gen: int) -> None:
        import json as _json

        spec = "\n".join(
            f"{s.rank},{s.hostname},{s.local_rank},{s.cross_rank},"
            f"{s.size},{s.local_size},{s.cross_size},"
            f"{s.pod},{s.pod_index},{s.pod_rank}" for s in slots)
        server.put_local(f"/rendezvous/{gen}/spec", spec.encode())
        # Freeze the pending-updates counter as of this rendezvous so
        # generation-gen workers baseline against it (worker.py init):
        # membership changes during their boot window stay visible.
        server.put_local(f"/rendezvous/{gen}/pending_base",
                         str(pending_state["n"]).encode())
        # Two-level rendezvous: the (dcn, ici) pod layout next to the
        # flat spec — what a worker needs to build the hierarchical
        # mesh (parallel.mesh.pod_mesh_spec) whose cross-pod axis rides
        # the dcn transport policy.
        server.put_local(f"/rendezvous/{gen}/pods", _json.dumps(
            pods_mod.pod_layout(slots)).encode())
        server.put_local("/rendezvous/version", str(gen).encode())

    def hosts_updated_cb(n: int) -> None:
        pending_state["n"] = n
        server.put_local("/rendezvous/pending", str(n).encode())

    def spawn_fn(slot: hosts_mod.SlotInfo, gen: int) -> int:
        from ..launch import _build_command

        coord = slot.hostname if slot.rank != slot.rank else slot.hostname
        base_env = {
            "HVDT_RENDEZVOUS_ADDR": addr,
            "HVDT_RENDEZVOUS_PORT": str(port),
            "HVDT_SECRET": server.secret.hex(),
            "HVDT_COORDINATOR_ADDR": f"{coord}:{coordinator_port}",
            "HVDT_ELASTIC": "1",
            "HVDT_GENERATION": str(gen),
            **knob_env,
        }
        cmd, env = _build_command(args, slot, base_env, args.command)
        prefix = f"[{slot.rank}]" if args.verbose else ""
        return safe_execute(cmd, env=env, prefix=prefix)

    def _int_knob(name: str) -> int:
        raw = knob_env.get(name) or os.environ.get(name) or "0"
        try:
            return int(raw)
        except ValueError:
            return 0

    tracker = pods_mod.PodTracker(
        evict_windows=_int_knob("HVDT_POD_STRAGGLER_EVICT") or None)
    # kv_server wires the driver-side KV consumers: worker state
    # publishes (/registry), telemetry snapshot aggregation, and the
    # pod-straggler eviction rung those snapshots feed.
    driver = ElasticDriver(hm, min_np, max_np, spawn_fn,
                           reset_limit=args.reset_limit,
                           kv_server=server,
                           hosts_updated_cb=hosts_updated_cb,
                           elastic_timeout=getattr(args, "elastic_timeout",
                                                   600.0),
                           pod_slots=_int_knob("HVDT_POD_SIZE"),
                           pod_tracker=tracker)
    try:
        driver.start(rendezvous_cb)
        code = driver.wait()
        return code if code is not None else 1
    finally:
        driver.stop()
        trace_dir = knob_env.get("HVDT_TRACE_DIR") or \
            os.environ.get("HVDT_TRACE_DIR", "")
        if trace_dir:
            # Driver-side merge (hvdtrun --trace-dir): pull every rank's
            # published dump from the KV before the server dies and emit
            # the single rank-as-pid Chrome trace.
            try:
                from ...telemetry.trace import write_merged

                merged = write_merged(server, trace_dir)
                if merged:
                    print(f"elastic: merged trace written to {merged}",
                          file=sys.stderr)
            except Exception as e:
                print(f"elastic: trace merge failed: {e}", file=sys.stderr)
        server.stop()
