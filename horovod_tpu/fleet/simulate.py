"""Trace-driven chaos simulation of the fleet scheduler — on CPU, with
no devices.

The point of this harness is that it runs the REAL scheduler
(:class:`~.scheduler.FleetScheduler`: same pricing, same guardrails,
same event records) against a synthetic world cheap enough for CI: pod
capacity is priced by ``TopologySpec`` + the cost model (a 16-pod fleet
is a dataclass, not hardware), serving is a fluid queue (offered rps vs
per-unit capacity, queue-proportional p99), and faults come from the
same ``resilience.faults`` plans the live stack injects — ``pod_crash``
lands as a correlated inventory removal mid-reclaim, ``slow_replica``
inflates the simulated p99, ``traffic_spike`` adds synthetic offered
load through :meth:`FaultInjector.extra_rps`.

One run emits a goodput-vs-SLO-compliance report::

    {"goodput_fraction": 0.97, "slo_compliance": 0.93,
     "reclaims": 1, "drains": 2, "dropped_requests": 0, ...}

where goodput_fraction is productive training chip-time over allocated
training chip-time (restart charges per world change, the sub-30s
recovery budget) and slo_compliance is the fraction of ticks with
simulated p99 inside the trace's SLO.  ``hvdtrun fleet`` is this
module's CLI, and
``--event-log`` threads every ``fleet_decision`` into the JSONL that
``analysis --report`` and ``hvdtrun top`` render.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

from ..common.logging_util import get_logger
from ..resilience import faults
from ..runner.elastic.discovery import HostManager
from ..runner.hosts import HostInfo
from .inventory import FleetInventory
from .scheduler import FleetConfig, FleetScheduler, Move
from .traces import TrafficTrace, load_trace

__all__ = ["simulate_trace", "main"]

log = get_logger(__name__)


class _SimExit(Exception):
    """Raised by the injector's exit_fn inside the simulator — a pod
    crash is an event here, not a process death."""

    def __init__(self, code: int):
        super().__init__(f"sim exit {code}")
        self.code = code


def simulate_trace(trace: TrafficTrace, *,
                   pods: int = 5,
                   chips_per_pod: int = 4,
                   serve_units: int = 1,
                   tick_s: float = 10.0,
                   rps_per_unit: float = 100.0,
                   base_p99_ms: float = 60.0,
                   queue_limit_per_unit: float = 50.0,
                   restart_s: float = 20.0,
                   fault_plan: Optional[str] = None,
                   seed: int = 0,
                   cfg: Optional[FleetConfig] = None,
                   model=None,
                   event_log=None) -> Dict[str, Any]:
    """Replay ``trace`` (+ an optional fault plan) against a fresh
    scheduler over a simulated ``pods``-pod fleet.  Deterministic for a
    given (trace, plan, seed).  Returns the report dict."""
    if pods < 2:
        raise ValueError("the fleet needs at least 2 pods to move one")
    serve_units = max(1, min(int(serve_units), pods - 1))
    names = [f"pod{i}" for i in range(pods)]
    hm = HostManager(
        lambda: [HostInfo(n, chips_per_pod, pod=n) for n in names])
    sim_now = [0.0]
    inv = FleetInventory(names, host_manager=hm,
                         clock=lambda: sim_now[0])
    for n in names[:serve_units]:
        inv.acquire(n, "serve")
    for n in names[serve_units:]:
        inv.acquire(n, "train")
    entitled_train = len(inv.leased("train"))

    sched = FleetScheduler(inv, cfg=cfg, model=model,
                           event_log=event_log,
                           clock=lambda: sim_now[0],
                           chips_per_pod=chips_per_pod)

    slow_s: List[float] = []
    inj: Optional[faults.FaultInjector] = None
    if fault_plan:
        inj = faults.FaultInjector(
            faults.parse_plan(fault_plan), seed=seed,
            sleep_fn=slow_s.append,
            exit_fn=lambda code: (_ for _ in ()).throw(_SimExit(code)))

    # The world-change ledger: every resize (reclaim/backfill/crash)
    # charges ``restart_s`` of the new training world — the emergency
    # commit + peer-RAM restore budget the live stack holds under 30s.
    charges = {"restart_chip_s": 0.0}

    def _world_changed() -> None:
        charges["restart_chip_s"] += \
            min(restart_s, tick_s) * len(inv.leased("train")) \
            * chips_per_pod

    def _apply_reclaim(move: Move) -> bool:
        # Drain the training pod (exit-83 path) and hand it to serving.
        _world_changed()
        counts["reclaims"] += 1
        counts["drains"] += 1
        return True

    def _apply_backfill(move: Move) -> bool:
        _world_changed()
        counts["backfills"] += 1
        counts["drains"] += 1
        return True

    sched.bind("reclaim", _apply_reclaim)
    sched.bind("backfill", _apply_backfill)

    counts = {"reclaims": 0, "backfills": 0, "drains": 0}
    queue = 0.0
    dropped = 0.0
    offered_total = 0.0
    slo_ok = 0
    max_p99 = 0.0
    alloc_chip_s = 0.0
    decisions: List[Dict[str, Any]] = []
    n_ticks = max(1, int(trace.duration_s / tick_s))

    for i in range(n_ticks):
        t = i * tick_s
        sim_now[0] = t
        slow_s.clear()

        # -- faults first: the world the scheduler sees this tick ------
        if inj is not None:
            inj.fire("serve.traffic", step=i, rank=0, now=t)
            for u in range(len(inv.leased("serve"))):
                try:
                    inj.fire("serve.predict", step=i, rank=u)
                except _SimExit:
                    # A serve-unit crash: the pod's removal event hits
                    # both workloads through the shared inventory.
                    victims = inv.leased("serve")
                    if victims:
                        inv.record_failure(victims[-1], now=t)
            for pod in list(inv.leased("train")):
                try:
                    inj.fire("step", step=i, rank=0, pod=pod)
                except _SimExit:
                    if inv.record_failure(pod, now=t):
                        _world_changed()

        # -- serving: fluid queue over the current unit count ----------
        units = len(inv.leased("serve"))
        offered = trace.rps_at(t)
        if inj is not None:
            offered += inj.extra_rps(now=t)
        offered_total += offered * tick_s
        capacity = units * rps_per_unit
        queue = max(0.0, queue + (offered - capacity) * tick_s)
        queue_cap = queue_limit_per_unit * max(1, units)
        dropped_tick = 0.0
        if queue > queue_cap:
            dropped_tick = queue - queue_cap
            dropped += dropped_tick
            queue = queue_cap
        slow_ms = 1e3 * sum(slow_s) / max(1, units)
        p99 = base_p99_ms * (1.0 + queue / max(capacity, 1e-9)) + slow_ms
        max_p99 = max(max_p99, p99)
        # A tick that sheds load is not compliant, whatever its p99 —
        # a dropped request is an SLO violation by definition.
        if p99 <= trace.slo_p99_ms and dropped_tick == 0.0:
            slo_ok += 1

        # -- training goodput accounting --------------------------------
        alloc_chip_s += len(inv.leased("train")) * chips_per_pod * tick_s

        # -- the scheduler's tick (the same code the launcher runs) -----
        for d in sched.tick(
                queue_per_replica=queue / max(1, units),
                p99_ms=p99, slo_p99_ms=trace.slo_p99_ms,
                goodput_fraction=_goodput(alloc_chip_s, charges),
                step=i):
            decisions.append(d.to_record())

    return {
        "trace": trace.name,
        "pods": pods,
        "chips_per_pod": chips_per_pod,
        "ticks": n_ticks,
        "tick_s": tick_s,
        "slo_p99_ms": trace.slo_p99_ms,
        "goodput_fraction": round(_goodput(alloc_chip_s, charges), 6),
        "slo_compliance": round(slo_ok / n_ticks, 6),
        "reclaims": counts["reclaims"],
        "backfills": counts["backfills"],
        "drains": counts["drains"],
        "rollbacks": sched.rollbacks,
        "dropped_requests": int(round(dropped)),
        "requests_offered": int(round(offered_total)),
        "max_p99_ms": round(max_p99, 3),
        "recovery_s": restart_s,
        "entitled_train_pods": entitled_train,
        "final": {"train_pods": len(inv.leased("train")),
                  "serve_units": len(inv.leased("serve"))},
        "faults": dict(inj.counters) if inj is not None else {},
        "removal_events": inv.tracker.removal_events,
        "decisions": decisions,
    }


def _goodput(alloc_chip_s: float, charges: Dict[str, float]) -> float:
    if alloc_chip_s <= 0:
        return 1.0
    return max(0.0, 1.0 - charges["restart_chip_s"] / alloc_chip_s)


def main(argv: Optional[List[str]] = None) -> int:
    """``hvdtrun fleet <trace>`` — replay a traffic trace (builtin name
    or JSON path) through the fleet scheduler on CPU and print the
    goodput-vs-SLO report as one JSON doc."""
    p = argparse.ArgumentParser(
        prog="hvdtrun fleet",
        description="Trace-driven CPU simulation of the bin-packing "
                    "fleet scheduler (no devices; TopologySpec + cost "
                    "model price the pod-scale capacity).")
    p.add_argument("trace",
                   help="Builtin trace name (diurnal, flash_crowd, "
                        "step_function) or a trace JSON path "
                        "(tools/traces/*.json).")
    p.add_argument("--pods", type=int, default=5,
                   help="Total fleet pods (default 5).")
    p.add_argument("--chips-per-pod", type=int, default=4,
                   help="Chips per pod for the cost model (default 4).")
    p.add_argument("--serve-units", type=int, default=1,
                   help="Pods initially leased to serving (default 1).")
    p.add_argument("--tick-s", type=float, default=10.0,
                   help="Simulated seconds per scheduler tick.")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="Override the trace's serving SLO.")
    p.add_argument("--fault-plan", default=None,
                   help="resilience.faults plan to inject (e.g. "
                        "'pod_crash@step=12:pod=pod3,"
                        "traffic_spike@step=20:rps=300:secs=120').")
    p.add_argument("--seed", type=int, default=0,
                   help="Fault RNG seed (deterministic replay).")
    p.add_argument("--observe", action="store_true",
                   help="Dry-run: the scheduler decides + logs but "
                        "never moves a pod.")
    p.add_argument("--event-log", default=None,
                   help="Append fleet_decision/fleet_outcome JSONL "
                        "records here (renders in analysis --report "
                        "and hvdtrun top).")
    args = p.parse_args(argv)

    trace = load_trace(args.trace, slo_p99_ms=args.slo_p99_ms)
    cfg = FleetConfig.from_env()
    if args.observe:
        cfg.mode = "observe"
    event_log = None
    if args.event_log:
        from ..telemetry.anomaly import EventLog

        event_log = EventLog(args.event_log)
    report = simulate_trace(
        trace, pods=args.pods, chips_per_pod=args.chips_per_pod,
        serve_units=args.serve_units, tick_s=args.tick_s,
        fault_plan=args.fault_plan, seed=args.seed, cfg=cfg,
        event_log=event_log)
    # The decision stream is for the event log / --report; the stdout
    # contract is the summary the bench harness parses.
    summary = {k: v for k, v in report.items() if k != "decisions"}
    print(json.dumps(summary, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
