"""Step-level training statistics: step time, throughput, MFU, goodput.

The aggregate layer above per-collective instrumentation — the numbers
the TPU-pod scaling study says are binding at scale (goodput, MFU,
straggler ranks) rather than per-op traces.  A :class:`StepTimer` wraps
the training loop (``step_pipeline.donated_step`` consumers,
user loops) and publishes:

* ``hvdt_step_time_seconds``  — host-fenced step duration summary
* ``hvdt_examples_per_sec``   — windowed throughput gauge
* ``hvdt_mfu``                — model-flops utilization gauge, from the
  caller's flops-per-step
  against the device generation's peak (:func:`peak_flops_for`)
* ``hvdt_steps_total``        — monotonic step counter

A :class:`GoodputLedger` charges wall-clock lost to recompiles, restores
and recovered faults against total elapsed time and publishes
``hvdt_goodput_fraction`` — the "fraction of wall time spent making
forward progress" scalar an operator pages on.
:func:`bind_resilience_gauges` bridges the PR-4 resilience counters
(fault injector fire counts, emergency preemption checkpoints) into the
registry as live probes, so one scrape tells the whole recovery story.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Optional

from .metrics import Gauge, MetricsRegistry, default_registry

__all__ = ["StepTimer", "GoodputLedger", "peak_flops_for",
           "bind_resilience_gauges", "record_memory_accounting",
           "tree_bytes", "PEAK_BY_DEVICE_KIND", "RECOVERY_PHASES",
           "recovery_ledger", "reset_recovery_ledger",
           "PerfExpectation", "DeviationTracker", "get_deviation_tracker",
           "publish_expected_schedule_cost",
           "maybe_publish_expected_cost", "reset_expectation",
           "expected_vs_observed_doc"]

# bf16 peak FLOP/s and HBM byte/s by TPU generation (device_kind
# substring, lowercase), so MFU math has one home.
PEAK_BY_DEVICE_KIND = (
    ("v6", 918e12, 1640e9), ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5 lite", 197e12, 819e9), ("v5e", 197e12, 819e9),
    ("v5litepod", 197e12, 819e9),
    ("v4", 275e12, 1228e9), ("v3", 123e12, 900e9), ("v2", 46e12, 700e9),
)


def _positive_or_none(value) -> Optional[float]:
    """Finite positive float, else None — the 'is MFU publishable' test
    (0, NaN, inf, and unparsable values all mean 'unknown')."""
    try:
        v = float(value)
    except (TypeError, ValueError):
        return None
    return v if (v > 0 and v != float("inf")) else None


def peak_flops_for(device_kind: str):
    """(peak_flops, peak_hbm_bw) for a device kind, or (None, None) when
    unknown (CPU, simulators) — MFU is then unpublishable, not faked."""
    dk = (device_kind or "").lower()
    for sub, flops, bw in PEAK_BY_DEVICE_KIND:
        if sub in dk:
            return flops, bw
    return None, None


class StepTimer:
    """Times training steps and publishes throughput/MFU metrics.

    Usage (custom loops)::

        timer = StepTimer(examples_per_step=batch,
                          flops_per_step=cost["flops"],
                          device_kind=dev.device_kind)
        for batch in loader:
            with timer.step():
                run_one_step(batch)   # must end with a host fence

    or call :meth:`observe` with externally measured durations (a loop
    that times whole iters and divides).  ``straggler`` optionally chains
    a :class:`~horovod_tpu.telemetry.straggler.StragglerMonitor` so the
    cross-rank skew check rides the same observation stream.
    """

    def __init__(self, examples_per_step: int = 0,
                 flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 device_kind: Optional[str] = None,
                 registry: Optional[MetricsRegistry] = None,
                 straggler=None,
                 ewma_alpha: float = 0.2):
        reg = registry if registry is not None else default_registry()
        self.registry = reg
        self.examples_per_step = int(examples_per_step)
        # MFU inputs are *validated up front*: an unknown device-peak
        # table entry (peak_flops_for -> None), zero/absent caller
        # flops, or a non-finite value mean MFU is unpublishable — the
        # gauge is then never registered (rather than rendering a
        # misleading 0) and observe() can't divide by zero.
        self.flops_per_step = _positive_or_none(flops_per_step)
        if peak_flops is None and device_kind:
            peak_flops, _ = peak_flops_for(device_kind)
        self.peak_flops = _positive_or_none(peak_flops)
        self.straggler = straggler
        self._alpha = float(ewma_alpha)
        self._ewma: Optional[float] = None
        self._lock = threading.Lock()
        self._summary = reg.summary(
            "hvdt_step_time_seconds",
            "Host-observed training step duration")
        self._steps = reg.counter(
            "hvdt_steps_total", "Training steps observed by the StepTimer")
        self._examples = reg.gauge(
            "hvdt_examples_per_sec",
            "Windowed training throughput (examples/s, EWMA of step time)")
        self._mfu: Optional[Gauge] = None
        if self.flops_per_step is not None and self.peak_flops is not None:
            self._mfu = reg.gauge(
                "hvdt_mfu",
                "Model-flops utilization: flops_per_step / (step_time * "
                "peak_flops); only published when caller flops and the "
                "device peak are both known")

    def step(self):
        """Context manager timing one step."""
        return _StepScope(self)

    def observe(self, seconds: float) -> None:
        """Record one step's duration (externally timed)."""
        s = float(seconds)
        self._summary.observe(s)
        self._steps.inc()
        with self._lock:
            self._ewma = s if self._ewma is None else (
                self._alpha * s + (1.0 - self._alpha) * self._ewma)
            ewma = self._ewma
        if ewma > 0:
            if self.examples_per_step:
                self._examples.set(self.examples_per_step / ewma)
            if self._mfu is not None:
                self._mfu.set(
                    self.flops_per_step / (ewma * self.peak_flops))
        if self.straggler is not None:
            self.straggler.observe(s)
        # Live perf attribution: the deviation tracker keeps
        # hvdt_perf_deviation_ratio current against the cost-model
        # prediction, and the history layer records the time-series
        # sample (both are None-when-off — one module lookup each).
        tracker = get_deviation_tracker()
        if tracker is not None:
            tracker.observe(s)
        from . import history as _history

        h = _history.get_history()
        if h is not None:
            h.observe_step(self._summary.count, s)

    @property
    def count(self) -> int:
        return self._summary.count

    def mean_step_seconds(self) -> Optional[float]:
        return self._summary.mean()

    def mfu(self) -> Optional[float]:
        if self._mfu is None:
            return None
        v = self._mfu.value()
        return v if v > 0 else None

    def snapshot(self) -> Dict[str, Optional[float]]:
        """The compact dict harnesses (bench JSON) embed."""
        pct = self._summary.percentiles()
        return {
            "steps": self._summary.count,
            "step_time_p50_ms": (round(pct[0.5] * 1e3, 3)
                                 if pct[0.5] is not None else None),
            "step_time_p99_ms": (round(pct[0.99] * 1e3, 3)
                                 if pct[0.99] is not None else None),
            "examples_per_sec": (round(self._examples.value(), 2)
                                 if self._summary.count else None),
            "mfu": (round(self._mfu.value(), 4)
                    if self._mfu is not None and self._mfu.value() > 0
                    else None),
        }


class _StepScope:
    __slots__ = ("_timer", "_t0")

    def __init__(self, timer: StepTimer):
        self._timer = timer
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self._timer.observe(time.perf_counter() - self._t0)
        return False


# The recovery-time budget's phase vocabulary: every non-training
# second of a detect→restore→resume cycle is attributed to exactly one
# of these (ROADMAP item 4 — "we recovered" becomes "we recovered fast
# enough", phase by phase).
RECOVERY_PHASES = ("checkpoint_snapshot", "checkpoint_write", "rendezvous",
                   "compile", "restore", "replay")


class GoodputLedger:
    """Wall-clock accounting: where did the non-training time go?

    ``charge(reason, seconds)`` books lost time under a reason label
    (``recompile``, ``restore``, ``fault_recovery``, ...); the published
    ``hvdt_goodput_fraction`` gauge is ``(elapsed - lost) / elapsed``
    live-probed at scrape time, and
    ``hvdt_goodput_lost_seconds_total{reason=...}`` itemizes the bill.

    The recovery-time budget rides on top: :meth:`charge_phase` books
    seconds against one of :data:`RECOVERY_PHASES` and publishes them as
    ``hvdt_recovery_seconds{phase=...}``, the per-phase decomposition a
    sub-30s recovery SLO is audited against.  A phase marked
    ``overlapped`` (the async checkpoint write, which runs UNDER
    training) is attributed but not charged against goodput.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 clock=time.monotonic, already_elapsed: float = 0.0):
        """``already_elapsed`` backdates the ledger start — a harness
        that constructs the ledger after a compile it intends to charge
        must include that time in the elapsed denominator too, or the
        fraction double-penalizes."""
        reg = registry if registry is not None else default_registry()
        self.registry = reg
        self._clock = clock
        self._start = clock() - max(0.0, float(already_elapsed))
        self._lock = threading.Lock()
        self._lost: Dict[str, float] = {}
        self._phases: Dict[str, float] = {}
        self._lost_counter = reg.counter(
            "hvdt_goodput_lost_seconds_total",
            "Wall-clock seconds lost to non-training work, by reason")
        self._phase_counter = reg.counter(
            "hvdt_recovery_seconds",
            "Non-training wall-clock attributed to the recovery-time "
            "budget, by phase (checkpoint_snapshot | checkpoint_write | "
            "rendezvous | compile | restore | replay)")
        reg.gauge(
            "hvdt_goodput_fraction",
            "(elapsed - lost) / elapsed since ledger start"
        ).set_function(self.fraction)

    def charge(self, reason: str, seconds: float) -> None:
        s = max(0.0, float(seconds))
        with self._lock:
            self._lost[reason] = self._lost.get(reason, 0.0) + s
        self._lost_counter.inc(s, reason=str(reason))

    def charge_phase(self, phase: str, seconds: float,
                     overlapped: bool = False) -> None:
        """Attribute ``seconds`` to a recovery phase.  Unknown phases
        raise — a typo'd phase would silently fall out of the budget
        audit.  ``overlapped`` phases (background checkpoint writes)
        appear in ``hvdt_recovery_seconds`` but do NOT reduce the
        goodput fraction: training kept running under them."""
        if phase not in RECOVERY_PHASES:
            raise ValueError(
                f"unknown recovery phase {phase!r}; valid: "
                f"{', '.join(RECOVERY_PHASES)}")
        s = max(0.0, float(seconds))
        with self._lock:
            self._phases[phase] = self._phases.get(phase, 0.0) + s
        self._phase_counter.inc(s, phase=phase)
        if not overlapped:
            self.charge(phase, s)

    @contextlib.contextmanager
    def phase(self, name: str, overlapped: bool = False):
        """Context manager timing one recovery phase::

            with ledger.phase("restore"):
                state.restore()
        """
        t0 = self._clock()
        try:
            yield
        finally:
            self.charge_phase(name, self._clock() - t0,
                              overlapped=overlapped)

    def recovery_seconds(self, phase: Optional[str] = None) -> float:
        with self._lock:
            if phase is not None:
                return self._phases.get(phase, 0.0)
            return sum(self._phases.values())

    def recovery_snapshot(self) -> Dict[str, float]:
        """Per-phase totals (the bench JSON / scenario-test handle)."""
        with self._lock:
            return dict(self._phases)

    def lost_seconds(self, reason: Optional[str] = None) -> float:
        with self._lock:
            if reason is not None:
                return self._lost.get(reason, 0.0)
            return sum(self._lost.values())

    def elapsed_seconds(self) -> float:
        return max(0.0, self._clock() - self._start)

    def fraction(self) -> float:
        elapsed = self.elapsed_seconds()
        if elapsed <= 0:
            return 1.0
        return max(0.0, (elapsed - self.lost_seconds()) / elapsed)


# ---------------------------------------------------------------------------
# Process-wide recovery ledger (the instance elastic.py / checkpoint.py
# charge into; None when telemetry is off — the zero-overhead contract)
# ---------------------------------------------------------------------------

_recovery_lock = threading.Lock()
_recovery: Optional[GoodputLedger] = None


def recovery_ledger() -> Optional[GoodputLedger]:
    """The process-wide ledger recovery phases are charged into, created
    on first use — or None when the telemetry subsystem is off, so the
    steady-state cost at every charge site is one None-check."""
    from . import instrument

    if not instrument.enabled():
        return None
    global _recovery
    with _recovery_lock:
        if _recovery is None:
            _recovery = GoodputLedger()
        return _recovery


def reset_recovery_ledger() -> None:
    """Drop the process-wide recovery ledger (tests; pairs with
    metrics.reset_default_registry, which orphans the old instance's
    metric objects)."""
    global _recovery
    with _recovery_lock:
        _recovery = None


def bind_resilience_gauges(registry: Optional[MetricsRegistry] = None
                           ) -> None:
    """Publish the resilience subsystem's ad-hoc counters as live gauges.

    Live probes (``set_function``) rather than shadow copies: the fault
    injector and preemption guard keep their own state; a scrape reads
    it at scrape time.  Safe to call repeatedly (gauges are
    get-or-create and rebinding the probe is idempotent)."""
    reg = registry if registry is not None else default_registry()

    def _injected() -> float:
        from ..resilience import faults

        inj = faults.get_injector()
        return float(inj.fired_total()) if inj is not None else 0.0

    def _emergency() -> float:
        from ..resilience.preempt import PreemptionGuard

        return float(PreemptionGuard.emergency_checkpoints)

    reg.gauge(
        "hvdt_injected_faults",
        "Faults the HVDT_FAULT_PLAN injector has fired in this process"
    ).set_function(_injected)
    reg.gauge(
        "hvdt_emergency_checkpoints",
        "Preemption-guard emergency checkpoints taken in this process"
    ).set_function(_emergency)


def tree_bytes(tree) -> int:
    """Total array bytes of a pytree (host-side shape math, no device
    access) — the feed for the memory-accounting gauges."""
    import numpy as np

    total = 0
    import jax

    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
    return int(total)


_MEMORY_GAUGE_DOCS = {
    "hvdt_param_bytes":
        "Per-rank parameter bytes (post-sharding: the replicated full "
        "tree, or 1/n of it under HVDT_ZERO=params)",
    "hvdt_optimizer_state_bytes":
        "Per-rank optimizer-state bytes (post-sharding: ~1/n of the "
        "replicated moments under HVDT_ZERO=states/params — the "
        "ZeRO memory win, observable from one scrape)",
}


def record_memory_accounting(param_bytes: Optional[float] = None,
                             optimizer_state_bytes: Optional[float] = None,
                             *, params=None, opt_state=None,
                             num_shards: int = 1,
                             zero_stage: str = "off",
                             registry: Optional[MetricsRegistry] = None
                             ) -> None:
    """Feed the per-rank memory-accounting gauges (``hvdt_param_bytes``,
    ``hvdt_optimizer_state_bytes``).

    Callers pass either precomputed byte counts or the live pytrees
    (``params=`` / ``opt_state=``, measured with :func:`tree_bytes` and
    divided by ``num_shards`` for sharded layouts).  No-op when the
    telemetry subsystem is off — the gauges themselves are registered
    (NaN) by ``hvd.init()``'s :func:`..telemetry.exporter.
    bind_process_gauges` so they always appear on /metrics."""
    from . import instrument

    if instrument.get_recorder() is None and registry is None:
        return
    reg = registry if registry is not None else default_registry()
    n = max(1, int(num_shards))
    if param_bytes is None and params is not None:
        param_bytes = tree_bytes(params)
        if zero_stage == "params":
            param_bytes //= n
    if optimizer_state_bytes is None and opt_state is not None:
        optimizer_state_bytes = tree_bytes(opt_state)
        if zero_stage in ("states", "params"):
            optimizer_state_bytes //= n
    if param_bytes is not None:
        reg.gauge("hvdt_param_bytes",
                  _MEMORY_GAUGE_DOCS["hvdt_param_bytes"]).set(
                      float(param_bytes))
    if optimizer_state_bytes is not None:
        reg.gauge("hvdt_optimizer_state_bytes",
                  _MEMORY_GAUGE_DOCS["hvdt_optimizer_state_bytes"]).set(
                      float(optimizer_state_bytes))


# ---------------------------------------------------------------------------
# Predicted-vs-observed perf attribution (the runtime mirror of the CI
# --perf ratchet): price the expected schedule fingerprint with the
# analytical cost model at init, then track observed step time against
# the prediction live.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerfExpectation:
    """The cost model's per-step prediction for this run.

    ``comm_exposed_s`` is the predicted NON-overlapped communication
    seconds (the number the CI perf baseline ratchets);
    ``wire_bytes_by_axis`` the predicted per-tier wire bytes per step;
    ``compute_s`` the device-peak compute seconds when the caller's
    flops and the device generation are both known (None on CPU sims —
    the deviation tracker then calibrates a compute anchor from the
    first observed steps instead)."""

    comm_exposed_s: float
    wire_bytes_by_axis: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    compute_s: Optional[float] = None
    label: str = ""
    source: str = ""


class DeviationTracker:
    """Maintains ``hvdt_perf_deviation_ratio``: observed EWMA step
    seconds over predicted step seconds.

    Predicted step seconds = predicted exposed comm + a compute anchor.
    The anchor is the expectation's device-peak compute time when
    known; otherwise it is **calibrated** from the median of the first
    ``calibration_steps`` observed steps minus the predicted comm (so
    the ratio reads 1.0 at calibration and any later slowdown —
    a straggling link, a throttled host, a policy regression — moves it
    off 1.0 in proportion).  The ratio is NaN until calibrated."""

    def __init__(self, expectation: PerfExpectation,
                 registry: Optional[MetricsRegistry] = None,
                 calibration_steps: int = 4, ewma_alpha: float = 0.3):
        reg = registry if registry is not None else default_registry()
        self.expectation = expectation
        self.calibration_steps = max(1, int(calibration_steps))
        self._alpha = float(ewma_alpha)
        self._lock = threading.Lock()
        self._warmup: list = []
        self._anchor: Optional[float] = expectation.compute_s
        self._ewma: Optional[float] = None
        self._gauge = reg.gauge(
            "hvdt_perf_deviation_ratio",
            "Observed EWMA step seconds / predicted step seconds "
            "(predicted exposed comm + compute anchor); the "
            "perf_deviation anomaly fires past "
            "HVDT_PERF_DEVIATION_RATIO")
        self._gauge.set(float("nan"))

    def observe(self, step_seconds: float) -> Optional[float]:
        """Feed one observed step; returns the current ratio (None
        while calibrating)."""
        s = float(step_seconds)
        with self._lock:
            if self._anchor is None:
                self._warmup.append(s)
                if len(self._warmup) < self.calibration_steps:
                    return None
                ordered = sorted(self._warmup)
                median = ordered[(len(ordered) - 1) // 2]
                self._anchor = max(
                    0.0, median - self.expectation.comm_exposed_s)
            self._ewma = s if self._ewma is None else (
                self._alpha * s + (1.0 - self._alpha) * self._ewma)
            predicted = self._anchor + self.expectation.comm_exposed_s
            if predicted <= 0:
                return None
            ratio = self._ewma / predicted
        self._gauge.set(ratio)
        return ratio

    def ratio(self) -> Optional[float]:
        with self._lock:
            if self._ewma is None or self._anchor is None:
                return None
            predicted = self._anchor + self.expectation.comm_exposed_s
            return self._ewma / predicted if predicted > 0 else None

    def observed_comm_s(self) -> Optional[float]:
        """Observed comm-exposed seconds: EWMA step time minus the
        compute anchor (what the prediction says compute costs)."""
        with self._lock:
            if self._ewma is None or self._anchor is None:
                return None
            return max(0.0, self._ewma - self._anchor)


_expect_lock = threading.Lock()
_expectation: Optional[PerfExpectation] = None
_deviation: Optional[DeviationTracker] = None


def get_expectation() -> Optional[PerfExpectation]:
    return _expectation


def get_deviation_tracker() -> Optional[DeviationTracker]:
    """The process-wide deviation tracker, or None when no expectation
    was published (the zero-overhead off path is one global read)."""
    return _deviation


def reset_expectation() -> None:
    """Drop the published expectation + tracker (test isolation; pairs
    with metrics.reset_default_registry)."""
    global _expectation, _deviation
    with _expect_lock:
        _expectation = None
        _deviation = None


def publish_expected_schedule_cost(
        fingerprint_path: Optional[str] = None,
        registry: Optional[MetricsRegistry] = None,
        device_kind: Optional[str] = None,
        flops_per_step: Optional[float] = None
        ) -> Optional[PerfExpectation]:
    """Price the expected schedule fingerprint with the fitted cost
    model on the ambient topology and publish the prediction:

    * ``hvdt_expected_step_comm_seconds`` — predicted exposed comm s;
    * ``hvdt_expected_wire_bytes{axis}`` — predicted per-tier wire
      bytes per step;
    * arms the process-wide :class:`DeviationTracker` so the StepTimer
      stream keeps ``hvdt_perf_deviation_ratio`` live.

    The fingerprint comes from ``fingerprint_path`` or the
    ``HVDT_EXPECTED_SCHEDULE`` knob (an in-process
    ``ScheduleFingerprint`` instance is also accepted via
    ``fingerprint_path``).  Returns None (and publishes nothing) when
    no fingerprint is available.  Raises on an unreadable file — use
    :func:`maybe_publish_expected_cost` from init paths."""
    from ..analysis import costmodel as _cm
    from ..analysis import schedule as _sched
    from ..analysis.topology import TopologySpec
    from ..common import config as _config

    global _expectation, _deviation
    fp = None
    source = ""
    if fingerprint_path is not None and not isinstance(
            fingerprint_path, str):
        fp = fingerprint_path            # an in-process fingerprint
        source = "in-process"
    else:
        path = (fingerprint_path
                or _config.get_str("HVDT_EXPECTED_SCHEDULE")).strip()
        if not path:
            return None
        fp = _sched.load_fingerprint(path)
        source = path
    topo = TopologySpec.from_env()
    cost = _cm.CostModel().evaluate(fp, topo)
    compute_s = None
    if device_kind and flops_per_step:
        peak, _ = peak_flops_for(device_kind)
        if peak:
            compute_s = float(flops_per_step) / peak
    exp = PerfExpectation(
        comm_exposed_s=float(cost.exposed_comm_s),
        wire_bytes_by_axis={k: int(v) for k, v in
                            sorted(cost.wire_bytes_by_axis.items())},
        compute_s=compute_s, label=fp.label or "step", source=source)
    reg = registry if registry is not None else default_registry()
    reg.gauge(
        "hvdt_expected_step_comm_seconds",
        "Cost-model-predicted exposed (non-overlapped) communication "
        "seconds per step for the expected schedule fingerprint on "
        "the ambient topology").set(exp.comm_exposed_s)
    wire_gauge = reg.gauge(
        "hvdt_expected_wire_bytes",
        "Cost-model-predicted wire bytes per step per transport tier "
        "for the expected schedule fingerprint")
    for axis in sorted(exp.wire_bytes_by_axis):
        wire_gauge.set(exp.wire_bytes_by_axis[axis], axis=axis)
    with _expect_lock:
        _expectation = exp
        _deviation = DeviationTracker(exp, registry=reg)
    return exp


def maybe_publish_expected_cost(**kwargs) -> Optional[PerfExpectation]:
    """The ``hvd.init()`` hook: publish the predicted-vs-observed feed
    iff telemetry is on and an expected schedule is configured.  Never
    raises — a bad fingerprint path must not sink init."""
    from . import instrument
    from ..common.logging_util import get_logger

    if not instrument.enabled():
        return None
    try:
        exp = publish_expected_schedule_cost(**kwargs)
    except Exception as e:
        get_logger(__name__).warning(
            "expected-schedule pricing failed (HVDT_EXPECTED_SCHEDULE): "
            "%s", e)
        return None
    if exp is not None:
        get_logger(__name__).info(
            "expected schedule %s priced: exposed comm %.1fus, wire %s",
            exp.label, exp.comm_exposed_s * 1e6,
            exp.wire_bytes_by_axis)
    return exp


def expected_vs_observed_doc(registry: Optional[MetricsRegistry] = None
                             ) -> Optional[Dict[str, object]]:
    """The compact predicted-vs-observed roll-up: predicted comm
    seconds, observed comm-exposed seconds, the deviation ratio, and
    per-kind anomaly counts.  None when no expectation was published."""
    exp = get_expectation()
    if exp is None:
        return None
    tracker = get_deviation_tracker()
    reg = registry if registry is not None else default_registry()
    anomaly_counts: Dict[str, float] = {}
    c = reg.get("hvdt_anomaly_total")
    if c is not None:
        for labels, v in c.items():
            kind = labels.get("kind", "")
            if kind:
                anomaly_counts[kind] = anomaly_counts.get(kind, 0) + v
    ratio = tracker.ratio() if tracker is not None else None
    observed = tracker.observed_comm_s() if tracker is not None else None
    return {
        "predicted_comm_s": round(exp.comm_exposed_s, 9),
        "predicted_wire_bytes_by_axis": dict(exp.wire_bytes_by_axis),
        "observed_comm_s": (round(observed, 6)
                            if observed is not None else None),
        "deviation_ratio": (round(ratio, 4)
                            if ratio is not None else None),
        "anomaly_counts": {k: int(v) for k, v in
                           sorted(anomaly_counts.items())},
        "fingerprint": exp.label,
        "source": exp.source,
    }
