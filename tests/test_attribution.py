"""Live perf attribution plane tests: bounded time-series history,
windowed anomaly detectors + JSONL event log, step-aligned cross-rank
aggregation, predicted-vs-observed deviation tracking (cost-model
pricing of the mesh-8 reference fingerprint), the /timeseries endpoint,
`hvdtrun top` rendering, the --report post-mortem, the metric-catalog
satellites, and the multiprocess hang-under-telemetry scenario."""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from horovod_tpu.telemetry import aggregate as tagg
from horovod_tpu.telemetry import anomaly as tanomaly
from horovod_tpu.telemetry import exporter as texp
from horovod_tpu.telemetry import history as thistory
from horovod_tpu.telemetry import instrument as tinst
from horovod_tpu.telemetry import metrics as tmetrics
from horovod_tpu.telemetry import step_stats as tstats
from horovod_tpu.telemetry import top as ttop

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_attribution(monkeypatch):
    """Attribution state is process-wide and env-gated; every test
    starts and ends from a clean slate."""
    for var in ("HVDT_TELEMETRY", "HVDT_HISTORY", "HVDT_HISTORY_WINDOW",
                "HVDT_HISTORY_SAMPLE_S", "HVDT_EVENT_LOG",
                "HVDT_EXPECTED_SCHEDULE", "HVDT_PERF_DEVIATION_RATIO",
                "HVDT_NUM_PODS", "HVDT_POD_SIZE", "HVDT_POD",
                "HVDT_RANK"):
        monkeypatch.delenv(var, raising=False)
    tmetrics.reset_default_registry()
    tinst.reset()
    thistory.reset()
    tanomaly.reset()
    tstats.reset_expectation()
    yield
    tmetrics.reset_default_registry()
    tinst.reset()
    thistory.reset()
    tanomaly.reset()
    tstats.reset_expectation()
    texp.stop_exporter()


def _fill(series_vals, history, name="step_time"):
    for i, v in enumerate(series_vals, start=1):
        history.record(name, i, v, wall_ts=1000.0 + i)


# ---------------------------------------------------------------------------
# History layer
# ---------------------------------------------------------------------------

class TestHistory:
    def test_series_ring_is_bounded_and_ordered(self):
        s = thistory.Series("t", window=4)
        for i in range(10):
            s.append(1000.0 + i, i, float(i))
        assert len(s) == 4
        assert s.values() == [6.0, 7.0, 8.0, 9.0]
        assert s.steps() == [6, 7, 8, 9]
        assert s.last() == (1009.0, 9, 9.0)

    def test_zero_overhead_when_unset(self, monkeypatch):
        monkeypatch.delenv("HVDT_HISTORY", raising=False)
        thistory.reset()
        assert thistory.get_history() is None
        # the StepTimer feed site is a no-op branch
        timer = tstats.StepTimer(examples_per_step=1)
        timer.observe(0.01)
        assert thistory.get_history() is None

    def test_get_history_env_gate_and_reset(self, monkeypatch):
        monkeypatch.setenv("HVDT_HISTORY", "1")
        thistory.reset()
        h = thistory.get_history()
        assert h is not None
        assert thistory.get_history() is h   # cached
        monkeypatch.delenv("HVDT_HISTORY")
        assert thistory.get_history() is None

    def test_observe_step_cadence_coalesces(self):
        clock = [100.0]
        h = thistory.MetricHistory(window=32, sample_s=1.0,
                                   registry=tmetrics.MetricsRegistry(),
                                   clock=lambda: clock[0])
        assert h.observe_step(1, 0.10) is True    # first always samples
        clock[0] += 0.3
        assert h.observe_step(2, 0.20) is False   # inside the cadence
        clock[0] += 0.8
        assert h.observe_step(3, 0.30) is True
        vals = h.series("step_time").values()
        # the second sample carries the MEAN of the coalesced steps
        assert vals == [0.10, pytest.approx(0.25)]

    def test_sample_records_gauges_and_wire_axes(self):
        reg = tmetrics.MetricsRegistry()
        reg.gauge("hvdt_mfu").set(0.33)
        reg.gauge("hvdt_goodput_fraction").set(0.9)
        wire = reg.counter("hvdt_wire_bytes_total")
        wire.inc(100, axis="ici", wire="f32")
        wire.inc(40, axis="dcn", wire="int8")
        h = thistory.MetricHistory(window=8, sample_s=0, registry=reg)
        h.sample(5, step_seconds=0.05)
        assert h.series("mfu").values() == [0.33]
        assert h.series("goodput_fraction").values() == [0.9]
        assert h.series("wire_bytes.ici").values() == [100.0]
        assert h.series("wire_bytes.dcn").values() == [40.0]
        assert h.series("step_time").values() == [0.05]
        assert reg.counter("hvdt_history_samples_total").total() == 1

    def test_nan_gauges_are_not_sampled(self):
        reg = tmetrics.MetricsRegistry()
        reg.gauge("hvdt_mfu").set(float("nan"))
        h = thistory.MetricHistory(window=8, sample_s=0, registry=reg)
        h.sample(1, step_seconds=0.01)
        assert h.series("mfu") is None

    def test_to_dict_roundtrip_and_max_points(self):
        h = thistory.MetricHistory(window=16, sample_s=0,
                                   registry=tmetrics.MetricsRegistry())
        _fill([0.1 * i for i in range(1, 11)], h)
        doc = h.to_dict()
        assert len(doc["series"]["step_time"]) == 10
        capped = h.to_dict(max_points=3)
        assert len(capped["series"]["step_time"]) == 3
        assert capped["series"]["step_time"][-1][1] == 10  # newest kept
        h2 = thistory.MetricHistory.from_dict(doc)
        assert h2.series("step_time").values() == \
            h.series("step_time").values()

    def test_step_timer_feeds_history(self, monkeypatch):
        monkeypatch.setenv("HVDT_HISTORY", "1")
        monkeypatch.setenv("HVDT_HISTORY_SAMPLE_S", "0")
        thistory.reset()
        timer = tstats.StepTimer(examples_per_step=2)
        for _ in range(5):
            timer.observe(0.02)
        h = thistory.get_history()
        assert len(h.series("step_time")) == 5
        assert h.series("step_time").steps()[-1] == 5


# ---------------------------------------------------------------------------
# Detectors
# ---------------------------------------------------------------------------

class TestDetectors:
    def test_level_shift_fires_on_shift(self):
        vals = [1.0] * 8 + [3.0] * 8
        hit = tanomaly.level_shift(vals, window=8, factor=1.5)
        assert hit is not None
        assert hit["ratio"] == pytest.approx(3.0)

    def test_level_shift_ignores_noise_spike(self):
        # one 10x spike inside an otherwise flat window moves the
        # median by at most one rank — no firing
        vals = [1.0] * 8 + [1.0, 1.0, 10.0, 1.0, 1.0, 1.0, 1.0, 1.0]
        assert tanomaly.level_shift(vals, window=8, factor=1.5) is None

    def test_level_shift_needs_two_windows(self):
        assert tanomaly.level_shift([5.0] * 15, window=8) is None

    def test_level_drop_goodput(self):
        vals = [0.95] * 8 + [0.5] * 8
        hit = tanomaly.level_drop(vals, window=8, fraction=0.25)
        assert hit is not None and hit["ratio"] < 0.6
        assert tanomaly.level_drop([0.95] * 8 + [0.9] * 8,
                                   window=8, fraction=0.25) is None

    def test_threshold_cross(self):
        assert tanomaly.threshold_cross([1.0, 2.5], 2.0)["value"] == 2.5
        assert tanomaly.threshold_cross([1.0, 1.9], 2.0) is None
        assert tanomaly.threshold_cross([], 2.0) is None

    def test_rate_shift_both_directions(self):
        # cumulative counter: 100 B/step then 300 B/step
        pts = [(0.0, i, 100.0 * i) for i in range(1, 10)]
        pts += [(0.0, i, pts[8][2] + 300.0 * (i - 9))
                for i in range(10, 19)]
        up = tanomaly.rate_shift(pts, window=8, factor=1.5)
        assert up is not None and up["ratio"] == pytest.approx(3.0)
        down = tanomaly.rate_shift(
            [(0.0, i, 300.0 * min(i, 9) + 100.0 * max(0, i - 9))
             for i in range(1, 19)], window=8, factor=1.5)
        assert down is not None and down["ratio"] < 1.0


# ---------------------------------------------------------------------------
# Event log
# ---------------------------------------------------------------------------

class TestEventLog:
    def test_gate_none_when_unset(self, monkeypatch):
        monkeypatch.delenv("HVDT_EVENT_LOG", raising=False)
        tanomaly.reset()
        assert tanomaly.get_event_log() is None

    def test_emit_and_read(self, tmp_path, monkeypatch):
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("HVDT_EVENT_LOG", path)
        tanomaly.reset()
        log = tanomaly.get_event_log()
        assert log is not None and log.path == path
        doc = log.emit({"kind": "step_time_shift", "step": 7, "rank": 1})
        assert doc["v"] == tanomaly.EVENT_VERSION and doc["ts"] > 0
        log.emit({"kind": "perf_deviation", "step": 9})
        with open(path, "a") as fh:
            fh.write("{torn json\n")   # crash-torn tail line
        events = tanomaly.read_event_log(path)
        assert [e["kind"] for e in events] == ["step_time_shift",
                                               "perf_deviation"]

    def test_read_missing_file(self):
        assert tanomaly.read_event_log("/nonexistent/events.jsonl") == []


# ---------------------------------------------------------------------------
# Worker-side monitor
# ---------------------------------------------------------------------------

class TestAnomalyMonitor:
    def _history(self, reg):
        return thistory.MetricHistory(window=64, sample_s=0, registry=reg)

    def test_step_time_shift_fires_once_and_rearms(self, tmp_path):
        reg = tmetrics.MetricsRegistry()
        log = tanomaly.EventLog(str(tmp_path / "e.jsonl"))
        mon = tanomaly.AnomalyMonitor(window=4, registry=reg,
                                      event_log=log, rank=3, pod="podX")
        h = self._history(reg)
        _fill([0.1] * 4 + [0.5] * 4, h)
        events = mon.check(h, 8)
        assert [e["kind"] for e in events] == ["step_time_shift"]
        assert events[0]["rank"] == 3 and events[0]["pod"] == "podX"
        # still shifted: latched, no second event
        _fill([0.5], h)
        assert mon.check(h, 9) == []
        # recovery re-arms, a second shift fires again
        _fill([0.5] * 8, h)
        assert mon.check(h, 17) == []
        _fill([2.0] * 4, h)
        assert [e["kind"] for e in mon.check(h, 21)] == \
            ["step_time_shift"]
        assert reg.counter("hvdt_anomaly_total").value(
            kind="step_time_shift") == 2

    def test_perf_deviation_threshold(self):
        reg = tmetrics.MetricsRegistry()
        mon = tanomaly.AnomalyMonitor(registry=reg,
                                      deviation_threshold=2.0)
        h = self._history(reg)
        h.record("perf_deviation_ratio", 5, 1.2)
        assert mon.check(h, 5) == []
        h.record("perf_deviation_ratio", 6, 3.1)
        events = mon.check(h, 6)
        assert [e["kind"] for e in events] == ["perf_deviation"]
        assert events[0]["value"] == pytest.approx(3.1)

    def test_wire_drift_names_axis(self):
        reg = tmetrics.MetricsRegistry()
        mon = tanomaly.AnomalyMonitor(window=4, registry=reg)
        h = self._history(reg)
        total = 0.0
        for i in range(1, 14):
            total += 100.0 if i <= 8 else 400.0
            h.record("wire_bytes.dcn", i, total)
        events = mon.check(h, 13)
        assert [e["kind"] for e in events] == ["wire_drift"]
        assert events[0]["axis"] == "dcn"

    def test_goodput_drop_and_mfu_regression(self):
        reg = tmetrics.MetricsRegistry()
        mon = tanomaly.AnomalyMonitor(window=4, registry=reg)
        h = self._history(reg)
        _fill([0.9] * 4 + [0.4] * 4, h, name="goodput_fraction")
        _fill([0.33] * 4 + [0.1] * 4, h, name="mfu")
        kinds = sorted(e["kind"] for e in mon.check(h, 8))
        assert kinds == ["goodput_drop", "mfu_regression"]

    def test_detection_rides_sampling(self, monkeypatch, tmp_path):
        """The full worker path: StepTimer -> history sample -> monitor
        -> event log, no manual plumbing."""
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("HVDT_HISTORY", "1")
        monkeypatch.setenv("HVDT_HISTORY_SAMPLE_S", "0")
        monkeypatch.setenv("HVDT_EVENT_LOG", path)
        thistory.reset()
        tanomaly.reset()
        timer = tstats.StepTimer()
        for _ in range(8):
            timer.observe(0.01)
        for _ in range(8):
            timer.observe(0.08)
        events = tanomaly.read_event_log(path)
        assert any(e["kind"] == "step_time_shift" for e in events)
        assert len([e for e in events
                    if e["kind"] == "step_time_shift"]) == 1


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

def _snap(pod, ms_values, step=None, dev=None, goodput=None):
    pts = [[1000.0 + i, i, ms / 1e3]
           for i, ms in enumerate(ms_values, start=1)]
    doc = {"step": step if step is not None else len(ms_values),
           "wall_ts": 1000.0 + len(ms_values), "pod": pod,
           "timeseries": {"series": {"step_time": pts}}}
    if dev is not None:
        doc["perf_deviation_ratio"] = dev
    if goodput is not None:
        doc["goodput_fraction"] = goodput
        doc["timeseries"]["series"]["goodput_fraction"] = [
            [p[0], p[1], goodput] for p in pts]
    return doc


class TestAggregate:
    def test_unaligned_ranks_skipped_and_counted(self):
        reg = tmetrics.MetricsRegistry()
        snaps = {0: _snap("podA", [50] * 4),
                 1: {"steps": 9, "step_time_p50_ms": 55.0},   # old schema
                 2: {}}
        aligned, unaligned = tagg.aligned_snapshots(snaps, registry=reg)
        assert sorted(aligned) == [0]
        assert unaligned == [1, 2]
        assert reg.counter("hvdt_snapshot_unaligned_total").total() == 2

    def test_step_join(self):
        snaps = {0: _snap("podA", [50, 51, 52]),
                 1: _snap("podB", [60, 61])}
        joined = tagg.step_join(snaps)
        assert joined[1] == {0: 0.050, 1: 0.060}
        assert joined[3] == {0: 0.052}

    def test_recent_step_means_with_scalar_fallback(self):
        snaps = {0: _snap("podA", [50] * 8),
                 1: {"step_time_p50_ms": 80.0}}
        means = tagg.recent_step_means(snaps)
        assert means[0] == pytest.approx(0.050)
        assert means[1] == pytest.approx(0.080)

    def test_rollup(self):
        snaps = {
            0: _snap("podA", [50] * 8, goodput=0.95),
            1: _snap("podA", [52] * 8, goodput=0.97),
            2: _snap("podB", [200] * 8, goodput=0.5),
            3: {"steps": 3},   # old schema rides along
        }
        for rank in (0, 1, 2):
            snaps[rank]["timeseries"]["series"]["wire_bytes.dcn"] = [
                [1000.0, 8, 1000.0 * (rank + 1)]]
        roll = tagg.rollup(snaps, registry=tmetrics.MetricsRegistry())
        assert roll["ranks"] == [0, 1, 2, 3]
        assert roll["unaligned_ranks"] == [3]
        assert roll["aligned_steps"] == [1, 8]
        assert roll["per_pod"]["podB"]["step_time_p50_ms"] == \
            pytest.approx(200.0)
        assert roll["cluster"]["worst_pod"] == "podB"
        assert roll["cluster"]["wire_bytes_by_axis"]["dcn"] == 6000
        assert roll["cluster"]["goodput_fraction_mean"] == \
            pytest.approx((0.95 + 0.97 + 0.5) / 3, abs=1e-3)
        series = roll["cluster"]["step_time_series"]
        assert series[8]["ranks"] == 3
        assert series[8]["p99_ms"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# Predicted vs observed
# ---------------------------------------------------------------------------

class TestDeviation:
    def test_tracker_calibrates_then_tracks(self):
        reg = tmetrics.MetricsRegistry()
        exp = tstats.PerfExpectation(comm_exposed_s=0.01)
        tr = tstats.DeviationTracker(exp, registry=reg,
                                     calibration_steps=4)
        for _ in range(3):
            assert tr.observe(0.05) is None      # still calibrating
        r = tr.observe(0.05)
        assert r == pytest.approx(1.0, abs=0.01)
        for _ in range(30):
            r = tr.observe(0.15)                 # 3x slowdown
        assert r == pytest.approx(3.0, abs=0.1)
        assert reg.gauge("hvdt_perf_deviation_ratio").value() == \
            pytest.approx(r)
        # observed comm-exposed = ewma - anchor
        assert tr.observed_comm_s() == pytest.approx(0.15 - 0.04,
                                                     abs=0.01)

    def test_tracker_with_known_compute_anchor(self):
        exp = tstats.PerfExpectation(comm_exposed_s=0.01, compute_s=0.04)
        tr = tstats.DeviationTracker(exp,
                                     registry=tmetrics.MetricsRegistry())
        assert tr.observe(0.05) == pytest.approx(1.0)   # no calibration

    def test_publish_requires_configured_fingerprint(self):
        assert tstats.publish_expected_schedule_cost() is None
        assert tstats.get_deviation_tracker() is None

    def test_maybe_publish_noop_when_telemetry_off(self, monkeypatch,
                                                   tmp_path):
        monkeypatch.setenv("HVDT_EXPECTED_SCHEDULE",
                           str(tmp_path / "missing.json"))
        assert tstats.maybe_publish_expected_cost() is None

    def test_maybe_publish_swallows_bad_path(self, monkeypatch):
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        monkeypatch.setenv("HVDT_EXPECTED_SCHEDULE", "/nonexistent.json")
        tinst.reset()
        assert tstats.maybe_publish_expected_cost() is None

    @pytest.fixture()
    def reference_fingerprint(self, tmp_path, monkeypatch):
        """The mesh-8 overlapped+hierarchical reference fingerprint,
        exported like `analysis --schedule` does."""
        monkeypatch.setenv("HVDT_OVERLAP", "on")
        monkeypatch.setenv("HVDT_TRANSPORT",
                           "ici:ring:f32:64M,dcn:ring:f32:64M")
        from horovod_tpu.analysis import schedule as sched
        from horovod_tpu.analysis.__main__ import _selfcheck_step
        from horovod_tpu.ops import overlap as ovl
        from horovod_tpu.transport import policy as tpolicy

        ovl.reset()
        tpolicy.reset()
        try:
            step, leaves, _ = _selfcheck_step()
            fp = sched.extract_schedule(step, *leaves,
                                        label="overlap-hier")
            path = str(tmp_path / "fp.json")
            fp.save(path)
            yield path
        finally:
            monkeypatch.delenv("HVDT_OVERLAP", raising=False)
            monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
            ovl.reset()
            tpolicy.reset()

    def test_deviation_gauge_e2e_on_reference_fingerprint(
            self, monkeypatch, reference_fingerprint):
        """Acceptance leg: hvdt_expected_step_comm_seconds is published
        from the checked-in calibration for the mesh-8 reference step,
        and hvdt_perf_deviation_ratio goes live off the StepTimer
        stream."""
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        monkeypatch.setenv("HVDT_EXPECTED_SCHEDULE",
                           reference_fingerprint)
        monkeypatch.setenv("HVDT_NUM_PODS", "2")
        monkeypatch.setenv("HVDT_POD_SIZE", "4")
        tinst.reset()
        exp = tstats.maybe_publish_expected_cost()
        assert exp is not None and exp.label == "overlap-hier"
        assert exp.comm_exposed_s > 0
        reg = tmetrics.default_registry()
        assert reg.get("hvdt_expected_step_comm_seconds").value() == \
            pytest.approx(exp.comm_exposed_s)
        wire = dict((labels["axis"], v) for labels, v in
                    reg.get("hvdt_expected_wire_bytes").items())
        assert set(wire) == {"ici", "dcn"}
        assert wire["ici"] > 0 and wire["dcn"] > 0
        rendered = reg.render()
        assert 'hvdt_expected_wire_bytes{axis="dcn"}' in rendered
        # live deviation off the StepTimer stream
        timer = tstats.StepTimer()
        for _ in range(8):
            timer.observe(0.02)
        ratio = reg.gauge("hvdt_perf_deviation_ratio").value()
        assert ratio == pytest.approx(1.0, abs=0.05)
        doc = tstats.expected_vs_observed_doc()
        # the doc rounds to 9 decimals — allow the half-quantum
        assert doc["predicted_comm_s"] == pytest.approx(
            exp.comm_exposed_s, abs=5e-10)
        assert doc["deviation_ratio"] == pytest.approx(ratio, abs=1e-3)
        assert doc["fingerprint"] == "overlap-hier"

    def test_expected_vs_observed_doc_none_without_expectation(self):
        assert tstats.expected_vs_observed_doc() is None


# ---------------------------------------------------------------------------
# Metrics satellites
# ---------------------------------------------------------------------------

class _SortSpy(tmetrics.Summary):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.sorts = 0

    def _sorted_window(self):
        self.sorts += 1
        return super()._sorted_window()


class TestMetricsSatellites:
    def test_summary_three_quantile_render_sorts_once(self):
        s = _SortSpy("t_lat")
        for v in range(100):
            s.observe(float(v))
        s.render()
        assert s.sorts == 1
        s.sorts = 0
        pct = s.percentiles()
        assert s.sorts == 1
        assert pct[0.5] == 49.0 and pct[0.99] == 98.0

    def test_summary_quantile_results_unchanged(self):
        s = tmetrics.Summary("t", window=100)
        for v in range(1, 101):
            s.observe(float(v))
        assert s.quantile(0.5) == 50.0
        assert s.percentiles()[0.95] == 95.0

    def test_gauge_labels_render_and_scalar_back_compat(self):
        reg = tmetrics.MetricsRegistry()
        g = reg.gauge("t_scalar")
        g.set(3.5)
        assert g.value() == 3.5
        assert "t_scalar 3.5" in reg.render()
        lg = reg.gauge("t_wire")
        lg.set(100, axis="ici")
        lg.set(40, axis="dcn")
        assert lg.value(axis="ici") == 100
        assert lg.value(axis="missing") != lg.value(axis="missing")  # NaN
        text = reg.render()
        assert 't_wire{axis="dcn"} 40' in text
        assert 't_wire{axis="ici"} 100' in text
        assert lg.items() == [({"axis": "dcn"}, 40.0),
                              ({"axis": "ici"}, 100.0)]

    def test_counter_items(self):
        c = tmetrics.Counter("t_total")
        c.inc(5, kind="a")
        c.inc(2, kind="b")
        assert c.items() == [({"kind": "a"}, 5.0), ({"kind": "b"}, 2.0)]

    def test_catalog_declares_wildcards(self):
        assert tmetrics.declared_metric("hvdt_step_time_seconds")
        assert tmetrics.declared_metric("hvdt_phase_EXEC_ALLREDUCE_seconds")
        assert tmetrics.declared_metric("serve_request_latency_ms_predict")
        assert not tmetrics.declared_metric("hvdt_made_up_total")

    def test_metric_drift_rule_fixtures(self):
        from horovod_tpu.analysis import lint

        bad = ('def f(reg):\n'
               '    reg.counter("hvdt_rogue_total", "doc")\n')
        findings = lint.lint_source(bad, "horovod_tpu/x.py")
        assert any(f.rule == "metric-drift" for f in findings)
        good = ('def f(reg):\n'
                '    reg.counter("hvdt_steps_total", "doc")\n'
                '    reg.gauge(name_var)\n'           # dynamic: skipped
                '    Counter(x.op for x in y)\n')     # collections.Counter
        findings = lint.lint_source(good, "horovod_tpu/x.py")
        assert not any(f.rule == "metric-drift" for f in findings)

    def test_repo_is_metric_drift_clean(self):
        from horovod_tpu.analysis import lint

        rule = [r for r in lint.RULES if r.name == "metric-drift"]
        findings = lint.lint_paths(lint.default_paths(REPO), root=REPO,
                                   rules=rule)
        assert findings == [], [f.format() for f in findings]

    def test_docs_metrics_md_is_fresh(self):
        from horovod_tpu.analysis.lint import check_metric_docs

        assert check_metric_docs(REPO) == []


# ---------------------------------------------------------------------------
# Exporter surface
# ---------------------------------------------------------------------------

class TestExporter:
    def test_snapshot_dict_schema_v2(self, monkeypatch):
        monkeypatch.setenv("HVDT_HISTORY", "1")
        monkeypatch.setenv("HVDT_HISTORY_SAMPLE_S", "0")
        thistory.reset()
        timer = tstats.StepTimer()
        for _ in range(3):
            timer.observe(0.01)
        snap = texp.snapshot_dict()
        assert snap["step"] == 3
        assert snap["wall_ts"] > 0
        assert len(snap["timeseries"]["series"]["step_time"]) == 3

    def test_snapshot_dict_without_history_still_v2(self):
        timer = tstats.StepTimer()
        timer.observe(0.01)
        snap = texp.snapshot_dict()
        assert snap["step"] == 1
        assert "timeseries" not in snap

    def test_timeseries_endpoint_e2e(self, monkeypatch):
        monkeypatch.setenv("HVDT_HISTORY", "1")
        monkeypatch.setenv("HVDT_HISTORY_SAMPLE_S", "0")
        monkeypatch.setenv("HVDT_POD", "podZ")
        thistory.reset()
        timer = tstats.StepTimer()
        for _ in range(4):
            timer.observe(0.03)
        exporter = texp.MetricsExporter(port=0, rank=7)
        port = exporter.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/timeseries",
                    timeout=5) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["rank"] == 7
            assert doc["pod"] == "podZ"
            assert doc["step"] == 4
            assert len(doc["series"]["step_time"]) == 4
        finally:
            exporter.stop()

    def test_timeseries_endpoint_404_when_disabled(self, monkeypatch):
        monkeypatch.delenv("HVDT_HISTORY", raising=False)
        thistory.reset()
        exporter = texp.MetricsExporter(port=0)
        port = exporter.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/timeseries", timeout=5)
            assert ei.value.code == 404
        finally:
            exporter.stop()


# ---------------------------------------------------------------------------
# hvdtrun top
# ---------------------------------------------------------------------------

class TestTop:
    def test_sparkline(self):
        assert ttop.sparkline([]) == ""
        flat = ttop.sparkline([1.0, 1.0, 1.0])
        assert len(flat) == 3 and len(set(flat)) == 1
        ramp = ttop.sparkline([0.0, 1.0, 2.0, 3.0])
        assert ramp[0] == "▁" and ramp[-1] == "█"
        assert len(ttop.sparkline(list(range(100)), width=24)) == 24

    def test_render_frame(self):
        docs = {
            "h0:9090": {"rank": 0, "pod": "podA", "step": 12,
                        "series": {"step_time": [[0, i, 0.05]
                                                 for i in range(1, 13)],
                                   "goodput_fraction": [[0, 12, 0.98]]}},
            "h1:9090": {"rank": 1, "pod": "podB", "step": 12,
                        "series": {"step_time": [[0, i, 0.25]
                                                 for i in range(1, 13)],
                                   "perf_deviation_ratio": [[0, 12,
                                                             3.1]]}},
            "h2:9090": None,
        }
        events = [{"kind": "perf_deviation", "step": 11, "rank": 1,
                   "pod": "podB", "message": "observed step time ..."}]
        frame = ttop.render_frame(docs, events)
        assert "2/3 ranks" in frame
        assert "podA" in frame and "podB" in frame
        assert "worst pod: podB" in frame
        assert "goodput 0.98" in frame
        assert "3.10" in frame
        assert "unreachable" in frame
        assert "perf_deviation rank=1 pod=podB" in frame

    def test_fetch_and_once_against_live_exporter(self, monkeypatch,
                                                  capsys):
        monkeypatch.setenv("HVDT_HISTORY", "1")
        monkeypatch.setenv("HVDT_HISTORY_SAMPLE_S", "0")
        thistory.reset()
        timer = tstats.StepTimer()
        for _ in range(3):
            timer.observe(0.02)
        exporter = texp.MetricsExporter(port=0, rank=2)
        port = exporter.start()
        try:
            doc = ttop.fetch_timeseries(f"127.0.0.1:{port}")
            assert doc is not None and doc["rank"] == 2
            rc = ttop.main(["--endpoints", f"127.0.0.1:{port}",
                            "--once"])
            assert rc == 0
            out = capsys.readouterr().out
            assert "hvdt top" in out and "1/1 ranks" in out
        finally:
            exporter.stop()

    def test_fetch_unreachable(self):
        assert ttop.fetch_timeseries("127.0.0.1:9") is None


# ---------------------------------------------------------------------------
# Post-mortem report
# ---------------------------------------------------------------------------

class TestReport:
    def _log(self, tmp_path):
        log = tanomaly.EventLog(str(tmp_path / "events.jsonl"))
        log.emit({"kind": "step_time_shift", "scope": "rank", "step": 40,
                  "rank": 1, "pod": "podB", "ratio": 4.2,
                  "message": "step time level shift", "ts": 1000.0})
        log.emit({"kind": "perf_deviation", "scope": "cluster",
                  "step": 44, "rank": 1, "pod": "podB", "ratio": 3.0,
                  "message": "deviates from prediction", "ts": 1004.0})
        return log.path

    def test_render_report_from_event_log(self, tmp_path):
        from horovod_tpu.analysis.report import render_report

        md = render_report(self._log(tmp_path))
        assert "# Run post-mortem report" in md
        assert "## Anomaly summary" in md
        assert "| step_time_shift | 1 | 40 | 40 |" in md
        assert "| perf_deviation | 1 | 44 | 44 |" in md
        assert "rank 1, pod podB" in md

    def test_render_report_directory_with_artifacts(self, tmp_path):
        from horovod_tpu.analysis.report import render_report

        self._log(tmp_path)
        (tmp_path / "desync_report_rank0.json").write_text(json.dumps(
            {"first_divergent_seq": 6, "missing_ranks": [1]}))
        (tmp_path / "trace_merged.json").write_text("{}")
        md = render_report(str(tmp_path))
        assert "## Forensics artifacts" in md
        assert "first divergent seq 6" in md
        assert "trace_merged.json" in md

    def test_render_report_empty(self, tmp_path):
        from horovod_tpu.analysis.report import render_report

        md = render_report(str(tmp_path))
        assert "No anomaly events found" in md

    def test_cli_report_mode(self, tmp_path, capsys):
        from horovod_tpu.analysis import main as analysis_main

        rc = analysis_main(["--report", self._log(tmp_path)])
        assert rc == 0
        assert "# Run post-mortem report" in capsys.readouterr().out

    def test_cli_report_out_file(self, tmp_path):
        from horovod_tpu.analysis import main as analysis_main

        out = str(tmp_path / "report.md")
        rc = analysis_main(["--report", self._log(tmp_path),
                            "--report-out", out])
        assert rc == 0
        assert "## Anomaly summary" in open(out).read()


# ---------------------------------------------------------------------------
# Cluster rules
# ---------------------------------------------------------------------------

class TestClusterMonitor:
    def test_pod_wide_shift_is_one_event(self, tmp_path):
        log = tanomaly.EventLog(str(tmp_path / "cluster.jsonl"))
        mon = tanomaly.ClusterAnomalyMonitor(
            registry=tmetrics.MetricsRegistry(), event_log=log,
            shift_factor=2.0)
        snaps = {0: _snap("podA", [50] * 8), 1: _snap("podA", [52] * 8),
                 2: _snap("podB", [200] * 8),
                 3: _snap("podB", [210] * 8)}
        events = mon.observe(snaps)
        pod_events = [e for e in events if e["kind"] == "step_time_shift"]
        assert len(pod_events) == 1           # ONE event, not pod_size
        assert pod_events[0]["scope"] == "pod"
        assert pod_events[0]["pod"] == "podB"
        assert pod_events[0]["ranks"] == [2, 3]
        # latched across rounds
        assert mon.observe(snaps) == []
        logged = tanomaly.read_event_log(log.path)
        assert len(logged) == 1

    def test_single_rank_shift_names_rank(self):
        mon = tanomaly.ClusterAnomalyMonitor(
            registry=tmetrics.MetricsRegistry(), shift_factor=2.0)
        snaps = {0: _snap("podA", [50] * 8), 1: _snap("podA", [51] * 8),
                 2: _snap("podB", [49] * 8),
                 3: _snap("podB", [300] * 8)}
        events = mon.observe(snaps)
        assert len(events) == 1
        assert events[0]["scope"] == "rank"
        assert events[0]["rank"] == 3 and events[0]["pod"] == "podB"

    def test_perf_deviation_cluster_event(self, tmp_path):
        log = tanomaly.EventLog(str(tmp_path / "cluster.jsonl"))
        mon = tanomaly.ClusterAnomalyMonitor(
            registry=tmetrics.MetricsRegistry(), event_log=log,
            deviation_threshold=2.0)
        snaps = {0: _snap("podA", [50] * 8, dev=1.1),
                 1: _snap("podB", [50] * 8, dev=4.5)}
        events = mon.observe(snaps)
        dev = [e for e in events if e["kind"] == "perf_deviation"]
        assert len(dev) == 1
        assert dev[0]["scope"] == "cluster"
        assert dev[0]["rank"] == 1 and dev[0]["pod"] == "podB"
        assert mon.observe(snaps) == []       # latched
        # recovery re-arms
        snaps[1]["perf_deviation_ratio"] = 1.0
        assert mon.observe(snaps) == []
        snaps[1]["perf_deviation_ratio"] = 5.0
        assert [e["kind"] for e in mon.observe(snaps)] == \
            ["perf_deviation"]

    def test_old_schema_snapshots_tolerated(self):
        mon = tanomaly.ClusterAnomalyMonitor(
            registry=tmetrics.MetricsRegistry())
        assert mon.observe({0: {"steps": 4}, 1: {}}) == []


# ---------------------------------------------------------------------------
# Driver integration
# ---------------------------------------------------------------------------

class TestDriverRollup:
    def test_telemetry_rollup_over_kv(self):
        from horovod_tpu.runner.elastic.discovery import HostManager
        from horovod_tpu.runner.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.http_kv import RendezvousServer

        server = RendezvousServer()
        server.start()
        try:
            server.put_local("/telemetry/0",
                             json.dumps(_snap("podA", [50] * 4)).encode())
            server.put_local("/telemetry/1",
                             json.dumps({"steps": 2}).encode())
            hm = HostManager(lambda: [HostInfo("localhost", 2)])
            driver = ElasticDriver(hm, min_np=2, kv_server=server)
            roll = driver.telemetry_rollup()
            assert roll["unaligned_ranks"] == [1]
            assert roll["per_pod"]["podA"]["step_time_p50_ms"] == \
                pytest.approx(50.0)
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# Multiprocess acceptance scenario
# ---------------------------------------------------------------------------

def _write_synthetic_fingerprint(path):
    """A tiny two-collective (dcn, ici) fingerprint — enough for the
    cost model to price a nonzero exposed-comm prediction without
    tracing jax in the worker processes."""
    doc = {
        "version": 1, "label": "attr-scenario", "n_barriers": 0,
        "events": [
            {"index": 0, "op": "psum", "axes": ["dcn", "ici"],
             "dtype": "float32", "count": 1024, "nbytes": 4096,
             "context": [], "post_barrier": False,
             "barriers_before": 0},
            {"index": 1, "op": "psum", "axes": ["ici"],
             "dtype": "float32", "count": 256, "nbytes": 1024,
             "context": [], "post_barrier": False,
             "barriers_before": 0},
        ],
    }
    with open(path, "w") as fh:
        json.dump(doc, fh)


def test_multiprocess_hang_fires_cluster_attribution(tmp_path, spawn):
    """Acceptance scenario: two ranks (pods podA/podB) run a lockstep
    step loop under full attribution telemetry; a hang@step fault
    wedges rank 1 inside one timed step.  The driver side (this
    process) aggregates the KV snapshots and must emit EXACTLY ONE
    cluster-level perf_deviation event and one step-time anomaly, both
    naming rank 1 / pod podB, into the JSONL event log; rank 1's own
    worker-side detector must fire perf_deviation too."""
    from horovod_tpu.runner.http_kv import RendezvousServer

    fp_path = str(tmp_path / "fp.json")
    _write_synthetic_fingerprint(fp_path)
    server = RendezvousServer()
    port = server.start()
    procs, outs = [], []
    try:
        for rank, pod in ((0, "podA"), (1, "podB")):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get(
                    "PYTHONPATH", ""),
                "HVDT_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVDT_RENDEZVOUS_PORT": str(port),
                "HVDT_SECRET": server.secret.hex(),
                "HVDT_RANK": str(rank),
                "HVDT_SIZE": "2",
                "HVDT_POD": pod,
                "HVDT_NUM_PODS": "2",
                "HVDT_POD_SIZE": "1",
                "HVDT_TELEMETRY": "1",
                "HVDT_HISTORY": "1",
                "HVDT_HISTORY_SAMPLE_S": "0",
                "HVDT_EVENT_LOG": str(tmp_path / f"events_r{rank}.jsonl"),
                "HVDT_EXPECTED_SCHEDULE": fp_path,
                "HVDT_FAULT_PLAN": "hang@step=8:rank=1:secs=2",
                "ATTR_TEST_STEPS": "14",
                "ATTR_TEST_STEP_S": "0.04",
            })
            env.pop("HVDT_FAULT_JOURNAL", None)
            procs.append(spawn(
                [sys.executable,
                 os.path.join(REPO, "tests", "data",
                              "attribution_main.py")],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT))
        deadline = time.monotonic() + 120
        for p in procs:
            out, _ = p.communicate(
                timeout=max(5, deadline - time.monotonic()))
            outs.append(out.decode())
        assert procs[0].returncode == 0, outs[0][-3000:]
        assert procs[1].returncode == 0, outs[1][-3000:]

        # -- the driver side: aggregate + cluster rules ----------------
        from horovod_tpu.telemetry.exporter import \
            collect_driver_snapshots

        snaps = collect_driver_snapshots(server)
        assert sorted(snaps) == [0, 1]
        assert snaps[1]["pod"] == "podB"
        assert snaps[1]["perf_deviation_ratio"] > 2.0, snaps[1]
        # rank 0 never crosses the firing threshold (its ratio is its
        # own load noise against its own calibration — keep the bound
        # at the threshold, not at 1.0, for loaded 1-core CI boxes)
        assert (snaps[0]["perf_deviation_ratio"] or 1.0) < 2.0

        driver_log = tanomaly.EventLog(str(tmp_path / "driver.jsonl"))
        mon = tanomaly.ClusterAnomalyMonitor(
            registry=tmetrics.MetricsRegistry(), event_log=driver_log)
        events = mon.observe(snaps)
        dev = [e for e in events if e["kind"] == "perf_deviation"]
        assert len(dev) == 1, events
        assert dev[0]["scope"] == "cluster"
        assert dev[0]["rank"] == 1 and dev[0]["pod"] == "podB"
        shifts = [e for e in events if e["kind"] == "step_time_shift"]
        assert len(shifts) == 1, events
        assert shifts[0]["rank"] == 1 and shifts[0]["pod"] == "podB"
        # latched: a second aggregation round emits nothing new
        assert mon.observe(snaps) == []
        logged = tanomaly.read_event_log(driver_log.path)
        assert len([e for e in logged
                    if e["kind"] == "perf_deviation"]) == 1

        # -- the worker side: rank 1's own detector fired --------------
        r1_events = tanomaly.read_event_log(
            str(tmp_path / "events_r1.jsonl"))
        assert any(e["kind"] == "perf_deviation" for e in r1_events), \
            (r1_events, outs[1][-2000:])
        r0_events = tanomaly.read_event_log(
            str(tmp_path / "events_r0.jsonl"))
        assert not any(e["kind"] == "perf_deviation"
                       for e in r0_events), r0_events

        # -- the surfaces render it ------------------------------------
        frame = ttop.render_frame(
            {"r0": {"rank": 0, "pod": "podA", "step": 14,
                    "series": (snaps[0].get("timeseries") or {}).get(
                        "series", {})},
             "r1": {"rank": 1, "pod": "podB", "step": 14,
                    "series": (snaps[1].get("timeseries") or {}).get(
                        "series", {})}},
            logged)
        assert "worst pod: podB" in frame
        assert "perf_deviation" in frame
        from horovod_tpu.analysis.report import render_report

        md = render_report(str(tmp_path))
        assert "perf_deviation" in md and "podB" in md
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("attribution scenario hung")
    finally:
        server.stop()
