"""Telemetry subsystem tests: registry encode round-trips, zero-overhead
disabled path, per-collective counters on eager and mesh runs, MFU /
goodput math, straggler detection (incl. an injected hang fault), the
/metrics HTTP exporter E2E, and driver-side snapshot aggregation."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import telemetry as tele
from horovod_tpu.telemetry import instrument as tinst
from horovod_tpu.telemetry import metrics as tmetrics

from conftest import jit_shard_map as shard_map

from jax.sharding import PartitionSpec as P


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """Telemetry state is process-wide (env-gated recorder + default
    registry); every test starts and ends from a clean slate."""
    monkeypatch.delenv("HVDT_TELEMETRY", raising=False)
    tmetrics.reset_default_registry()
    tinst.reset()
    yield
    tmetrics.reset_default_registry()
    tinst.reset()
    tele.stop_exporter()


@pytest.fixture()
def telemetry_on(monkeypatch):
    monkeypatch.setenv("HVDT_TELEMETRY", "1")
    monkeypatch.setenv("HVDT_METRICS_PORT", "0")
    tmetrics.reset_default_registry()
    tinst.reset()
    return tele.default_registry()


@pytest.fixture()
def hvd_telemetry(telemetry_on):
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_label_encode_round_trip(self):
        reg = tmetrics.MetricsRegistry()
        c = reg.counter("t_bytes_total", "help text")
        c.inc(100, op="allreduce", dtype="float32")
        c.inc(28, op="allreduce", dtype="float32")
        c.inc(5, op="allgather", dtype="uint8")
        assert c.value(op="allreduce", dtype="float32") == 128
        assert c.total() == 133
        text = reg.render()
        assert "# HELP t_bytes_total help text" in text
        assert "# TYPE t_bytes_total counter" in text
        assert ('t_bytes_total{dtype="float32",op="allreduce"} 128'
                in text)
        assert 't_bytes_total{dtype="uint8",op="allgather"} 5' in text

    def test_gauge_live_probe_and_summary_quantiles(self):
        reg = tmetrics.MetricsRegistry()
        g = reg.gauge("t_depth")
        g.set_function(lambda: 7)
        assert g.value() == 7
        s = reg.summary("t_lat_ms", window=100)
        for v in range(1, 101):
            s.observe(float(v))
        assert s.quantile(0.5) == 50.0
        assert s.count == 100
        assert s.mean() == pytest.approx(50.5)
        text = reg.render()
        assert 't_lat_ms{quantile="0.99"} 99' in text
        assert "t_lat_ms_count 100" in text
        assert "t_depth 7" in text

    def test_type_conflict_raises(self):
        reg = tmetrics.MetricsRegistry()
        reg.counter("t_metric")
        with pytest.raises(TypeError):
            reg.gauge("t_metric")

    def test_default_registry_is_process_wide_and_resettable(self):
        a = tele.default_registry()
        assert tele.default_registry() is a
        a.counter("t_x").inc()
        b = tmetrics.reset_default_registry()
        assert b is not a
        assert tele.default_registry() is b
        assert b.get("t_x") is None

    def test_serve_back_compat_reexport(self):
        # serve/metrics.py must hand out the exact telemetry classes so
        # pre-existing isinstance checks and registries keep working.
        from horovod_tpu.serve import metrics as serve_metrics

        assert serve_metrics.MetricsRegistry is tmetrics.MetricsRegistry
        assert serve_metrics.Counter is tmetrics.Counter
        assert serve_metrics.Gauge is tmetrics.Gauge
        assert serve_metrics.Summary is tmetrics.Summary


# ---------------------------------------------------------------------------
# Zero-overhead disabled path
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_recorder_is_none_when_disabled(self, monkeypatch):
        for raw in (None, "0", "off", "false", ""):
            if raw is None:
                monkeypatch.delenv("HVDT_TELEMETRY", raising=False)
            else:
                monkeypatch.setenv("HVDT_TELEMETRY", raw)
            assert tinst.get_recorder() is None

    def test_wrap_step_is_identity_when_disabled(self):
        def step(x):
            return x

        assert tinst.wrap_step(step) is step

    def test_donated_step_installs_no_wrapper_when_disabled(self):
        from horovod_tpu.step_pipeline import donated_step

        step = donated_step(lambda p, o: (p, o))
        assert type(step).__name__ != "_TimedStep"

    def test_recorder_toggles_with_env(self, monkeypatch):
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        assert tinst.get_recorder() is not None
        monkeypatch.setenv("HVDT_TELEMETRY", "0")
        assert tinst.get_recorder() is None

    def test_donated_step_wraps_and_forwards_when_enabled(self, telemetry_on):
        from horovod_tpu.step_pipeline import donated_step

        step = donated_step(lambda p, o: (p + o, o), donate_argnums=())
        assert type(step).__name__ == "_TimedStep"
        assert hasattr(step, "lower")   # jit surface forwards
        p, o = step(jnp.ones(4), jnp.ones(4))
        np.testing.assert_allclose(np.asarray(p), 2.0)
        disp = telemetry_on.get("hvdt_step_dispatch_seconds")
        assert disp is not None and disp.count >= 1


# ---------------------------------------------------------------------------
# Per-collective instrumentation
# ---------------------------------------------------------------------------

class TestCollectiveCounters:
    def test_eager_path_records_bytes_and_latency(self, hvd_telemetry):
        hvd = hvd_telemetry
        reg = tele.default_registry()
        out = hvd.allreduce(np.ones((16, 4), np.float32), name="tel.ar0")
        np.testing.assert_allclose(np.asarray(out), 1.0)
        hvd.allgather(np.ones((3,), np.float32), name="tel.ag0")
        c = reg.get("hvdt_collective_bytes_total")
        assert c.value(op="allreduce", dtype="float32", wire="float32",
                       path="eager") == 16 * 4 * 4
        assert c.value(op="allgather", dtype="float32", wire="float32",
                       path="eager") == 3 * 4
        n = reg.get("hvdt_collectives_total")
        assert n.value(op="allreduce", dtype="float32", wire="float32",
                       path="eager") == 1
        for name in ("hvdt_collective_negotiate_seconds",
                     "hvdt_collective_queue_seconds",
                     "hvdt_collective_execute_seconds"):
            assert reg.get(name).count >= 2, name

    def test_mesh_jit_path_records_buckets(self, telemetry_on, mesh8):
        from horovod_tpu.ops import device as dev

        def body(x):
            return dev.fused_allreduce(x, axis="dp")

        x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64)
        y = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                      out_specs=P())(x)
        np.testing.assert_allclose(
            np.asarray(y).reshape(64), np.asarray(x).sum(axis=0) / 8,
            rtol=1e-6)
        c = telemetry_on.get("hvdt_collective_bytes_total")
        # per-shard bucket: (1, 64) f32 = 256 B, recorded at trace time
        # (jit-path records carry the reduce-axis label)
        assert c.value(op="allreduce", dtype="float32", wire="float32",
                       path="jit", axis="dp", payload="leaves") == 64 * 4
        wb = telemetry_on.get("hvdt_wire_bytes_total")
        assert wb.value(axis="dp", wire="float32") == 64 * 4
        fill = telemetry_on.get("hvdt_fusion_fill_ratio")
        assert fill.count >= 1

    def test_quant_jit_path_records_int8_wire(self, telemetry_on, mesh8):
        from horovod_tpu.quant.collectives import quantized_allreduce_flat

        def body(x):
            return quantized_allreduce_flat(x, axis="dp")

        x = jnp.ones((2048,), jnp.float32)
        shard_map(body, mesh=mesh8, in_specs=(P("dp"),), out_specs=P())(x)
        c = telemetry_on.get("hvdt_collective_bytes_total")
        # per-shard 256 elems: 256 B payload + one f32 block scale
        # (jit-path records carry the reduce-axis label)
        assert c.value(op="allreduce", dtype="float32",
                       wire="int8_blockwise", path="jit",
                       axis="dp") == 256 + 4


# ---------------------------------------------------------------------------
# Step stats: MFU / goodput math
# ---------------------------------------------------------------------------

class TestStepStats:
    def test_mfu_and_throughput_math(self, telemetry_on):
        timer = tele.StepTimer(examples_per_step=100,
                               flops_per_step=2e12, peak_flops=1e13,
                               ewma_alpha=1.0)
        timer.observe(0.5)
        assert telemetry_on.get("hvdt_mfu").value() == pytest.approx(
            2e12 / (0.5 * 1e13))
        assert telemetry_on.get(
            "hvdt_examples_per_sec").value() == pytest.approx(200.0)
        assert telemetry_on.get("hvdt_steps_total").total() == 1
        snap = timer.snapshot()
        assert snap["steps"] == 1
        assert snap["mfu"] == pytest.approx(0.4)
        assert snap["step_time_p50_ms"] == pytest.approx(500.0)

    def test_mfu_unpublished_without_peak(self, telemetry_on):
        timer = tele.StepTimer(examples_per_step=8,
                               device_kind="cpu")   # unknown -> no peak
        timer.observe(0.1)
        assert timer.mfu() is None
        assert timer.snapshot()["mfu"] is None

    def test_mfu_gauge_not_registered_for_unknown_device(self,
                                                         telemetry_on):
        """Regression: an unknown device-peak table entry must not
        register (or render) a misleading hvdt_mfu=0 gauge."""
        timer = tele.StepTimer(examples_per_step=8, flops_per_step=1e9,
                               device_kind="riscv-sim-9000")
        timer.observe(0.01)
        assert telemetry_on.get("hvdt_mfu") is None
        assert "hvdt_mfu" not in telemetry_on.render()
        assert timer.mfu() is None

    def test_mfu_guard_zero_and_nonfinite_inputs(self, telemetry_on):
        """Regression: zero/absent/NaN caller flops or peak never divide
        by zero and simply leave the gauge unpublished."""
        for flops, peak in ((0, 1e12), (None, 1e12), (float("nan"), 1e12),
                            (1e9, 0), (1e9, float("nan")),
                            (1e9, float("inf")), ("garbage", 1e12)):
            tmetrics.reset_default_registry()
            reg = tele.default_registry()
            timer = tele.StepTimer(examples_per_step=8,
                                   flops_per_step=flops, peak_flops=peak,
                                   registry=reg)
            timer.observe(0.01)   # must not raise
            assert reg.get("hvdt_mfu") is None, (flops, peak)
            assert timer.mfu() is None
            assert timer.snapshot()["mfu"] is None

    def test_peak_table(self):
        flops, bw = tele.peak_flops_for("TPU v4")
        assert flops == 275e12 and bw == 1228e9
        assert tele.peak_flops_for("Intel Xeon") == (None, None)

    def test_step_context_manager(self, telemetry_on):
        timer = tele.StepTimer()
        with timer.step():
            time.sleep(0.01)
        assert timer.count == 1
        assert timer.mean_step_seconds() >= 0.01

    def test_goodput_ledger_math(self, telemetry_on):
        now = [100.0]
        led = tele.GoodputLedger(clock=lambda: now[0])
        now[0] = 110.0
        led.charge("recompile", 1.5)
        led.charge("restore", 1.0)
        led.charge("recompile", 0.5)
        assert led.lost_seconds("recompile") == pytest.approx(2.0)
        assert led.lost_seconds() == pytest.approx(3.0)
        assert led.fraction() == pytest.approx(0.7)
        c = telemetry_on.get("hvdt_goodput_lost_seconds_total")
        assert c.value(reason="recompile") == pytest.approx(2.0)
        # the gauge is a live probe of the ledger
        assert telemetry_on.get(
            "hvdt_goodput_fraction").value() == pytest.approx(0.7)
        # losses can never push the fraction below zero
        led.charge("fault_recovery", 100.0)
        assert led.fraction() == 0.0

    def test_goodput_ledger_backdated_start(self, telemetry_on):
        """already_elapsed puts a pre-construction compile into the
        elapsed denominator (bench charges the compile it measured
        before building the ledger)."""
        now = [50.0]
        led = tele.GoodputLedger(clock=lambda: now[0], already_elapsed=5.0)
        led.charge("recompile", 5.0)
        now[0] = 55.0
        assert led.elapsed_seconds() == pytest.approx(10.0)
        assert led.fraction() == pytest.approx(0.5)

    def test_resilience_bridge_gauges(self, monkeypatch, telemetry_on):
        from horovod_tpu.resilience import faults

        tele.bind_resilience_gauges()
        assert telemetry_on.get("hvdt_injected_faults").value() == 0
        # env-configured (not configure()): the live probe re-resolves
        # through get_injector(), which is keyed on the env plan string
        monkeypatch.setenv("HVDT_FAULT_PLAN", "exc@step=1")
        monkeypatch.delenv("HVDT_FAULT_JOURNAL", raising=False)
        inj = faults.get_injector()
        with pytest.raises(faults.InjectedFault):
            inj.fire("step", step=1)
        assert telemetry_on.get("hvdt_injected_faults").value() == 1


# ---------------------------------------------------------------------------
# Straggler detection
# ---------------------------------------------------------------------------

class TestStraggler:
    def test_flags_outlier_rank(self, telemetry_on):
        flagged = []
        mon = tele.StragglerMonitor(
            window=4, threshold=1.5,
            allgather_fn=lambda m: [0.01, 0.01, 0.05, 0.01],
            on_straggler=lambda r, s: flagged.append((r, s)))
        for _ in range(4):
            mon.observe(0.01)
        assert mon.straggler_rank_gauge.value() == 2
        assert mon.skew_gauge.value() == pytest.approx(5.0)
        assert flagged and flagged[0][0] == 2
        assert telemetry_on.get(
            "hvdt_straggler_flags_total").value(rank="2") == 1

    def test_no_straggler_below_threshold(self, telemetry_on):
        mon = tele.StragglerMonitor(
            window=2, threshold=2.0,
            allgather_fn=lambda m: [0.01, 0.011, 0.012])
        mon.observe(0.01)
        mon.observe(0.01)
        assert mon.straggler_rank_gauge.value() == -1
        # lower median baseline: max 0.012 / median 0.011
        assert mon.skew_gauge.value() == pytest.approx(0.012 / 0.011,
                                                       rel=1e-3)

    def test_detects_injected_hang_fault(self, monkeypatch, telemetry_on):
        """A hang@step fault from HVDT_FAULT_PLAN inflates this rank's
        measured step time; the skew check must name us the straggler
        against a healthy peer baseline."""
        monkeypatch.setenv("HVDT_FAULT_PLAN", "hang@step=5:secs=0.08")
        monkeypatch.delenv("HVDT_FAULT_JOURNAL", raising=False)
        from horovod_tpu.resilience import faults

        inj = faults.get_injector()
        assert inj is not None
        flagged = []
        mon = tele.StragglerMonitor(
            window=4, threshold=3.0,
            # two-rank cluster: rank 0 is us (measured), rank 1 healthy
            allgather_fn=lambda m: [m, 0.002],
            on_straggler=lambda r, s: flagged.append(r))
        for step in range(1, 9):
            t0 = time.perf_counter()
            inj.fire("step", step=step)     # fires once, at step 5
            mon.observe(time.perf_counter() - t0 + 0.002)
        # window 1 (steps 1-4): healthy, no flag; window 2 (5-8): the
        # 80 ms hang dominates the 4-step mean -> rank 0 flagged
        assert flagged == [0]
        assert mon.straggler_rank_gauge.value() == 0
        assert inj.counters.get("hang") == 1

    def test_window_disabled(self, telemetry_on):
        calls = []
        mon = tele.StragglerMonitor(window=0,
                                    allgather_fn=lambda m: calls.append(m))
        for _ in range(10):
            mon.observe(0.01)
        assert not calls

    def test_probe_failure_is_swallowed(self, telemetry_on):
        def boom(mean):
            raise ConnectionError("probe down")

        mon = tele.StragglerMonitor(window=1, allgather_fn=boom)
        mon.observe(0.01)    # must not raise
        assert mon.straggler_rank_gauge.value() == -1


# ---------------------------------------------------------------------------
# /metrics exporter E2E + driver-side aggregation
# ---------------------------------------------------------------------------

def _scrape(port, route="/metrics"):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{route}", timeout=10) as r:
        return r.read().decode()


class TestExporter:
    def test_http_metrics_e2e(self, hvd_telemetry):
        """The acceptance-criterion scrape: during an instrumented run, a
        worker's /metrics returns Prometheus text with nonzero collective
        bytes, step-time percentiles, and the MFU gauge."""
        hvd = hvd_telemetry
        exp = tele.get_exporter()
        assert exp is not None, "hvd.init() must start the exporter"
        timer = tele.StepTimer(examples_per_step=8, flops_per_step=1e9,
                               peak_flops=1e12,
                               straggler=tele.StragglerMonitor(window=2))
        for _ in range(4):
            timer.observe(0.005)
        hvd.allreduce(np.ones((64,), np.float32), name="tel.e2e")
        text = _scrape(exp.port)
        assert "hvdt_collective_bytes_total{" in text
        bytes_lines = [ln for ln in text.splitlines()
                       if ln.startswith("hvdt_collective_bytes_total{")]
        assert any(float(ln.rsplit(" ", 1)[1]) > 0 for ln in bytes_lines)
        assert 'hvdt_step_time_seconds{quantile="0.5"}' in text
        assert "hvdt_mfu" in text
        assert "hvdt_straggler_rank" in text
        health = json.loads(_scrape(exp.port, "/healthz"))
        assert health["status"] == "ok"
        assert health["steps"] == 4
        with pytest.raises(urllib.error.HTTPError) as ei:
            _scrape(exp.port, "/nope")
        assert ei.value.code == 404

    def test_exporter_not_started_when_disabled(self):
        import horovod_tpu as hvd

        hvd.init()
        try:
            assert tele.get_exporter() is None
        finally:
            hvd.shutdown()

    def test_port_collision_falls_back_to_ephemeral(self, telemetry_on):
        a = tele.MetricsExporter(port=0)
        pa = a.start()
        b = tele.MetricsExporter(port=pa)
        pb = b.start()
        try:
            assert pb != pa and pb > 0
            assert "hvdt" in _scrape(pb) or _scrape(pb) is not None
        finally:
            a.stop()
            b.stop()

    def test_two_workers_same_env_port_both_scrapeable(self, monkeypatch,
                                                       telemetry_on):
        """The launch-contract collision path: two same-host workers read
        the same HVDT_METRICS_PORT (no port_offset plan); the second must
        fall back to an ephemeral port with a logged warning, and BOTH
        endpoints must scrape."""
        import logging
        import socket

        # pick a concrete free port, then hand it to both workers via env
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base_port = probe.getsockname()[1]
        probe.close()
        monkeypatch.setenv("HVDT_METRICS_PORT", str(base_port))
        a = tele.MetricsExporter(rank=0)
        b = tele.MetricsExporter(rank=1)
        # the hvdt logger root doesn't propagate (logging_util), so
        # caplog can't see it — attach a capturing handler directly
        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        exporter_log = logging.getLogger(
            tele.exporter.log.name if hasattr(tele, "exporter")
            else "horovod_tpu.telemetry.exporter")
        handler = _Capture(level=logging.WARNING)
        exporter_log.addHandler(handler)
        try:
            pa = a.start()
            pb = b.start()
            assert pa == base_port
            assert pb != pa and pb > 0
            reg = tele.default_registry()
            reg.counter("t_shared").inc()
            assert "t_shared" in _scrape(pa)
            assert "t_shared" in _scrape(pb)
            assert any("unavailable" in m for m in records), records
        finally:
            exporter_log.removeHandler(handler)
            a.stop()
            b.stop()

    def test_process_resource_gauges(self, telemetry_on):
        """RSS / open-fds / HBM gauges: live probes, guarded — on this
        container (Linux, CPU jax 0.4.37) RSS and fds are real numbers
        and memory_stats() returns None, which must render as nan, not
        raise."""
        tele.bind_process_gauges()
        reg = tele.default_registry()
        rss = reg.get("hvdt_process_rss_bytes").value()
        assert rss > 1024 * 1024     # a Python+JAX process is >1 MiB
        fds = reg.get("hvdt_process_open_fds").value()
        assert fds >= 3              # stdin/stdout/stderr at minimum
        hbm = reg.get("hvdt_hbm_bytes_in_use").value()
        assert hbm != hbm or hbm >= 0    # nan (CPU/old jax) or a real byte count
        text = reg.render()          # probes render without raising
        assert "hvdt_process_rss_bytes" in text
        assert "hvdt_process_open_fds" in text
        assert "hvdt_hbm_bytes_in_use" in text

    def test_snapshot_dict_rolls_up_headline_metrics(self, telemetry_on):
        rec = tinst.get_recorder()
        rec.record_collective("allreduce", "float32", "float32", 4096)
        timer = tele.StepTimer(examples_per_step=4)
        timer.observe(0.01)
        tele.GoodputLedger()
        snap = tele.snapshot_dict()
        assert snap["bytes_on_wire_total"] == 4096
        assert snap["collectives_total"] == 1
        assert snap["steps"] == 1
        assert snap["step_time_p50_ms"] == pytest.approx(10.0)
        assert snap["goodput_fraction"] == pytest.approx(1.0, abs=1e-3)

    def test_kv_publish_and_driver_aggregation(self, telemetry_on):
        class FakeKV:
            def __init__(self):
                self.lock = threading.Lock()
                self.store = {}

            def put(self, key, value):
                with self.lock:
                    self.store[key] = value

        kv = FakeKV()
        rec = tinst.get_recorder()
        rec.record_collective("allreduce", "float32", "float32", 512)
        exp = tele.MetricsExporter(port=0, rank=3, kv_client=kv,
                                   publish_interval_s=0)
        assert exp.publish_snapshot()
        snaps = tele.collect_driver_snapshots(kv)
        assert 3 in snaps
        assert snaps[3]["bytes_on_wire_total"] == 512
        assert "ts" in snaps[3]

    def test_driver_method_aggregates(self, telemetry_on):
        """ElasticDriver.telemetry_snapshots reads worker publishes out
        of the rendezvous KV store."""
        from horovod_tpu.runner.elastic.driver import ElasticDriver

        class FakeKV:
            def __init__(self):
                self.lock = threading.Lock()
                self.store = {"/telemetry/0": json.dumps(
                    {"mfu": 0.5, "steps": 10}).encode(),
                    "/telemetry/junk": b"not json"}

        driver = ElasticDriver.__new__(ElasticDriver)
        driver._kv = FakeKV()
        snaps = driver.telemetry_snapshots()
        assert snaps == {0: {"mfu": 0.5, "steps": 10}}
        driver._kv = None
        assert driver.telemetry_snapshots() == {}


# ---------------------------------------------------------------------------
# Timeline: flush on stop + double-record into phase histograms
# ---------------------------------------------------------------------------

class TestTimelineFlush:
    def test_stop_timeline_drains_and_closes_valid_json(self, tmp_path):
        from horovod_tpu import timeline as tl

        path = tmp_path / "tl.json"
        tl.start_timeline(str(path))
        t = tl.current()
        for i in range(200):
            name = f"tensor{i % 5}"
            t.start_activity(name, "NEGOTIATE_ALLREDUCE")
            t.end_activity(name, {"shape": [4]})
        tl.stop_timeline()
        assert tl.current() is None
        assert t._file.closed
        data = json.loads(path.read_text())   # valid, properly terminated
        assert len([r for r in data if r.get("ph") == "B"]) == 200
        assert len([r for r in data if r.get("ph") == "E"]) == 200
        # 5 tensor rows -> 5 process_name meta records
        assert len([r for r in data if r.get("ph") == "M"]) == 5

    def test_spans_double_record_into_histograms(self, tmp_path,
                                                 telemetry_on):
        from horovod_tpu import timeline as tl

        path = tmp_path / "tl2.json"
        tl.start_timeline(str(path))
        t = tl.current()
        for _ in range(16):
            t.start_activity("g", "EXEC_ALLREDUCE")
            t.end_activity("g")
        tl.stop_timeline()
        s = telemetry_on.get("hvdt_phase_EXEC_ALLREDUCE_seconds")
        assert s is not None and s.count == 16

    def test_no_histograms_when_disabled(self, tmp_path):
        from horovod_tpu import timeline as tl

        path = tmp_path / "tl3.json"
        tl.start_timeline(str(path))
        t = tl.current()
        t.start_activity("g", "EXEC_ALLREDUCE")
        t.end_activity("g")
        tl.stop_timeline()
        assert tele.default_registry().get(
            "hvdt_phase_EXEC_ALLREDUCE_seconds") is None


# ---------------------------------------------------------------------------
# Launcher knob plumbing
# ---------------------------------------------------------------------------

class TestLauncherFlags:
    def test_telemetry_flags_forward_to_env(self):
        import argparse

        from horovod_tpu.runner.config_parser import (add_knob_arguments,
                                                      env_from_args)

        p = argparse.ArgumentParser()
        add_knob_arguments(p)
        args = p.parse_args(["--telemetry", "--metrics-port", "9100",
                             "--straggler-window", "32"])
        env = env_from_args(args, {}, base_env={})
        assert env["HVDT_TELEMETRY"] == "1"
        assert env["HVDT_METRICS_PORT"] == "9100"
        assert env["HVDT_STRAGGLER_WINDOW"] == "32"

    def test_knob_defaults(self):
        from horovod_tpu.common import config

        assert config.get_bool("HVDT_TELEMETRY") is False
        assert config.get_int("HVDT_METRICS_PORT") == 9090
        assert config.get_int("HVDT_STRAGGLER_WINDOW") == 64
        assert config.get_float("HVDT_STRAGGLER_THRESHOLD") == 2.0
