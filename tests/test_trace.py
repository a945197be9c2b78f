"""Distributed tracing + collective flight recorder tests: zero-overhead
identity contracts, span recording with deterministic per-step trace
ids, Chrome-trace dump/merge validity, flight-recorder ring semantics on
the eager and jit paths, the cross-rank desync analyzer, the stall-abort
/ preemption dump triggers, the /flightrecorder exporter endpoint,
launcher flag plumbing — and the multiprocess hang-injection scenario
whose stall-abort emits a desync report naming the hung rank."""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import telemetry as tele
from horovod_tpu.telemetry import flight_recorder as frm
from horovod_tpu.telemetry import instrument as tinst
from horovod_tpu.telemetry import metrics as tmetrics
from horovod_tpu.telemetry import trace as ttrace

from conftest import jit_shard_map as shard_map

from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_forensics(monkeypatch):
    """Trace/flight state is process-wide and env-gated; every test
    starts and ends from a clean slate."""
    for var in ("HVDT_TELEMETRY", "HVDT_TRACE_DIR", "HVDT_FLIGHT_RECORDER",
                "HVDT_RANK", "HVDT_SIZE"):
        monkeypatch.delenv(var, raising=False)
    tmetrics.reset_default_registry()
    tinst.reset()
    ttrace.reset()
    frm.reset()
    yield
    tmetrics.reset_default_registry()
    tinst.reset()
    ttrace.reset()
    frm.reset()
    tele.stop_exporter()


@pytest.fixture()
def forensics_on(monkeypatch, tmp_path):
    """Tracing + flight recorder on, trace dir at tmp_path."""
    monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
    return tmp_path


# ---------------------------------------------------------------------------
# Zero-overhead disabled path
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_tracer_is_none_when_disabled(self, monkeypatch):
        for raw in (None, "", "0", "off", "none"):
            if raw is None:
                monkeypatch.delenv("HVDT_TRACE_DIR", raising=False)
            else:
                monkeypatch.setenv("HVDT_TRACE_DIR", raw)
            assert ttrace.get_tracer() is None

    def test_flight_recorder_is_none_when_disabled(self, monkeypatch):
        for raw in (None, "0", "off", "false", ""):
            if raw is None:
                monkeypatch.delenv("HVDT_FLIGHT_RECORDER", raising=False)
            else:
                monkeypatch.setenv("HVDT_FLIGHT_RECORDER", raw)
            assert frm.get_flight_recorder() is None

    def test_wrap_step_is_identity_with_all_flags_unset(self):
        def step(x):
            return x

        assert tinst.get_recorder() is None
        assert ttrace.get_tracer() is None
        assert tinst.wrap_step(step) is step

    def test_donated_step_installs_no_wrapper_when_disabled(self):
        from horovod_tpu.step_pipeline import donated_step

        step = donated_step(lambda p, o: (p, o))
        assert type(step).__name__ != "_TimedStep"

    def test_flush_is_noop_when_disabled(self):
        assert ttrace.flush() is None

    def test_emit_desync_report_is_noop_when_disabled(self):
        assert frm.emit_desync_report(stalled="x") is None


# ---------------------------------------------------------------------------
# Tracer: spans, step ids, bounds, dumps
# ---------------------------------------------------------------------------

class TestTracer:
    def test_records_spans_with_deterministic_step_ids(self, forensics_on):
        tr = ttrace.get_tracer()
        assert tr is not None
        tr.complete("EXEC_ALLREDUCE:g0", 0.002, args={"fused": 2})
        tr.step_span(0.01)
        tr.complete("EXEC_ALLREDUCE:g1", 0.003)
        evs = tr.events()
        assert evs[0]["args"]["trace_id"] == ttrace.step_trace_id(0)
        assert evs[1]["name"] == "train.step"
        # events after the step span carry the NEXT deterministic id
        assert evs[2]["args"]["trace_id"] == ttrace.step_trace_id(1)
        # two independent tracers derive identical ids for the same step
        assert ttrace.step_trace_id(7) == ttrace.step_trace_id(7)

    def test_buffer_is_bounded(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("HVDT_TRACE_BUFFER", "32")
        tr = ttrace.get_tracer()
        for i in range(100):
            tr.complete(f"s{i}", 0.001)
        assert len(tr.events()) == 32
        assert tr.events()[-1]["name"] == "s99"

    def test_dump_is_valid_chrome_trace(self, forensics_on):
        tr = ttrace.get_tracer()
        tr.complete("a", 0.001, cat="collective")
        tr.instant("mark", args={"k": "v"})
        doc = json.loads(json.dumps(tr.dump()))
        assert isinstance(doc["traceEvents"], list)
        x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
        assert x["name"] == "a" and x["dur"] >= 0 and "ts" in x
        assert x["pid"] == tr.rank
        i = [e for e in doc["traceEvents"] if e["ph"] == "i"][0]
        assert i["args"]["k"] == "v"

    def test_flush_writes_per_rank_file(self, forensics_on):
        tr = ttrace.get_tracer()
        tr.complete("a", 0.001)
        path = ttrace.flush(publish=False)
        assert path and os.path.exists(path)
        assert path.endswith("trace_rank0.json")
        assert json.load(open(path))["traceEvents"]

    def test_donated_step_traces_with_telemetry_off(self, forensics_on):
        from horovod_tpu.step_pipeline import donated_step

        assert tinst.get_recorder() is None
        step = donated_step(lambda p, o: (p + o, o), donate_argnums=())
        assert type(step).__name__ == "_TimedStep"
        assert hasattr(step, "lower")
        p, o = step(jnp.ones(4), jnp.ones(4))
        np.testing.assert_allclose(np.asarray(p), 2.0)
        tr = ttrace.get_tracer()
        assert tr.step == 1
        assert any(e["name"] == "train.step" for e in tr.events())


# ---------------------------------------------------------------------------
# Driver-side merge
# ---------------------------------------------------------------------------

class TestMerge:
    def _two_rank_dumps(self):
        a = ttrace.Tracer(rank=0, capacity=64)
        b = ttrace.Tracer(rank=1, capacity=64)
        a.complete("EXEC_ALLREDUCE:g", 0.002)
        a.step_span(0.01)
        b.complete("EXEC_ALLREDUCE:g", 0.004)
        b.step_span(0.012)
        return {0: a.dump(), 1: b.dump()}

    def test_merge_two_ranks_single_valid_trace(self):
        merged = ttrace.merge_dumps(self._two_rank_dumps())
        doc = json.loads(json.dumps(merged))   # valid JSON round-trip
        evs = doc["traceEvents"]
        data = [e for e in evs if e.get("ph") != "M"]
        assert len(data) == 4
        assert {e["pid"] for e in data} == {0, 1}
        names = {(e["ph"], e["name"], e["pid"]) for e in evs
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert ("M", "process_name", 0) in names
        assert ("M", "process_name", 1) in names
        # timestamps rebased to the earliest event
        assert min(e["ts"] for e in data) == 0.0
        assert doc["metadata"]["ranks"] == [0, 1]

    def test_write_merged_from_kv_server(self, tmp_path):
        import threading

        class FakeKV:
            lock = threading.Lock()

            def __init__(self, dumps):
                self.store = {
                    f"/trace/{r}": json.dumps(d).encode()
                    for r, d in dumps.items()}
                self.store["/trace/junk"] = b"not json"

        path = ttrace.write_merged(FakeKV(self._two_rank_dumps()),
                                   str(tmp_path))
        assert path and os.path.exists(path)
        doc = json.load(open(path))
        assert {e["pid"] for e in doc["traceEvents"]
                if e.get("ph") != "M"} == {0, 1}

    def test_driver_trace_dumps_method(self):
        import threading

        from horovod_tpu.runner.elastic.driver import ElasticDriver

        class FakeKV:
            lock = threading.Lock()
            store = {"/trace/2": json.dumps(
                {"traceEvents": [], "metadata": {"rank": 2}}).encode()}

        driver = ElasticDriver.__new__(ElasticDriver)
        driver._kv = FakeKV()
        assert 2 in driver.trace_dumps()
        driver._kv = None
        assert driver.trace_dumps() == {}
        assert driver.flight_recorder_events() == {}


# ---------------------------------------------------------------------------
# Flight recorder ring
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_begin_end_lifecycle_and_monotonic_seq(self, forensics_on):
        fr = frm.get_flight_recorder()
        s1 = fr.record_begin("allreduce", "g.0", "float32", (4, 4), 64)
        s2 = fr.record_begin("allgather", "g.1", "float32", (3,), 12)
        evs = fr.events()
        assert [e["seq"] for e in evs] == [s1, s2] == [1, 2]
        assert all(e["status"] == "inflight" for e in evs)
        assert all(e["end_ts"] is None for e in evs)
        fr.record_end(s1)
        fr.record_end(s2, status="error")
        evs = fr.events()
        assert evs[0]["status"] == "done" and evs[0]["end_ts"] is not None
        assert evs[1]["status"] == "error"
        assert evs[0]["shape"] == [4, 4] and evs[0]["nbytes"] == 64

    def test_ring_is_bounded_and_drops_oldest(self, monkeypatch):
        monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
        monkeypatch.setenv("HVDT_FLIGHT_RECORDER_EVENTS", "16")
        fr = frm.get_flight_recorder()
        for i in range(50):
            fr.record("allreduce", f"g{i}", "float32", (4,), 16)
        evs = fr.events()
        assert len(evs) == 16
        assert evs[0]["seq"] == 35 and evs[-1]["seq"] == 50
        # closing an evicted seq is a safe no-op
        fr.record_end(1)

    def test_eager_path_records_events(self, forensics_on):
        import horovod_tpu as hvd

        hvd.init()
        try:
            hvd.allreduce(np.ones((16, 4), np.float32), name="fr.ar0")
            hvd.allgather(np.ones((3,), np.float32), name="fr.ag0")
            evs = frm.get_flight_recorder().events()
            assert [e["name"] for e in evs] == ["fr.ar0", "fr.ag0"]
            assert [e["op"] for e in evs] == ["allreduce", "allgather"]
            assert all(e["status"] == "done" for e in evs)
            assert evs[0]["nbytes"] == 16 * 4 * 4
            assert evs[0]["path"] == "eager"
        finally:
            hvd.shutdown()

    def test_jit_fused_path_records_traced_buckets(self, forensics_on,
                                                   mesh8):
        from horovod_tpu.ops import device as dev

        def body(x):
            return dev.fused_allreduce(x, axis="dp")

        x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64)
        shard_map(body, mesh=mesh8, in_specs=(P("dp"),), out_specs=P())(x)
        evs = frm.get_flight_recorder().events()
        traced = [e for e in evs if e["path"] == "jit"]
        assert traced and traced[0]["status"] == "traced"
        assert traced[0]["op"] == "allreduce"
        assert traced[0]["nbytes"] == 64 * 4

    def test_quant_jit_path_records_int8_wire(self, forensics_on, mesh8):
        from horovod_tpu.quant.collectives import quantized_allreduce_flat

        def body(x):
            return quantized_allreduce_flat(x, axis="dp")

        x = jnp.ones((2048,), jnp.float32)
        shard_map(body, mesh=mesh8, in_specs=(P("dp"),), out_specs=P())(x)
        evs = frm.get_flight_recorder().events()
        assert any(e["wire"] == "int8_blockwise" and e["path"] == "jit"
                   for e in evs)


# ---------------------------------------------------------------------------
# Desync analyzer
# ---------------------------------------------------------------------------

def _seq_events(n, start=1, **overrides):
    out = []
    for i in range(start, start + n):
        ev = {"seq": i, "op": "allreduce", "name": f"g{i}",
              "dtype": "float32", "shape": [1024], "nbytes": 4096,
              "status": "done"}
        ev.update(overrides)
        out.append(ev)
    return out


class TestDesyncAnalyzer:
    def test_names_first_divergent_seq_and_missing_rank(self):
        rep = frm.analyze_desync(
            {0: _seq_events(8), 1: _seq_events(5), 2: _seq_events(8)},
            expected_ranks=[0, 1, 2])
        assert rep["first_divergent_seq"] == 6
        assert rep["missing_ranks"] == [1]
        assert rep["per_rank_last_seq"] == {"0": 8, "1": 5, "2": 8}
        assert rep["divergent_event"]["name"] == "g6"

    def test_rank_with_no_events_is_missing_from_the_start(self):
        rep = frm.analyze_desync({0: _seq_events(4), 1: []},
                                 expected_ranks=[0, 1])
        assert rep["first_divergent_seq"] == 1
        assert rep["missing_ranks"] == [1]

    def test_dtype_and_shape_mismatches_reported(self):
        a = _seq_events(4)
        b = _seq_events(4)
        b[1]["dtype"] = "bfloat16"
        b[2]["shape"] = [512]
        rep = frm.analyze_desync({0: a, 1: b})
        fields = {(m["seq"], m["field"]) for m in rep["mismatches"]}
        assert (2, "dtype") in fields and (3, "shape") in fields
        # all seqs present on all ranks -> divergence point is the first
        # mismatching seq
        assert rep["first_divergent_seq"] == 2

    def test_agreement_is_clean(self):
        rep = frm.analyze_desync({0: _seq_events(6), 1: _seq_events(6)})
        assert rep["first_divergent_seq"] is None
        assert rep["missing_ranks"] == []
        assert rep["mismatches"] == []

    def test_ring_eviction_overlap_window(self):
        # rank 0's ring evicted seqs 1-10; comparison starts at the
        # overlap, not at a false divergence on evicted history
        rep = frm.analyze_desync(
            {0: _seq_events(10, start=11), 1: _seq_events(20)})
        assert rep["first_divergent_seq"] is None

    def test_inflight_events_surface_by_rank(self):
        a = _seq_events(3)
        a[-1]["status"] = "inflight"
        rep = frm.analyze_desync({0: a, 1: _seq_events(3)})
        assert rep["inflight_by_rank"]["0"] == [3]


# ---------------------------------------------------------------------------
# Dump triggers: stall-abort forensics, preemption, HTTP endpoint
# ---------------------------------------------------------------------------

class TestDumpTriggers:
    def test_escalator_abort_rung_emits_report(self, forensics_on):
        from horovod_tpu.resilience.escalation import (EscalationPolicy,
                                                       Escalator)

        fr = frm.get_flight_recorder()
        fr.record("allreduce", "g1", "float32", (4,), 16)
        esc = Escalator(EscalationPolicy(warn_s=0.1, abort_s=0.2))
        esc.observe("grads.bucket0", 5.0)   # crosses warn + abort
        path = os.path.join(str(forensics_on), "desync_report_rank0.json")
        assert os.path.exists(path)
        report = json.load(open(path))
        assert report["stalled_collective"] == "grads.bucket0"
        assert report["stall_age_s"] == pytest.approx(5.0)
        assert report["reporting_rank"] == 0

    def test_abort_without_flight_recorder_is_noop(self, monkeypatch,
                                                   tmp_path):
        from horovod_tpu.resilience.escalation import (EscalationPolicy,
                                                       Escalator)

        monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
        esc = Escalator(EscalationPolicy(warn_s=0.1, abort_s=0.2))
        esc.observe("t", 5.0)
        assert not os.path.exists(
            os.path.join(str(tmp_path), "desync_report_rank0.json"))

    def test_preemption_dumps_ring(self, forensics_on):
        from horovod_tpu.resilience.preempt import (Preempted,
                                                    PreemptionGuard)

        fr = frm.get_flight_recorder()
        fr.record("allreduce", "g1", "float32", (4,), 16)
        guard = PreemptionGuard()
        guard._triggered.set()
        with pytest.raises(Preempted):
            guard.check(exit=False)
        path = os.path.join(str(forensics_on),
                            "flightrecorder_rank0.json")
        assert os.path.exists(path)
        dump = json.load(open(path))
        assert dump["events"] and dump["events"][0]["name"] == "g1"

    def test_flightrecorder_http_endpoint(self, forensics_on, monkeypatch):
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        tinst.reset()
        exp = tele.MetricsExporter(port=0)
        port = exp.start()
        try:
            fr = frm.get_flight_recorder()
            fr.record("allreduce", "g1", "float32", (4,), 16)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/flightrecorder",
                    timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert doc["rank"] == 0
            assert doc["events"][0]["name"] == "g1"
        finally:
            exp.stop()

    def test_flightrecorder_endpoint_404_when_off(self, monkeypatch):
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        tinst.reset()
        exp = tele.MetricsExporter(port=0)
        port = exp.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/flightrecorder", timeout=10)
            assert ei.value.code == 404
        finally:
            exp.stop()

    def test_exporter_publishes_trace_and_flight_to_kv(self, forensics_on):
        import threading

        class FakeKV:
            def __init__(self):
                self.lock = threading.Lock()
                self.store = {}

            def put(self, key, value):
                with self.lock:
                    self.store[key] = value

        kv = FakeKV()
        ttrace.get_tracer().complete("a", 0.001)
        frm.get_flight_recorder().record("allreduce", "g", "float32",
                                         (4,), 16)
        exp = tele.MetricsExporter(port=0, rank=1, kv_client=kv,
                                   publish_interval_s=0)
        assert exp.publish_snapshot()
        assert "/trace/1" in kv.store
        assert "/flightrecorder/1" in kv.store
        assert json.loads(kv.store["/flightrecorder/1"])["events"]


# ---------------------------------------------------------------------------
# Launcher knob plumbing
# ---------------------------------------------------------------------------

class TestLauncherFlags:
    def test_trace_flags_forward_to_env(self):
        import argparse

        from horovod_tpu.runner.config_parser import (add_knob_arguments,
                                                      env_from_args)

        p = argparse.ArgumentParser()
        add_knob_arguments(p)
        args = p.parse_args(["--trace-dir", "/tmp/tr", "--flight-recorder"])
        env = env_from_args(args, {}, base_env={})
        assert env["HVDT_TRACE_DIR"] == "/tmp/tr"
        assert env["HVDT_FLIGHT_RECORDER"] == "1"

    def test_knob_defaults(self):
        from horovod_tpu.common import config

        assert config.get_str("HVDT_TRACE_DIR") == ""
        assert config.get_bool("HVDT_FLIGHT_RECORDER") is False
        assert config.get_int("HVDT_FLIGHT_RECORDER_EVENTS") == 256
        assert config.get_int("HVDT_TRACE_BUFFER") == 65536


# ---------------------------------------------------------------------------
# Multiprocess hang -> stall-abort -> desync report (acceptance scenario)
# ---------------------------------------------------------------------------

@pytest.mark.integration
def test_multiprocess_hang_emits_desync_report(tmp_path, spawn):
    """Two ranks in a lockstep loop; a hang@step fault wedges rank 1
    before it records step 6's collective.  Rank 0's escalation abort
    rung must gather both rings over the rendezvous KV and emit a desync
    report naming the hung rank and the first divergent seq."""
    from horovod_tpu.runner.http_kv import RendezvousServer

    server = RendezvousServer()
    port = server.start()
    procs = []
    try:
        for rank in (0, 1):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
                "HVDT_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVDT_RENDEZVOUS_PORT": str(port),
                "HVDT_SECRET": server.secret.hex(),
                "HVDT_RANK": str(rank),
                "HVDT_SIZE": "2",
                "HVDT_FLIGHT_RECORDER": "1",
                "HVDT_TRACE_DIR": str(tmp_path),
                "HVDT_FAULT_PLAN": "hang@step=6:rank=1:secs=6",
                "DESYNC_TEST_STEPS": "12",
                "DESYNC_TEST_ABORT_S": "1.0",
            })
            env.pop("HVDT_FAULT_JOURNAL", None)
            procs.append(spawn(
                [sys.executable,
                 os.path.join(REPO, "tests", "data", "desync_main.py")],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT))
        outs = []
        deadline = time.monotonic() + 120
        for p in procs:
            out, _ = p.communicate(
                timeout=max(5, deadline - time.monotonic()))
            outs.append(out.decode())
        assert procs[0].returncode == 0, outs[0][-3000:]
        assert procs[1].returncode == 0, outs[1][-3000:]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("desync scenario hung")
    finally:
        server.stop()

    report_path = os.path.join(str(tmp_path), "desync_report_rank0.json")
    assert os.path.exists(report_path), outs[0][-3000:]
    report = json.load(open(report_path))
    # the report names the hung rank...
    assert report["missing_ranks"] == [1]
    # ...and the first collective seq it never recorded (the hang fires
    # before step 6's event is booked -> rank 1's ring stops at seq 5)
    assert report["first_divergent_seq"] == 6
    assert report["per_rank_last_seq"]["1"] == 5
    assert report["per_rank_last_seq"]["0"] >= 6
    assert report["stalled_collective"].startswith("grads.step")
    # the KV copy the driver would read is published too
    assert report["ranks"] == [0, 1]
