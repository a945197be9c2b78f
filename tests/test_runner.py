"""Launcher tests — mirrors the reference's tier-2 strategy (SURVEY.md §4):
pure-Python unit tests of launcher/elastic logic with fake discovery, plus
a real-subprocess programmatic-run integration test.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu.runner import hosts as hosts_mod
from horovod_tpu.runner.http_kv import RendezvousServer, KVClient, new_secret
from horovod_tpu.runner.safe_shell_exec import safe_execute
from horovod_tpu.runner.launch import parse_args
from horovod_tpu.runner.elastic.discovery import HostManager
from horovod_tpu.runner.elastic.driver import ElasticDriver
from horovod_tpu.runner.elastic.registration import (WorkerStateRegistry,
                                                     READY)
from horovod_tpu.runner.hosts import HostInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHosts:
    def test_parse_hosts(self):
        hs = hosts_mod.parse_hosts("a:2,b:4,c")
        assert [(h.hostname, h.slots) for h in hs] == [
            ("a", 2), ("b", 4), ("c", 1)]

    def test_assignments_contiguous(self):
        hs = hosts_mod.parse_hosts("a:2,b:2")
        slots = hosts_mod.get_host_assignments(hs, 4)
        assert [(s.hostname, s.rank, s.local_rank, s.cross_rank)
                for s in slots] == [
            ("a", 0, 0, 0), ("a", 1, 1, 0), ("b", 2, 0, 1), ("b", 3, 1, 1)]
        assert all(s.size == 4 and s.cross_size == 2 and s.local_size == 2
                   for s in slots)

    def test_assignments_insufficient(self):
        with pytest.raises(ValueError):
            hosts_mod.get_host_assignments(hosts_mod.parse_hosts("a:1"), 2)

    def test_env_contract(self):
        s = hosts_mod.get_host_assignments(
            hosts_mod.parse_hosts("x:1"), 1)[0]
        env = s.to_env()
        assert env["HVDT_RANK"] == "0"
        assert env["HVDT_SIZE"] == "1"
        assert env["HVDT_HOSTNAME"] == "x"
        # one process per host drives every local chip: no chip binding
        assert not any(k.startswith("TPU_") for k in env)

    def test_local_slots_each_get_one_chip(self):
        """Four slots on one host: libtpu's per-process variables give
        each worker its own chip (a chip belongs to one process)."""
        slots = hosts_mod.get_host_assignments(
            hosts_mod.parse_hosts("localhost:4"), 4)
        envs = [s.to_env() for s in slots]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        for i, e in enumerate(envs):
            assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["CLOUD_TPU_TASK_ID"] == str(i)
            addrs = e["TPU_PROCESS_ADDRESSES"].split(",")
            assert addrs[i] == f"localhost:{e['TPU_PROCESS_PORT']}"

    def test_no_chip_binding_without_a_process_grid(self):
        """Three slots have no libtpu process grid, and two hosts need a
        global one: nothing is exported, and hvd.init() refuses on a TPU
        host (tests/test_basics.py)."""
        for spec, n in (("localhost:3", 3), ("a:2,b:2", 4)):
            for s in hosts_mod.get_host_assignments(
                    hosts_mod.parse_hosts(spec), n):
                assert "TPU_VISIBLE_CHIPS" not in s.to_env()


class TestKV:
    def test_put_get_roundtrip(self):
        server = RendezvousServer()
        port = server.start()
        try:
            c = KVClient("127.0.0.1", port, server.secret)
            c.put("/a/b", b"hello")
            assert c.get("/a/b") == b"hello"
            assert c.get("/missing") is None
            c.delete("/a/b")
            assert c.get("/a/b") is None
        finally:
            server.stop()

    def test_auth_rejected(self):
        server = RendezvousServer()
        port = server.start()
        try:
            bad = KVClient("127.0.0.1", port, new_secret())
            with pytest.raises(ConnectionError):
                bad.put("/x", b"v")
        finally:
            server.stop()

    def test_wait(self):
        server = RendezvousServer()
        port = server.start()
        try:
            c = KVClient("127.0.0.1", port, server.secret)
            threading.Timer(0.2, lambda: server.put_local("/k", b"v")).start()
            assert c.wait("/k", timeout=5.0) == b"v"
            with pytest.raises(TimeoutError):
                c.wait("/nope", timeout=0.3)
        finally:
            server.stop()


class TestSafeExec:
    def test_exit_code_and_output(self, capfd):
        code = safe_execute("echo out1; echo err1 >&2; exit 3")
        assert code == 3
        cap = capfd.readouterr()
        assert "out1" in cap.out
        assert "err1" in cap.err

    def test_prefix(self, capfd):
        safe_execute("echo hi", prefix="[0]:")
        assert "[0]:hi" in capfd.readouterr().out

    def test_terminate_event_kills_group(self):
        ev = threading.Event()
        t0 = time.monotonic()
        threading.Timer(0.3, ev.set).start()
        code = safe_execute("sleep 30", terminate_event=ev, graceful_s=1.0)
        assert time.monotonic() - t0 < 10
        assert code != 0


class TestParseArgs:
    def test_basic(self):
        a = parse_args(["-np", "4", "-H", "h1:2,h2:2", "--",
                        "python", "train.py"])
        assert a.num_proc == 4
        assert a.hosts == "h1:2,h2:2"
        assert a.command == ["python", "train.py"]

    def test_elastic_flags(self):
        a = parse_args(["--host-discovery-script", "./d.sh", "--min-np", "2",
                        "--max-np", "4", "python", "t.py"])
        assert a.host_discovery_script == "./d.sh"
        assert a.min_np == 2 and a.max_np == 4


class _FakeCluster:
    """Scripted discovery + worker behavior for driver tests
    (ref: test/single/test_elastic_driver.py mock style)."""

    def __init__(self, hosts):
        self.hosts = {h: s for h, s in hosts}
        self.fail_ranks = set()
        self.exited = {}
        self.running = threading.Semaphore(0)
        self.stopped = threading.Event()

    def discover(self):
        return [HostInfo(h, s) for h, s in sorted(self.hosts.items())]

    def spawn(self, slot, gen):
        self.running.release()
        # Workers run until told to exit (simulate a training process).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not self.stopped.is_set():
            if (slot.rank, gen) in self.exited:
                return self.exited[(slot.rank, gen)]
            if slot.rank in self.fail_ranks and \
                    slot.hostname in self.hosts:
                return 1
            time.sleep(0.02)
        return 0

    def stop(self):
        """End the workers still running, so none outlives its test."""
        self.stopped.set()


class TestElasticDriver:
    def test_rank_and_size_with_host_failure(self):
        """Host dies → blacklist → re-rendezvous with fewer hosts
        (ref: test_elastic_driver.py:83 test_rank_and_size_with_host_failure)."""
        cluster = _FakeCluster([("a", 2), ("b", 2)])
        hm = HostManager(cluster.discover)
        driver = ElasticDriver(hm, min_np=2, max_np=4,
                               spawn_fn=cluster.spawn,
                               discovery_interval=0.05)
        gens = []
        driver.start(lambda slots, gen: gens.append(
            (gen, [(s.hostname, s.rank) for s in slots])))
        try:
            assert driver.generation == 1
            assert len(driver.assignments) == 4
            # Kill host b's workers: both report failure, b blacklisted.
            cluster.hosts.pop("b")
            survivors = []
            for w in driver.assignments:
                if w.hostname == "b":
                    cluster.exited[(w.rank, 1)] = 1
                else:
                    survivors.append(w.rank)
            # Surviving workers hit the collective failure and request a
            # new rendezvous (the READY path).
            time.sleep(0.3)
            for r in survivors:
                driver.record_ready(r)
            deadline = time.monotonic() + 5
            while driver.generation < 2 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert driver.generation == 2
            assign2 = driver.assignments
            assert all(s.hostname == "a" for s in assign2)
            assert [s.rank for s in assign2] == [0, 1]
            assert hm.is_blacklisted("b")
        finally:
            driver.stop()
            cluster.stop()

    def test_all_success_finishes_zero(self):
        cluster = _FakeCluster([("a", 2)])
        hm = HostManager(cluster.discover)
        driver = ElasticDriver(hm, min_np=2, spawn_fn=cluster.spawn,
                               discovery_interval=0.05)
        driver.start()
        try:
            for r in (0, 1):
                cluster.exited[(r, 1)] = 0
            assert driver.wait(timeout=5.0) == 0
        finally:
            driver.stop()
            cluster.stop()

    def test_total_failure_finishes_nonzero(self):
        cluster = _FakeCluster([("a", 2)])
        hm = HostManager(cluster.discover)
        driver = ElasticDriver(hm, min_np=2, spawn_fn=cluster.spawn,
                               discovery_interval=0.05)
        driver.start()
        try:
            for r in (0, 1):
                cluster.exited[(r, 1)] = 1
            assert driver.wait(timeout=5.0) == 1
        finally:
            driver.stop()
            cluster.stop()


class TestRegistry:
    def test_barrier_fires_once_all_reported(self):
        fired = []
        reg = WorkerStateRegistry(lambda s: fired.append(s))
        reg.reset(3)
        reg.record_success(0)
        reg.record_success(1)
        assert not fired
        reg.record_ready(2)
        assert len(fired) == 1
        assert fired[0][READY] == {2}
        assert reg.reset_count == 1

    def test_reset_limit(self):
        reg = WorkerStateRegistry(lambda s: None, reset_limit=1)
        reg.reset(1)
        reg.record_ready(0)
        assert reg.reset_limit_reached()


class TestProgrammaticRun:
    def test_run_two_local_workers(self):
        import horovod_tpu.runner as runner

        # Lambda ⇒ cloudpickle serializes by value (test modules are not
        # importable from the worker processes).
        results = runner.run(
            lambda: [int(__import__("os").environ["HVDT_RANK"]),
                     int(__import__("os").environ["HVDT_SIZE"])], np=2)
        assert sorted(results) == [[0, 2], [1, 2]]


class TestConfigParser:
    """CLI/env/config-file knob translation (ref: runner/common/util/
    config_parser.py precedence CLI > env > file > default)."""

    def _args(self, argv):
        return parse_args(argv + ["--", "python", "train.py"])

    def test_cli_flags_to_env(self):
        from horovod_tpu.runner.launch import knob_env_for

        args = self._args(["-np", "2", "--fusion-threshold-mb", "32",
                           "--cycle-time-ms", "2.5", "--autotune",
                           "--timeline-filename", "/tmp/tl.json",
                           "--no-stall-check", "--log-level", "debug"])
        env = knob_env_for(args)
        assert env["HVDT_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
        assert env["HVDT_CYCLE_TIME"] == "2.5"
        assert env["HVDT_AUTOTUNE"] == "1"
        assert env["HVDT_TIMELINE"] == "/tmp/tl.json"
        assert env["HVDT_STALL_CHECK_DISABLE"] == "1"
        assert env["HVDT_LOG_LEVEL"] == "debug"

    def test_config_file_and_precedence(self, tmp_path, monkeypatch):
        from horovod_tpu.runner.config_parser import (apply_config_file,
                                                      env_from_args)

        cfg = tmp_path / "hvdt.yaml"
        cfg.write_text(
            "params:\n  fusion_threshold_mb: 16\n  cycle_time_ms: 7\n"
            "autotune:\n  enabled: true\n"
            "stall_check:\n  warning_time_seconds: 90\n"
            "logging:\n  level: info\n")
        # CLI sets cycle-time (beats file); env sets log level (beats
        # file); file supplies fusion threshold + autotune + stall.
        args = self._args(["--config-file", str(cfg),
                           "--cycle-time-ms", "3"])
        file_values = apply_config_file(args, args.config_file)
        env = env_from_args(args, file_values,
                            base_env={"HVDT_LOG_LEVEL": "error"})
        assert env["HVDT_CYCLE_TIME"] == "3.0"            # CLI wins
        assert env["HVDT_LOG_LEVEL"] == "error"           # env beats file
        assert env["HVDT_FUSION_THRESHOLD"] == str(16 * 1024 * 1024)
        assert env["HVDT_AUTOTUNE"] == "1"
        assert env["HVDT_STALL_CHECK_TIME_SECONDS"] == "90"

    def test_config_file_unknown_key_rejected(self, tmp_path):
        from horovod_tpu.runner.config_parser import apply_config_file

        cfg = tmp_path / "bad.yaml"
        cfg.write_text("params:\n  no_such_knob: 1\n")
        args = self._args(["--config-file", str(cfg)])
        with pytest.raises(ValueError, match="no_such_knob"):
            apply_config_file(args, args.config_file)

    def test_tcp_addrs_allocation(self):
        from horovod_tpu.runner.launch import tcp_addrs_env

        args = self._args(["--cpu-operations", "tcp",
                           "--tcp-base-port", "41000"])
        slots = hosts_mod.get_host_assignments(
            [HostInfo("localhost", 2)], 2)
        env = tcp_addrs_env(args, slots, {"HVDT_CPU_OPERATIONS": "tcp"})
        assert env["HVDT_TCP_ADDRS"] == "127.0.0.1:41000,127.0.0.1:41001"
        # operator-provided addrs are never overwritten
        env2 = tcp_addrs_env(args, slots,
                             {"HVDT_CPU_OPERATIONS": "tcp",
                              "HVDT_TCP_ADDRS": "h:1"})
        assert env2 == {}

    def test_preflight_local_ok_and_remote_failure(self):
        from horovod_tpu.runner.launch import preflight_reachability

        server = RendezvousServer(secret=new_secret())
        port = server.start()
        try:
            args = self._args(["-np", "1"])
            slots = hosts_mod.get_host_assignments(
                [HostInfo("localhost", 1)], 1)
            preflight_reachability(args, slots, "127.0.0.1", port)  # no raise
        finally:
            server.stop()
        # unreachable local port fails fast, with the diagnostic message
        args = self._args(["-np", "1"])
        with pytest.raises(RuntimeError, match="cannot reach"):
            preflight_reachability(args, slots, "127.0.0.1", 1)  # closed port

    def test_elastic_rejects_tcp_data_plane(self):
        from horovod_tpu.runner.elastic.driver import run_elastic

        args = self._args(["--host-discovery-script", "/bin/true",
                           "--cpu-operations", "tcp"])
        with pytest.raises(RuntimeError, match="elastic"):
            run_elastic(args)

    def test_top_level_run_alias(self):
        import horovod_tpu as hvd
        from horovod_tpu import runner

        assert hvd.run is runner.run


def _hvdtrun(spawn, tmp_path, argv):
    """The real CLI as a subprocess, ``hvdtrun <argv>``, with its
    workers; returns (returncode, stdout, stderr).  ``spawn`` ends the
    launcher and its workers with the test, the timeout well before the
    test's own limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = spawn([sys.executable, "-m", "horovod_tpu.runner.launch"] + argv,
                 env=env, cwd=str(tmp_path), stdout=subprocess.PIPE,
                 stderr=subprocess.PIPE, text=True)
    out, err = proc.communicate(timeout=200)
    return proc.returncode, out, err


@pytest.mark.integration
def test_static_cli_end_to_end(tmp_path, spawn):
    """The real CLI as a subprocess: `hvdtrun -np 2 -- python main.py`
    (ref: test/integration/test_static_run.py)."""
    rc, text, err = _hvdtrun(spawn, tmp_path, [
        "-np", "2", "--coordinator-port", "29763",
        "--fusion-threshold-mb", "8",
        "--", sys.executable,
        os.path.join(REPO, "tests", "data", "static_main.py")])
    assert rc == 0, text[-2000:] + err[-2000:]
    assert "STATIC_MAIN rank=0 size=2 red=1.50" in text
    assert "STATIC_MAIN rank=1 size=2 red=1.50" in text


# The two ports of the reference's MNIST examples are ``slow``: 13 and
# 29 s alone, nearly all of it two workers importing torch or TensorFlow
# beside jax.  The compose test-integration service runs them.  Tier-1
# keeps the CLI end to end (test_static_cli_end_to_end), the torch
# DistributedOptimizer over two real processes
# (tests/test_torch_optimizer.py test_two_process_equivalence) and the
# Keras one with its scalar-variable broadcast
# (tests/test_interop_tf_keras.py, tests/test_interop_tf.py
# test_two_process_tf_tape).
@pytest.mark.slow
@pytest.mark.integration
def test_ported_torch_mnist_under_cli(tmp_path, spawn):
    """The porting-guide proof artifact keeps working: the reference's
    pytorch_mnist port runs under the real CLI with 2 workers."""
    rc, out, err = _hvdtrun(spawn, tmp_path, [
        "-np", "2", "--coordinator-port", "29764",
        "--", sys.executable,
        os.path.join(REPO, "examples", "torch_mnist_ported.py"),
        "--epochs", "1", "--train-size", "512", "--test-batch-size",
        "256", "--log-interval", "100"])
    assert rc == 0, out[-2000:] + err[-2000:]
    assert "Test set: Average loss" in out


@pytest.mark.slow
@pytest.mark.integration
def test_ported_tf_keras_mnist_under_cli(tmp_path, spawn):
    """The TF/Keras porting proof runs under the real CLI with 2 workers:
    DistributedOptimizer in model.fit, BroadcastGlobalVariables (incl.
    the optimizer's SCALAR iteration counter — regression for the 0-d
    host-broadcast shard bug), MetricAverage, LR warmup."""
    pytest.importorskip("tensorflow")
    rc, out, err = _hvdtrun(spawn, tmp_path, [
        "-np", "2", "--coordinator-port", "29768",
        "--", sys.executable,
        os.path.join(REPO, "examples", "tf_keras_mnist_ported.py"),
        "--epochs", "1", "--steps-per-epoch", "4", "--samples", "256"])
    assert rc == 0, out[-2000:] + err[-2000:]
