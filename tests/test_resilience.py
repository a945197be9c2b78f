"""Chaos battery for the resilience subsystem (horovod_tpu/resilience):
fault-plan parsing and deterministic injection, the zero-overhead no-op
contract, backoff/retry, checkpoint manifest + last-good fallback,
preemption-safe shutdown, stall escalation, KV/rendezvous hardening, and
the multiprocess kill-one-worker elastic recovery scenario."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from horovod_tpu.resilience import faults as faults_mod
from horovod_tpu.resilience.escalation import (ABORT, RESET, WARN,
                                               EscalationPolicy, Escalator)
from horovod_tpu.resilience.faults import (FaultInjector, InjectedFault,
                                           corrupt_checkpoint_dir, parse_plan)
from horovod_tpu.resilience.preempt import (PREEMPT_EXIT_CODE, Preempted,
                                            PreemptionGuard)
from horovod_tpu.resilience.retry import Backoff, RetriesExhausted, retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_ambient_fault_plan(monkeypatch):
    """Tests own the plan: clear any ambient env plan and reset the
    module cache around each test."""
    monkeypatch.delenv("HVDT_FAULT_PLAN", raising=False)
    faults_mod.configure(None)
    yield
    faults_mod.configure(None)


# ---------------------------------------------------------------------------
# Fault-plan grammar
# ---------------------------------------------------------------------------

class TestPlanParsing:
    def test_issue_example_plan(self):
        specs = parse_plan("crash@step=12:rank=1,hang@step=30:secs=20,"
                           "corrupt_ckpt@step=40,kv_drop@p=0.1")
        kinds = [(s.kind, s.point) for s in specs]
        assert kinds == [("crash", "step"), ("hang", "step"),
                         ("corrupt_ckpt", "checkpoint.save"),
                         ("kv_drop", "kv")]
        assert specs[0].step == 12 and specs[0].rank == 1
        assert specs[1].secs == 20.0
        assert specs[3].p == 0.1

    def test_step_faults_default_to_once(self):
        crash, drop = parse_plan("crash@step=3,kv_drop@p=0.5")
        assert crash.times == 1          # fire once, not every commit
        assert drop.times is None        # probabilistic: unlimited

    def test_point_override_and_times(self):
        (spec,) = parse_plan("exc@point=serve.reload:times=2")
        assert spec.point == "serve.reload" and spec.times == 2

    def test_malformed_entries_raise(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            parse_plan("meteor@step=1")
        with pytest.raises(ValueError, match="unknown key"):
            parse_plan("crash@sstep=1")
        with pytest.raises(ValueError, match="key=value"):
            parse_plan("crash@step")

    def test_empty_entries_skipped(self):
        assert parse_plan(" , ,") == []


# ---------------------------------------------------------------------------
# Zero-overhead no-op contract (acceptance: identity-object test)
# ---------------------------------------------------------------------------

class TestNoOpWhenUnset:
    def test_get_injector_is_none(self):
        assert faults_mod.get_injector() is None

    def test_instrument_returns_the_same_object(self):
        def hot_path():
            return 42

        assert faults_mod.instrument(hot_path, "step") is hot_path
        assert faults_mod.instrument(hot_path, "kv") is hot_path

    def test_instrument_wraps_only_with_a_plan(self, monkeypatch):
        monkeypatch.setenv("HVDT_FAULT_PLAN", "kv_drop@p=0.0")

        def hot_path():
            return 42

        wrapped = faults_mod.instrument(hot_path, "kv")
        assert wrapped is not hot_path
        assert wrapped.__wrapped__ is hot_path
        assert wrapped() == 42

    def test_env_cache_follows_plan_changes(self, monkeypatch):
        assert faults_mod.get_injector() is None
        monkeypatch.setenv("HVDT_FAULT_PLAN", "exc@step=1")
        inj = faults_mod.get_injector()
        assert inj is not None and inj.active
        monkeypatch.delenv("HVDT_FAULT_PLAN")
        assert faults_mod.get_injector() is None

    def test_elastic_commit_unchanged_without_plan(self, monkeypatch):
        """State.commit's resilience hook must do literally nothing when
        no plan and no guard exist (the hot-path contract)."""
        import horovod_tpu.elastic as elastic

        state = elastic.ObjectState(batch=7)
        fired = []
        monkeypatch.setattr(
            state, "check_host_updates", lambda: fired.append(True))
        state.commit()
        assert fired == [True]


# ---------------------------------------------------------------------------
# Injector semantics
# ---------------------------------------------------------------------------

class TestInjectorSemantics:
    def test_exc_fires_at_first_step_past_threshold_once(self):
        inj = FaultInjector(parse_plan("exc@step=5"))
        inj.fire("step", step=4)                       # below: no fire
        with pytest.raises(InjectedFault):
            inj.fire("step", step=6)                   # >= threshold
        inj.fire("step", step=7)                       # once-only
        assert inj.counters == {"exc": 1}

    def test_injected_fault_is_a_horovod_internal_error(self):
        from horovod_tpu.common.exceptions import HorovodInternalError

        assert issubclass(InjectedFault, HorovodInternalError)

    def test_rank_filter(self):
        inj = FaultInjector(parse_plan("exc@step=1:rank=1"))
        inj.fire("step", step=5, rank=0)               # wrong rank
        with pytest.raises(InjectedFault):
            inj.fire("step", step=5, rank=1)

    def test_probabilistic_faults_are_deterministic_under_seed(self):
        def draw(seed):
            inj = FaultInjector(parse_plan("kv_drop@p=0.3"), seed=seed)
            hits = []
            for i in range(50):
                try:
                    inj.fire("kv")
                    hits.append(0)
                except ConnectionError:
                    hits.append(1)
            return hits

        assert draw(7) == draw(7)
        assert draw(7) != draw(8)

    def test_crash_and_hang_actions(self):
        exits, sleeps = [], []
        inj = FaultInjector(parse_plan("crash@step=2:code=9,"
                                       "hang@step=4:secs=0.5"),
                            sleep_fn=sleeps.append, exit_fn=exits.append)
        inj.fire("step", step=2)
        assert exits == [9]
        inj.fire("step", step=4)
        assert sleeps == [0.5]

    def test_wrong_point_never_fires(self):
        inj = FaultInjector(parse_plan("exc@step=1"))
        inj.fire("kv", step=99)
        inj.fire("checkpoint.save", step=99)
        assert inj.fired_total() == 0


# ---------------------------------------------------------------------------
# Backoff / retry primitive
# ---------------------------------------------------------------------------

class TestBackoff:
    def test_exponential_growth_capped(self):
        b = Backoff(first=0.1, factor=2.0, cap=0.4, jitter=0.0,
                    sleep_fn=lambda s: None)
        assert [b.next_delay() for _ in range(4)] == [0.1, 0.2, 0.4, 0.4]

    def test_jitter_stays_within_band(self):
        import random

        b = Backoff(first=1.0, factor=1.0, cap=1.0, jitter=0.5,
                    rng=random.Random(0), sleep_fn=lambda s: None)
        for _ in range(100):
            d = b.next_delay()
            assert 0.5 <= d <= 1.0

    def test_deadline_bounds_total_sleep(self):
        slept = []
        clock = [0.0]

        def fake_sleep(s):
            slept.append(s)
            clock[0] += s

        b = Backoff(first=0.1, cap=10.0, jitter=0.0, deadline_s=1.0,
                    sleep_fn=fake_sleep, clock=lambda: clock[0])
        while b.sleep():
            pass
        assert sum(slept) <= 1.0 + 1e-9
        assert not b.sleep()      # stays exhausted

    def test_reset_rewinds_the_ladder(self):
        b = Backoff(first=0.1, factor=2.0, cap=10.0, jitter=0.0)
        b.next_delay(), b.next_delay()
        b.reset()
        assert b.next_delay() == 0.1

    def test_rejects_nonsense(self):
        with pytest.raises(ValueError):
            Backoff(first=0.0)
        with pytest.raises(ValueError):
            Backoff(first=1.0, cap=0.5)


class TestRetry:
    def test_succeeds_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("transient")
            return "ok"

        assert retry(flaky, attempts=5,
                     backoff=Backoff(first=0.001, cap=0.002)) == "ok"
        assert len(calls) == 3

    def test_exhaustion_raises_with_cause(self):
        def dead():
            raise ConnectionError("still down")

        with pytest.raises(RetriesExhausted) as ei:
            retry(dead, attempts=3, backoff=Backoff(first=0.001, cap=0.002))
        assert isinstance(ei.value.__cause__, ConnectionError)

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def fatal():
            calls.append(1)
            raise ValueError("a 403 is not a flake")

        with pytest.raises(ValueError):
            retry(fatal, attempts=5, backoff=Backoff(first=0.001, cap=0.002))
        assert len(calls) == 1

    def test_unbounded_retry_rejected(self):
        with pytest.raises(ValueError, match="attempts"):
            retry(lambda: 1)


# ---------------------------------------------------------------------------
# Checkpoint hardening: manifest, LAST_GOOD, corrupt fallback
# ---------------------------------------------------------------------------

class TestCheckpointHardening:
    def _mgr(self, tmp_path, **kw):
        from horovod_tpu.checkpoint import CheckpointManager

        kw.setdefault("max_to_keep", 10)
        return CheckpointManager(os.path.join(tmp_path, "ckpts"), **kw)

    def test_save_writes_manifest_and_last_good(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(5, {"x": jnp.ones(3)}, force=True)
        assert os.path.exists(mgr._manifest_path(5))
        assert mgr.last_good_step() == 5
        assert mgr.verify_step(5)
        mgr.save(9, {"x": jnp.ones(3)}, force=True)
        assert mgr.last_good_step() == 9

    def test_corrupt_newest_falls_back_to_intact(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(1, {"x": jnp.ones(2) * 1.0}, force=True)
        mgr.save(2, {"x": jnp.ones(2) * 2.0}, force=True)
        assert corrupt_checkpoint_dir(mgr.step_path(2)) is not None
        assert not mgr.verify_step(2)
        tree, step = mgr.restore_latest({"x": jnp.zeros(2)})
        assert step == 1
        np.testing.assert_allclose(np.asarray(tree["x"]), [1.0, 1.0])
        assert mgr.corrupt_detected == 1

    def test_all_corrupt_returns_none_never_raises(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(1, {"x": jnp.ones(2)}, force=True)
        mgr.save(2, {"x": jnp.ones(2)}, force=True)
        corrupt_checkpoint_dir(mgr.step_path(1))
        corrupt_checkpoint_dir(mgr.step_path(2))
        assert mgr.restore_latest({"x": jnp.zeros(2)}) == (None, None)
        assert mgr.corrupt_detected == 2

    def test_manifestless_checkpoint_still_restores(self, hvd, tmp_path):
        """Pre-hardening checkpoints (no manifest) must stay loadable."""
        mgr = self._mgr(tmp_path)
        mgr.save(3, {"x": jnp.ones(2) * 3.0}, force=True)
        os.remove(mgr._manifest_path(3))
        assert mgr.verify_step(3)
        tree, step = mgr.restore_latest({"x": jnp.zeros(2)})
        assert step == 3

    def test_corrupt_ckpt_fault_plan_end_to_end(self, hvd, tmp_path,
                                                monkeypatch):
        """The injected corruption lands AFTER the manifest, so restore
        detects it and falls back — the acceptance scenario."""
        monkeypatch.setenv("HVDT_FAULT_PLAN", "corrupt_ckpt@step=2")
        mgr = self._mgr(tmp_path)
        mgr.save(1, {"x": jnp.ones(2) * 1.0}, force=True)
        mgr.save(2, {"x": jnp.ones(2) * 2.0}, force=True)
        inj = faults_mod.get_injector()
        assert inj.counters.get("corrupt_ckpt") == 1
        tree, step = mgr.restore_latest({"x": jnp.zeros(2)})
        assert step == 1
        np.testing.assert_allclose(np.asarray(tree["x"]), [1.0, 1.0])

    def test_prune_removes_manifests_and_last_good_follows(self, hvd,
                                                           tmp_path):
        mgr = self._mgr(tmp_path, max_to_keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"x": jnp.ones(1)}, force=True)
        assert mgr.all_steps() == [3, 4]
        assert not os.path.exists(mgr._manifest_path(1))
        assert mgr.last_good_step() == 4

    def test_last_good_pointer_survives_pruned_target(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(5, {"x": jnp.ones(1)}, force=True)
        import shutil

        shutil.rmtree(mgr.step_path(5))
        mgr.save(3, {"x": jnp.ones(1)}, force=True)  # older step remains
        # Pointer says 5, 5 is gone -> newest surviving step.
        assert mgr.last_good_step() == 3


# ---------------------------------------------------------------------------
# Preemption guard
# ---------------------------------------------------------------------------

class TestPreemptionGuard:
    def test_sigterm_sets_flag_then_check_raises(self):
        saved = []
        guard = PreemptionGuard(on_preempt=lambda: saved.append(True))
        before = PreemptionGuard.emergency_checkpoints
        with guard:
            assert guard.check(step=1) is False
            os.kill(os.getpid(), signal.SIGTERM)
            for _ in range(100):
                if guard.triggered:
                    break
                time.sleep(0.01)
            assert guard.triggered
            with pytest.raises(Preempted):
                guard.check(step=2, exit=False)
        assert saved == [True]
        assert PreemptionGuard.emergency_checkpoints == before + 1

    def test_preempted_is_a_system_exit_with_the_code(self):
        exc = Preempted()
        assert isinstance(exc, SystemExit)
        assert exc.code == PREEMPT_EXIT_CODE

    def test_uninstall_restores_previous_handler(self):
        prev = signal.getsignal(signal.SIGTERM)
        guard = PreemptionGuard().install()
        assert signal.getsignal(signal.SIGTERM) != prev
        guard.uninstall()
        assert signal.getsignal(signal.SIGTERM) == prev

    def test_failing_emergency_save_still_exits_clean(self, monkeypatch):
        def broken():
            raise OSError("disk full")

        exits = []
        monkeypatch.setattr(os, "_exit", exits.append)
        guard = PreemptionGuard(on_preempt=broken)
        guard._triggered.set()
        guard.check(exit=True)
        assert exits == [PREEMPT_EXIT_CODE]

    @pytest.mark.integration
    def test_sigterm_subprocess_emergency_checkpoint_and_exit_code(
            self, tmp_path, spawn):
        """Acceptance: SIGTERM produces an emergency checkpoint and the
        clean-removal exit code (real process, real signal)."""
        out = os.path.join(tmp_path, "emergency.json")
        env = dict(os.environ, PREEMPT_TEST_OUT=out, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""))
        proc = spawn(
            [sys.executable, os.path.join(REPO, "tests", "data",
                                          "preempt_main.py")],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)
        assert proc.stdout.readline().strip() == "ready"
        time.sleep(0.3)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
        assert rc == PREEMPT_EXIT_CODE
        with open(out) as f:
            payload = json.load(f)
        assert payload["emergency"] and payload["step"] > 0

    def test_driver_treats_preempt_exit_as_clean_removal(self):
        """PREEMPT_EXIT_CODE -> READY (re-rendezvous), no blacklist —
        unlike a crash exit."""
        from horovod_tpu.runner.elastic.discovery import HostManager
        from horovod_tpu.runner.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo, get_host_assignments

        hm = HostManager(lambda: [HostInfo("a", 2)])
        hm.update_available_hosts()
        driver = ElasticDriver(hm, min_np=2, spawn_fn=lambda s, g: 0)
        driver._generation = 1
        driver._assignments = get_host_assignments(
            [HostInfo("a", 2)], 2)
        driver.registry.reset(2)
        try:
            driver.record_exit(driver._assignments[1], 1, PREEMPT_EXIT_CODE)
            assert driver.registry.count("READY") == 1
            assert not hm.is_blacklisted("a")
            driver.record_exit(driver._assignments[0], 1, 1)  # real crash
            assert hm.is_blacklisted("a")
        finally:
            # the second exit completes the barrier, which starts a
            # re-rendezvous thread that waits for slots the blacklist
            # has just taken away: end it with the test
            driver.stop()


# ---------------------------------------------------------------------------
# Stall escalation ladder
# ---------------------------------------------------------------------------

class TestEscalation:
    def test_rungs_fire_in_order_once(self):
        events = []
        esc = Escalator(EscalationPolicy(warn_s=1, abort_s=2, reset_s=3),
                        on_warn=lambda n, a: events.append(("warn", n)),
                        on_abort=lambda n: events.append(("abort", n)),
                        on_reset=lambda: events.append(("reset",)))
        assert esc.observe("t", 0.5) == 0
        assert esc.observe("t", 1.5) == WARN
        assert esc.observe("t", 1.6) == WARN          # no re-fire
        assert esc.observe("t", 3.5) == RESET          # abort+reset together
        assert events == [("warn", "t"), ("abort", "t"), ("reset",)]
        assert esc.counters == {"warn": 1, "abort": 1, "reset": 1}

    def test_drain_and_reset_are_one_shot(self):
        esc = Escalator(EscalationPolicy(warn_s=1, abort_s=2, reset_s=3))
        esc.observe("t", 10.0)
        assert esc.drain_aborts() == {"t"}
        assert esc.drain_aborts() == set()
        assert esc.reset_requested() is True
        assert esc.reset_requested() is False

    def test_resolve_rearms_the_ladder(self):
        esc = Escalator(EscalationPolicy(warn_s=1, abort_s=2))
        esc.observe("t", 5.0)
        esc.resolve("t")
        esc.observe("t", 5.0)
        assert esc.counters["abort"] == 2

    def test_policy_clamps_out_of_order_thresholds(self):
        p = EscalationPolicy(warn_s=60, abort_s=10, reset_s=5)
        assert p.abort_s >= p.warn_s
        assert p.reset_s >= p.abort_s

    def test_disabled_rungs_stop_the_ladder(self):
        esc = Escalator(EscalationPolicy(warn_s=1, abort_s=0, reset_s=0))
        assert esc.observe("t", 1e9) == WARN
        assert esc.drain_aborts() == set()

    def test_stall_inspector_feeds_escalator(self, monkeypatch):
        from horovod_tpu.stall import StallInspector

        monkeypatch.delenv("HVDT_STALL_CHECK_DISABLE", raising=False)
        esc = Escalator(EscalationPolicy(warn_s=0.01, abort_s=0.02))
        insp = StallInspector(world_size=2, warn_seconds=1,
                              escalator=esc)
        insp.record("grad", rank=0)      # rank 1 never shows up
        time.sleep(0.05)
        insp._last_check = 0.0
        insp.check()
        assert esc.drain_aborts() == {"grad"}
        insp.resolve("grad")             # resolution propagates
        assert esc.observe("grad", 5.0) == ABORT   # fresh episode

    def test_controller_builds_escalator_from_env(self, monkeypatch):
        """The eager controller consumes the ladder when a rung is
        configured, and aborting a stalled key emits an error response."""
        monkeypatch.setenv("HVDT_STALL_ABORT_TIME_SECONDS", "1")
        from horovod_tpu.ops.eager import EagerController
        from horovod_tpu.ops.control_plane import LocalControlPlane

        ctl = EagerController(control_plane=LocalControlPlane())
        try:
            assert ctl._escalator is not None
            assert ctl._stall.escalator is ctl._escalator
            # Simulate the coordinator seeing a stalled key, then the
            # ladder crossing the abort rung.
            from horovod_tpu.ops.messages import Request, RequestType

            req = Request(0, RequestType.ALLREDUCE, "stuck", 0, (2,))
            ctl._message_table.pending[(0, "stuck")] = {0: req}
            ctl._escalator.observe("stuck", 1e9)
            out = ctl._abort_escalated_stalls()
            assert len(out) == 1
            assert "aborted" in out[0].error_message
            assert (0, "stuck") not in ctl._message_table.pending
        finally:
            ctl.shutdown()


# ---------------------------------------------------------------------------
# Rendezvous KV hardening
# ---------------------------------------------------------------------------

class TestKVHardening:
    def _server_client(self):
        from horovod_tpu.runner.http_kv import KVClient, RendezvousServer

        server = RendezvousServer()
        port = server.start()
        client = KVClient("127.0.0.1", port, server.secret, timeout=5.0)
        return server, client

    def test_stop_kills_the_serve_thread(self):
        server, client = self._server_client()
        t = server._thread
        assert t.is_alive()
        assert server.stop() is True
        assert not t.is_alive()
        assert server._thread is None

    def test_wait_backoff_returns_value_published_midway(self):
        server, client = self._server_client()
        try:
            threading.Timer(0.2, server.put_local,
                            args=("/k", b"v")).start()
            assert client.wait("/k", timeout=10.0, poll=0.1) == b"v"
        finally:
            server.stop()

    def test_wait_timeout_raises(self):
        server, client = self._server_client()
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError):
                client.wait("/missing", timeout=0.5, poll=0.05)
            assert time.monotonic() - t0 < 5.0
        finally:
            server.stop()

    def test_wait_survives_injected_kv_drops(self, monkeypatch):
        """kv_drop faults make individual gets raise; the backoff loop
        absorbs them and still finds the key within the deadline."""
        monkeypatch.setenv("HVDT_FAULT_PLAN", "kv_drop@p=0.5")
        monkeypatch.setenv("HVDT_FAULT_SEED", "3")
        server, client = self._server_client()
        try:
            server.put_local("/k2", b"v2")
            assert client.wait("/k2", timeout=10.0, poll=0.05) == b"v2"
            inj = faults_mod.get_injector()
            assert inj.counters.get("kv_drop", 0) >= 1
        finally:
            server.stop()

    def test_get_raises_injected_drop_directly(self, monkeypatch):
        monkeypatch.setenv("HVDT_FAULT_PLAN", "kv_drop@p=1.0:times=1")
        server, client = self._server_client()
        try:
            with pytest.raises(ConnectionError, match="injected kv drop"):
                client.get("/x")
            assert client.get("/x") is None      # fault exhausted
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# Discovery blacklist cooldown
# ---------------------------------------------------------------------------

class TestBlacklistCooldown:
    def test_default_blacklist_is_permanent(self):
        from horovod_tpu.runner.elastic.discovery import HostState

        st = HostState()
        st.blacklist()
        assert st.is_blacklisted

    def test_cooldown_expires_and_doubles(self):
        from horovod_tpu.runner.elastic.discovery import HostState

        st = HostState(cooldown_s=0.1)
        st.blacklist()
        assert st.is_blacklisted
        time.sleep(0.15)
        assert not st.is_blacklisted       # transient crash forgiven
        st.blacklist()                     # second failure: 2x cooldown
        time.sleep(0.15)
        assert st.is_blacklisted
        time.sleep(0.1)
        assert not st.is_blacklisted
        assert st.failures == 2

    def test_env_knob_drives_default(self, monkeypatch):
        from horovod_tpu.runner.elastic.discovery import HostState

        monkeypatch.setenv("HVDT_ELASTIC_BLACKLIST_COOLDOWN_S", "0.05")
        st = HostState()
        st.blacklist()
        time.sleep(0.1)
        assert not st.is_blacklisted


# ---------------------------------------------------------------------------
# Serve reload hardening
# ---------------------------------------------------------------------------

class TestServeReloadHardening:
    def test_failure_streak_and_last_good_gauge(self, hvd, tmp_path):
        from horovod_tpu.checkpoint import CheckpointManager
        from horovod_tpu.serve.reload import CheckpointWatcher

        mgr = CheckpointManager(os.path.join(tmp_path, "c"), max_to_keep=10)
        mgr.save(1, {"x": jnp.ones(2) * 1.0}, force=True)
        seen = []
        watcher = CheckpointWatcher(
            mgr, template={"x": jnp.zeros(2)},
            on_reload=lambda tree, step: seen.append(step),
            poll_interval_s=0.05)
        assert watcher.check_once() == 1
        assert watcher._fail_streak == 0
        # Corrupt the next step: its manifest verification fails, so the
        # watcher SKIPS it (counted, but no failure streak — a corrupt
        # newest step must not slow the poll down) and keeps serving
        # step 1.
        mgr.save(2, {"x": jnp.ones(2) * 2.0}, force=True)
        corrupt_checkpoint_dir(mgr.step_path(2))
        assert watcher.check_once() is None
        assert watcher._fail_streak == 0
        assert watcher.current_step == 1
        # A good step arrives: reload succeeds immediately.
        mgr.save(3, {"x": jnp.ones(2) * 3.0}, force=True)
        assert watcher.check_once() == 3
        assert watcher._fail_streak == 0
        assert seen == [1, 3]
        text = watcher.metrics.render()
        assert "serve_last_good_step 3" in text
        assert "serve_skipped_unverified_total 1" in text
        assert "serve_reload_failures_total 0" in text

    def test_reload_fault_point(self, hvd, tmp_path, monkeypatch):
        from horovod_tpu.checkpoint import CheckpointManager
        from horovod_tpu.serve.reload import CheckpointWatcher

        monkeypatch.setenv("HVDT_FAULT_PLAN",
                           "exc@point=serve.reload:step=1")
        mgr = CheckpointManager(os.path.join(tmp_path, "c"), max_to_keep=10)
        mgr.save(1, {"x": jnp.ones(2)}, force=True)
        watcher = CheckpointWatcher(
            mgr, template={"x": jnp.zeros(2)},
            on_reload=lambda tree, step: None, poll_interval_s=0.05)
        # Injected failure is absorbed by the watcher's failure policy.
        assert watcher.check_once() is None
        assert watcher._fail_streak == 1


# ---------------------------------------------------------------------------
# TCP connect retry (stubbed native group)
# ---------------------------------------------------------------------------

class TestTcpConnectRetry:
    def test_bootstrap_retries_then_succeeds(self, monkeypatch):
        from horovod_tpu.ops import tcp_backend
        from horovod_tpu import native as native_mod

        attempts = []

        class FakeGroup:
            def __init__(self, rank, size, addrs, timeout_ms=0):
                attempts.append(1)
                if len(attempts) < 3:
                    raise native_mod.NativeError(1, "connect refused")

            def close(self):
                pass

        class PS:
            id = 7
            ranks = [0]

            def rank(self):
                return 0

            def size(self):
                return 1

        monkeypatch.setenv("HVDT_TCP_ADDRS", "127.0.0.1:49000")
        monkeypatch.setattr(native_mod, "TcpProcessGroup", FakeGroup)
        monkeypatch.setattr(tcp_backend, "_groups", {})
        g = tcp_backend.group_for(PS())
        assert isinstance(g, FakeGroup)
        assert len(attempts) == 3

    def test_bootstrap_exhaustion_raises(self, monkeypatch):
        from horovod_tpu.ops import tcp_backend
        from horovod_tpu import native as native_mod

        class DeadGroup:
            def __init__(self, *a, **kw):
                raise native_mod.NativeError(1, "nope")

        class PS:
            id = 8
            ranks = [0]

            def rank(self):
                return 0

            def size(self):
                return 1

        monkeypatch.setenv("HVDT_TCP_ADDRS", "127.0.0.1:49100")
        monkeypatch.setattr(native_mod, "TcpProcessGroup", DeadGroup)
        monkeypatch.setattr(tcp_backend, "_groups", {})
        with pytest.raises(RetriesExhausted):
            tcp_backend.group_for(PS())


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------

class TestCliWiring:
    def test_fault_plan_flag_forwards_as_env(self):
        from horovod_tpu.runner.launch import knob_env_for, parse_args

        args = parse_args(["--fault-plan", "crash@step=5:rank=1",
                           "--blacklist-cooldown", "2.5",
                           "--stall-abort-time-seconds", "30",
                           "-np", "2", "--", "python", "train.py"])
        env = knob_env_for(args)
        assert env["HVDT_FAULT_PLAN"] == "crash@step=5:rank=1"
        assert env["HVDT_ELASTIC_BLACKLIST_COOLDOWN_S"] == "2.5"
        assert env["HVDT_STALL_ABORT_TIME_SECONDS"] == "30"

    def test_fault_journal_survives_process_restart(self, tmp_path,
                                                    monkeypatch):
        """Once-only faults must stay once-only across elastic respawns:
        a fresh injector with the same journal sees the fired count."""
        journal = os.path.join(tmp_path, "j")
        monkeypatch.setenv("HVDT_FAULT_PLAN", "exc@step=5")
        monkeypatch.setenv("HVDT_FAULT_JOURNAL", journal)
        monkeypatch.setenv("HVDT_RANK", "0")
        inj1 = FaultInjector.from_env()
        with pytest.raises(InjectedFault):
            inj1.fire("step", step=10, rank=0)
        inj2 = FaultInjector.from_env()   # the "respawned" process
        inj2.fire("step", step=15, rank=0)   # must NOT re-fire
        assert inj2.fired_total() == 0
        assert inj2.specs[0].fired == 1   # loaded from the journal


# ---------------------------------------------------------------------------
# Multiprocess chaos: kill one worker mid-training, elastic recovery
# ---------------------------------------------------------------------------

def _rows(path):
    out = []
    with open(path) as f:
        for ln in f:
            if ln.strip():
                r, s, b, lr, ts = map(int, ln.split())
                out.append((r, s, b, lr, ts))
    return out


@pytest.mark.integration
def test_injected_crash_recovers_with_step_continuity(tmp_path, spawn):
    """Acceptance scenario: HVDT_FAULT_PLAN kills rank 1 at a commit
    point mid-training.  The hardened stack must recover — the
    survivor's peer-stall detection converts the dead peer into the
    elastic restore path (HorovodInternalError → exit-for-respawn), the
    cooldown blacklist lets the host rejoin, and the new generation
    resumes from the disk commit with monotone step continuation and
    loss continuity to the target batch count.

    (The coupling rides rendezvous-KV heartbeats, not eager collectives:
    the container's CPU jax cannot run multiprocess XLA computations —
    the pre-existing test_elastic_integration failures — and the
    recovery machinery under test is identical either way; see
    tests/data/resilient_main.py.)"""
    log_path = os.path.join(tmp_path, "progress.log")
    env = dict(os.environ)
    env.update({
        "ELASTIC_TEST_LOG": log_path,
        "ELASTIC_TEST_STATE": os.path.join(tmp_path, "state.pkl"),
        "ELASTIC_TEST_BATCHES": "30",
        "ELASTIC_TEST_SLEEP": "0.1",
        "ELASTIC_TEST_HB_TIMEOUT": "6",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        # The chaos knobs under test:
        "HVDT_FAULT_PLAN": "crash@step=10:rank=1",
        "HVDT_FAULT_JOURNAL": os.path.join(tmp_path, "fault_journal"),
        "HVDT_ELASTIC_BLACKLIST_COOLDOWN_S": "1",
    })
    discover = os.path.join(tmp_path, "discover.sh")
    with open(discover, "w") as f:
        f.write("#!/bin/sh\necho localhost:2\n")
    os.chmod(discover, 0o755)
    proc = spawn(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", "2", "--max-np", "2",
         "--host-discovery-script", discover,
         "--coordinator-port", "29761",
         "--", sys.executable, os.path.join(REPO, "tests", "data",
                                            "resilient_main.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    try:
        out, _ = proc.communicate(timeout=200)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"chaos run hung:\n{out.decode()[-3000:]}")
    assert proc.returncode == 0, out.decode()[-3000:]

    rows = _rows(log_path)
    # Training reached the target despite the mid-training kill.
    assert max(b for _, _, b, _, _ in rows) == 30
    # Rank 1 died at its batch-10 commit and came back: it logged batches
    # past the crash point...
    r1_batches = [b for r, _, b, _, _ in rows if r == 1]
    assert max(r1_batches) == 30
    # ...and the recovered generation resumed from the disk commit, not
    # from scratch (monotone continuation: no restart at batch 1).
    post_crash = [b for b in r1_batches if b > 10]
    assert post_crash, "rank 1 never progressed past the injected crash"
    assert min(post_crash) == 11
    resumed_from = r1_batches[r1_batches.index(11) - 1] \
        if r1_batches.index(11) > 0 else 0
    assert resumed_from >= 5, (
        f"recovered worker resumed from batch {resumed_from}, "
        f"not from the last commit")
    # Both ranks finished the final world.
    assert {r for r, _, b, _, _ in rows if b == 30} == {0, 1}
    # Loss continuity: every batch applied its update exactly once
    # across crash/restore/replay (w0 == 30 batches * lr 0.2).
    assert "final: batches=30 w0=6.0" in out.decode()
    # Recovery-time budget: "we recovered" is not enough — the wall
    # clock from rank 1's death (its last pre-crash batch-10 line) to
    # its first NEW batch (11) must stay under the 30 s SLO.
    r1_rows = sorted((ts, b) for r, _, b, _, ts in rows if r == 1)
    t_kill = min(ts for ts, b in r1_rows if b == 10)
    t_recovered = min(ts for ts, b in r1_rows if b == 11)
    recovery_s = (t_recovered - t_kill) / 1000.0
    assert recovery_s < 30.0, (
        f"rank 1 recovery took {recovery_s:.1f}s (budget 30s)")
