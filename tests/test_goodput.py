"""Continuous-goodput battery: async non-blocking checkpoints, the
peer-replicated RAM tier, the recovery-time budget, and deterministic
data resume (ROADMAP item 4).

The multiprocess scenario tests at the bottom are the acceptance bar:
kill rank 1 (and, pod variant, a whole pod) mid-run with
``HVDT_ASYNC_CKPT=1`` + ``HVDT_PEER_STORE=1`` and prove recovery came
from the surviving peer RAM tier (no disk restore), landed inside the
30 s budget, and replayed zero committed batches.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from horovod_tpu.checkpoint import CheckpointManager  # noqa: E402
from horovod_tpu.resilience import faults  # noqa: E402
from horovod_tpu.resilience import peer_store as peer_store_mod  # noqa: E402
from horovod_tpu.resilience.peer_store import PeerStore  # noqa: E402
from horovod_tpu.runner.http_kv import KVClient, RendezvousServer  # noqa: E402
from horovod_tpu.telemetry import step_stats  # noqa: E402
from horovod_tpu.telemetry.metrics import (MetricsRegistry,  # noqa: E402
                                           reset_default_registry)


@pytest.fixture(autouse=True)
def _clean_goodput_state(monkeypatch):
    """Each test gets a fresh default registry, recovery ledger, fault
    plan, and peer-store cache — all four are process-wide singletons."""
    monkeypatch.delenv("HVDT_ASYNC_CKPT", raising=False)
    monkeypatch.delenv("HVDT_PEER_STORE", raising=False)
    monkeypatch.delenv("HVDT_FAULT_PLAN", raising=False)
    reset_default_registry()
    step_stats.reset_recovery_ledger()
    peer_store_mod.reset()
    faults.configure(None)
    yield
    reset_default_registry()
    step_stats.reset_recovery_ledger()
    peer_store_mod.reset()
    faults.configure(None)


def _tree(k=1.0):
    return {"w": jnp.ones(8) * k, "b": np.arange(4.0) * k}


# ---------------------------------------------------------------------------
# Async checkpointing
# ---------------------------------------------------------------------------

class TestAsyncCheckpoint:
    def test_identity_contract_when_unset(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"))
        # The faults/telemetry/overlap idiom: no knob, no wrapper — the
        # attribute IS the synchronous save.
        assert mgr.save_async == mgr.save
        assert mgr.save_async.__func__ is CheckpointManager.save

    def test_async_write_advances_last_good(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        mgr = CheckpointManager(str(tmp_path / "c"))
        assert mgr.save_async.__func__ is not CheckpointManager.save
        assert mgr.last_good_step() is None
        assert mgr.save_async(3, _tree(3.0), force=True)
        assert mgr.wait_for_async(30)
        assert mgr.last_good_step() == 3
        assert mgr.verify_step(3)
        tree, step = mgr.restore_latest(_tree(0.0), broadcast=False)
        assert step == 3
        np.testing.assert_allclose(np.asarray(tree["w"]), 3.0)
        mgr.close()

    def test_interval_respected(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        mgr = CheckpointManager(str(tmp_path / "c"), save_interval_steps=5)
        assert not mgr.save_async(3, _tree())
        assert mgr.save_async(5, _tree())
        assert mgr.wait_for_async(30)
        assert mgr.last_good_step() == 5
        mgr.close()

    def test_newer_snapshot_supersedes_queued(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        mgr = CheckpointManager(str(tmp_path / "c"), max_to_keep=10)
        gate = threading.Event()
        orig = CheckpointManager._write_step_payload

        def gated(self, step, payload):
            gate.wait(30)
            orig(self, step, payload)

        monkeypatch.setattr(CheckpointManager, "_write_step_payload", gated)
        mgr.save_async(1, _tree(1.0), force=True)   # writer blocks on gate
        deadline = time.monotonic() + 5
        while not mgr._writer._busy and time.monotonic() < deadline:
            time.sleep(0.01)                        # let it pick up step 1
        assert mgr._writer._busy
        mgr.save_async(2, _tree(2.0), force=True)   # queued
        mgr.save_async(3, _tree(3.0), force=True)   # supersedes step 2
        gate.set()
        assert mgr.wait_for_async(30)
        assert mgr.last_good_step() == 3
        assert mgr.all_steps() == [1, 3]            # step 2 never written
        reg = mgr._async_metrics()
        assert reg["superseded"].total() == 1
        mgr.close()

    def test_write_failure_keeps_last_good(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        mgr = CheckpointManager(str(tmp_path / "c"))
        mgr.save_async(1, _tree(1.0), force=True)
        assert mgr.wait_for_async(30)
        assert mgr.last_good_step() == 1

        def boom(self, step, payload):
            raise OSError("disk on fire")

        monkeypatch.setattr(CheckpointManager, "_write_step_payload", boom)
        mgr.save_async(2, _tree(2.0), force=True)
        assert mgr.wait_for_async(30)
        assert mgr.last_good_step() == 1            # pointer never moved
        assert mgr._async_metrics()["failures"].total() == 1
        mgr.close()

    def test_snapshot_budget_counter(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        monkeypatch.setenv("HVDT_CKPT_SNAPSHOT_BUDGET_S", "0")
        mgr = CheckpointManager(str(tmp_path / "c"))
        mgr.save_async(1, _tree(), force=True)
        assert mgr.wait_for_async(30)
        assert mgr._async_metrics()["over_budget"].total() >= 1
        assert mgr._async_metrics()["snapshot"].count >= 1
        mgr.close()

    def test_nonblocking_under_slow_disk(self, tmp_path, monkeypatch):
        """The acceptance proof: under slow_disk@step=N:secs=S the step
        loop stays within 2x of baseline while the background write is
        in flight — and LAST_GOOD still only advances after a verified
        manifest."""
        from horovod_tpu.telemetry.step_stats import StepTimer

        step_sleep = 0.05
        baseline = StepTimer(registry=MetricsRegistry())
        for _ in range(4):
            with baseline.step():
                time.sleep(step_sleep)

        monkeypatch.setenv("HVDT_ASYNC_CKPT", "1")
        monkeypatch.setenv("HVDT_FAULT_PLAN", "slow_disk@step=1:secs=1.5")
        mgr = CheckpointManager(str(tmp_path / "c"))
        timed = StepTimer(registry=MetricsRegistry())
        tree = _tree()
        for i in range(1, 5):
            with timed.step():
                time.sleep(step_sleep)
                mgr.save_async(i, tree, force=True)
        # The 1.5 s injected fsync stall must not have surfaced in any
        # step: mean within 2x of the no-checkpoint baseline.
        assert timed.mean_step_seconds() < 2 * baseline.mean_step_seconds()
        assert mgr.wait_for_async(30)
        good = mgr.last_good_step()
        assert good is not None and good >= 1
        assert mgr.verify_step(good)
        mgr.close()

    def test_sync_save_stalls_under_slow_disk(self, tmp_path, monkeypatch):
        """Control leg: the same fault at the same seam DOES stall the
        synchronous save — proving the fault fires where claimed."""
        monkeypatch.setenv("HVDT_FAULT_PLAN", "slow_disk@step=1:secs=0.4")
        mgr = CheckpointManager(str(tmp_path / "c"))
        t0 = time.perf_counter()
        mgr.save(1, _tree(), force=True)
        assert time.perf_counter() - t0 >= 0.4


# ---------------------------------------------------------------------------
# Durable manifests + torn-manifest fault (satellite)
# ---------------------------------------------------------------------------

class TestDurableManifest:
    def test_truncated_manifest_fails_verification(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"), max_to_keep=10)
        mgr.save(1, _tree(1.0), force=True)
        mgr.save(2, _tree(2.0), force=True)
        assert mgr.verify_step(2)
        assert faults.truncate_file(mgr._manifest_path(2))
        assert not mgr.verify_step(2)
        tree, step = mgr.restore_latest(_tree(0.0), broadcast=False)
        assert step == 1
        assert mgr.corrupt_detected == 1

    def test_corrupt_ckpt_truncate_manifest_plan(self, tmp_path,
                                                 monkeypatch):
        """The new fault-plan variant: the manifest of the step-2 save
        is truncated between write and LAST_GOOD advance — restore must
        fall back to step 1 without crashing."""
        mgr = CheckpointManager(str(tmp_path / "c"), max_to_keep=10)
        mgr.save(1, _tree(1.0), force=True)
        monkeypatch.setenv(
            "HVDT_FAULT_PLAN", "corrupt_ckpt@step=2:mode=truncate_manifest")
        mgr.save(2, _tree(2.0), force=True)
        assert not mgr.verify_step(2)
        tree, step = mgr.restore_latest(_tree(0.0), broadcast=False)
        assert step == 1
        np.testing.assert_allclose(np.asarray(tree["w"]), 1.0)

    def test_manifest_and_pointer_are_fsynced(self, tmp_path, monkeypatch):
        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync",
                            lambda fd: (synced.append(fd), real_fsync(fd)))
        mgr = CheckpointManager(str(tmp_path / "c"))
        mgr.save(1, _tree(), force=True)
        # manifest file + directory + LAST_GOOD tmp + directory again.
        assert len(synced) >= 4

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="truncate_manifest"):
            faults.parse_plan("corrupt_ckpt@step=1:mode=shred")

    def test_slow_disk_grammar(self):
        spec = faults.parse_plan("slow_disk@step=8:secs=5")[0]
        assert spec.kind == "slow_disk"
        assert spec.point == "checkpoint.write"
        assert spec.secs == 5.0
        assert spec.times == 1


# ---------------------------------------------------------------------------
# Serve reload skips unverified steps (satellite)
# ---------------------------------------------------------------------------

class TestReloadSkipsUnverified:
    def test_truncated_manifest_falls_back_immediately(self, hvd, tmp_path):
        from horovod_tpu.serve.reload import CheckpointWatcher

        mgr = CheckpointManager(str(tmp_path / "c"), max_to_keep=10)
        mgr.save(1, _tree(1.0), force=True)
        mgr.save(2, _tree(2.0), force=True)
        faults.truncate_file(mgr._manifest_path(2))
        seen = []
        watcher = CheckpointWatcher(
            mgr, template=_tree(0.0),
            on_reload=lambda tree, step: seen.append(step),
            poll_interval_s=0.05)
        # The corrupt newest step is skipped, the previous good step
        # loads, and the failure backoff is NOT charged.
        assert watcher.check_once() == 1
        assert watcher._fail_streak == 0
        assert seen == [1]
        assert "serve_skipped_unverified_total 1" in watcher.metrics.render()
        # A verified newer step loads on the next poll.
        mgr.save(3, _tree(3.0), force=True)
        assert watcher.check_once() == 3
        assert seen == [1, 3]


# ---------------------------------------------------------------------------
# Recovery-time budget ledger
# ---------------------------------------------------------------------------

class TestRecoveryLedger:
    def test_phase_attribution_and_metric(self):
        reg = MetricsRegistry()
        ledger = step_stats.GoodputLedger(registry=reg)
        ledger.charge_phase("restore", 1.5)
        ledger.charge_phase("rendezvous", 0.5)
        ledger.charge_phase("restore", 0.5)
        assert ledger.recovery_seconds("restore") == 2.0
        assert ledger.recovery_seconds() == 2.5
        assert ledger.recovery_snapshot() == {
            "restore": 2.0, "rendezvous": 0.5}
        counter = reg.get("hvdt_recovery_seconds")
        assert counter.value(phase="restore") == 2.0
        assert counter.value(phase="rendezvous") == 0.5
        # Non-overlapped phases also charge the goodput bill.
        assert ledger.lost_seconds("restore") == 2.0

    def test_unknown_phase_raises(self):
        ledger = step_stats.GoodputLedger(registry=MetricsRegistry())
        with pytest.raises(ValueError, match="checkpoint_snapshot"):
            ledger.charge_phase("coffee_break", 1.0)

    def test_overlapped_phase_not_charged_to_goodput(self):
        now = [100.0]
        ledger = step_stats.GoodputLedger(registry=MetricsRegistry(),
                                          clock=lambda: now[0])
        ledger.charge_phase("checkpoint_write", 5.0, overlapped=True)
        now[0] += 10.0
        assert ledger.recovery_seconds("checkpoint_write") == 5.0
        assert ledger.lost_seconds() == 0.0
        assert ledger.fraction() == 1.0

    def test_phase_context_manager(self):
        now = [0.0]
        ledger = step_stats.GoodputLedger(registry=MetricsRegistry(),
                                          clock=lambda: now[0])
        with ledger.phase("rendezvous"):
            now[0] += 3.0
        assert ledger.recovery_seconds("rendezvous") == 3.0

    def test_recovery_ledger_zero_overhead_contract(self, monkeypatch):
        monkeypatch.delenv("HVDT_TELEMETRY", raising=False)
        step_stats.reset_recovery_ledger()
        assert step_stats.recovery_ledger() is None
        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        ledger = step_stats.recovery_ledger()
        assert ledger is not None
        assert step_stats.recovery_ledger() is ledger


# ---------------------------------------------------------------------------
# Peer store
# ---------------------------------------------------------------------------

@pytest.fixture()
def kv_server():
    srv = RendezvousServer(port=0, addr="127.0.0.1")
    srv.start()
    yield srv
    srv.stop()


def _client(srv):
    return KVClient("127.0.0.1", srv.port, srv.secret)


class TestPeerStore:
    def test_commit_restore_roundtrip(self, kv_server):
        kv = _client(kv_server)
        ps = PeerStore(kv, rank=1, size=2, registry=MetricsRegistry())
        snap = {"w": np.arange(4.0), "batch": 7}
        assert ps.commit(7, snap)
        assert ps.peek_step() == 7
        got, step = ps.restore()
        assert step == 7
        np.testing.assert_array_equal(got["w"], snap["w"])
        assert ps.restore_count() == 1

    def test_corrupt_replica_is_a_miss(self, kv_server):
        kv = _client(kv_server)
        reg = MetricsRegistry()
        ps = PeerStore(kv, rank=0, size=1, registry=reg)
        ps.commit(3, {"x": 1})
        kv_server.put_local("/peer/0", b"HVPS1\x00\x00\x00\x05kaput")
        assert ps.restore() is None
        assert reg.get("hvdt_peer_miss_total").total() == 1
        assert ps.restore_count() == 0

    def test_ram_replica_served_back_after_kv_loss(self, kv_server):
        """rank 0 mirrors rank 1's snapshot; when the KV forgets it,
        serve_replicas re-offers the RAM copy and rank 1 restores."""
        kv = _client(kv_server)
        ps0 = PeerStore(kv, rank=0, size=2, registry=MetricsRegistry())
        ps1 = PeerStore(kv, rank=1, size=2, registry=MetricsRegistry())
        ps1.commit(9, {"w": np.ones(2)})
        assert ps0.refresh_replica() == 9        # rank 0 watches rank 1
        with kv_server.lock:
            kv_server.store.pop("/peer/1")
        assert ps1.restore() is None             # KV lost it...
        assert ps0.serve_replicas() == 1         # ...RAM tier re-offers
        got, step = ps1.restore()
        assert step == 9

    def test_newer_commit_refreshes_replica(self, kv_server):
        kv = _client(kv_server)
        ps0 = PeerStore(kv, rank=0, size=2, registry=MetricsRegistry())
        ps1 = PeerStore(kv, rank=1, size=2, registry=MetricsRegistry())
        ps1.commit(1, {"v": 1})
        ps0.refresh_replica()
        ps1.commit(2, {"v": 2})
        assert ps0.refresh_replica() == 2
        got, step = ps1.restore()
        assert (got["v"], step) == (2, 2)

    def test_zero_shard_rows_roundtrip(self, kv_server):
        from horovod_tpu.ops import zero as zero_mod

        kv = _client(kv_server)
        ps = PeerStore(kv, rank=2, size=4, registry=MetricsRegistry())
        state = zero_mod.ZeroSgdState(
            trace=(jnp.arange(12, dtype=jnp.float32).reshape(4, 3),))
        assert ps.commit_zero_shard(state, step=5)
        blank = zero_mod.ZeroSgdState(
            trace=(jnp.zeros((4, 3), jnp.float32),))
        restored, step = ps.restore_zero_shard(blank)
        assert step == 5
        got = np.asarray(restored.trace[0])
        np.testing.assert_array_equal(got[2], [6.0, 7.0, 8.0])
        np.testing.assert_array_equal(got[0], 0.0)   # other rows untouched

    def test_env_contract(self, kv_server, monkeypatch):
        # Unset: None, no wrappers anywhere.
        assert peer_store_mod.get_peer_store() is None
        monkeypatch.setenv("HVDT_PEER_STORE", "1")
        # Knob set but no rendezvous env: still None (no transport).
        monkeypatch.delenv("HVDT_RENDEZVOUS_ADDR", raising=False)
        peer_store_mod.reset()
        assert peer_store_mod.get_peer_store() is None
        monkeypatch.setenv("HVDT_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HVDT_RENDEZVOUS_PORT", str(kv_server.port))
        monkeypatch.setenv("HVDT_SECRET", kv_server.secret.hex())
        monkeypatch.setenv("HVDT_RANK", "1")
        monkeypatch.setenv("HVDT_SIZE", "4")
        ps = peer_store_mod.get_peer_store()
        assert ps is not None
        assert (ps.rank, ps.size, ps.watched_peer()) == (1, 4, 2)
        assert peer_store_mod.get_peer_store() is ps   # cached

    def test_jax_state_commit_and_peer_resume(self, kv_server, monkeypatch,
                                              tmp_path):
        """JaxState integration: commit publishes to the peer tier; a
        fresh state resumes from it (ties beat the disk tier) and
        records restored_from."""
        import horovod_tpu as hvd

        monkeypatch.setenv("HVDT_PEER_STORE", "1")
        monkeypatch.setenv("HVDT_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HVDT_RENDEZVOUS_PORT", str(kv_server.port))
        monkeypatch.setenv("HVDT_SECRET", kv_server.secret.hex())
        monkeypatch.setenv("HVDT_RANK", "0")
        monkeypatch.setenv("HVDT_SIZE", "1")
        peer_store_mod.reset()
        path = str(tmp_path / "state.pkl")

        class LocalState(hvd.elastic.JaxState):
            def sync(self):
                self.save()

        st = LocalState(path=path, w=np.zeros(2, np.float32), batch=0)
        assert st.restored_from is None
        st.w = st.w + 4.0
        st.batch = 6
        st.commit()
        st2 = LocalState(path=path, w=np.zeros(2, np.float32), batch=0)
        assert st2.restored_from == "peer"
        assert st2.batch == 6
        np.testing.assert_allclose(st2.w, 4.0)
        # Disk wins when it is strictly newer (peer publish lost).
        st2.batch = 9
        st2.save()
        st2.persist()
        st3 = LocalState(path=path, w=np.zeros(2, np.float32), batch=0)
        assert st3.restored_from == "disk"
        assert st3.batch == 9


# ---------------------------------------------------------------------------
# Deterministic data resume: sampler cursor + loader seek (satellite)
# ---------------------------------------------------------------------------

class TestSamplerCursor:
    def test_record_batch_advances_cursor(self):
        from horovod_tpu.data.sampler import ElasticSampler

        s = ElasticSampler(100, shuffle=False, rank=0, size=4)
        assert s.cursor() == {"epoch": 0, "batch_idx": 0}
        for i in range(3):
            s.record_batch(i, 8)
        assert s.cursor() == {"epoch": 0, "batch_idx": 3}
        assert s.state_dict()["batch_idx"] == 3
        s.set_epoch(1)
        assert s.cursor() == {"epoch": 1, "batch_idx": 0}

    def test_cursor_survives_shrink_grow_resize(self):
        """4 -> 2 -> 4: the cursor rides load_state_dict across world
        resizes and the remaining work repartitions each time."""
        from horovod_tpu.data.sampler import ElasticSampler

        s4 = ElasticSampler(96, shuffle=False, rank=0, size=4)
        for i in range(2):
            s4.record_batch(i, 8)        # 2 batches * 8 * 4 ranks = 64
        state = s4.state_dict()
        assert state == {"epoch": 0, "processed_num": 64, "batch_idx": 2}

        s2 = ElasticSampler(96, shuffle=False, rank=1, size=2)
        s2.load_state_dict(state)
        assert s2.cursor() == {"epoch": 0, "batch_idx": 2}
        assert len(s2.remaining_indices) == 96 - 64
        assert len(s2) == 16                       # 32 remaining / 2 ranks
        s2.record_batch(2, 8)                      # 64 + 8*2 = 80

        s4b = ElasticSampler(96, shuffle=False, rank=3, size=4)
        s4b.load_state_dict(s2.state_dict())
        assert s4b.cursor() == {"epoch": 0, "batch_idx": 3}
        assert len(s4b.remaining_indices) == 96 - 80
        assert len(s4b) == 4
        # Remaining indices are exactly the unprocessed tail.
        assert s4b.remaining_indices[0] == 80

    def test_pre_cursor_state_dict_accepted(self):
        from horovod_tpu.data.sampler import ElasticSampler

        s = ElasticSampler(10, shuffle=False, rank=0, size=1)
        s.load_state_dict({"epoch": 2, "processed_num": 4})
        assert s.cursor() == {"epoch": 2, "batch_idx": 0}


class TestLoaderSeek:
    def test_seek_skips_unprocessed(self):
        from horovod_tpu.data.loader import BaseDataLoader

        processed = []

        class Loader(BaseDataLoader):
            def __len__(self):
                return 8

            def _iterate(self):
                yield from range(8)

            def _process_batch(self, batch):
                processed.append(batch)
                return batch * 10

        ld = Loader()
        assert ld.seek({"epoch": 0, "batch_idx": 5}) is ld
        assert list(ld) == [50, 60, 70]
        # Skipped batches never hit _process_batch (no wasted decode /
        # device transfer on the replay window).
        assert processed == [5, 6, 7]

    def test_seek_forms_and_validation(self):
        from horovod_tpu.data.loader import AsyncDataLoader

        ld = AsyncDataLoader(list(range(4)), async_loader_queue_size=0)
        assert list(ld.seek((1, 2))) == [2, 3]
        assert list(ld.seek(3)) == [3]
        with pytest.raises(ValueError, match=">= 0"):
            ld.seek(-1)

    def test_async_reiteration_after_seek(self):
        """The satellite case: an AsyncDataLoaderMixin iterates after a
        seek (fast-forward through the producer queue), and the NEXT
        iteration is a fresh full epoch — seek is one-shot."""
        from horovod_tpu.data.loader import AsyncDataLoader

        ld = AsyncDataLoader(list(range(10)), async_loader_queue_size=4)
        ld.seek({"epoch": 0, "batch_idx": 6})
        assert list(ld) == [6, 7, 8, 9]
        assert list(ld) == list(range(10))
        ld.seek({"epoch": 0, "batch_idx": 9})
        assert list(ld) == [9]
        ld.close()

    def test_seek_past_end_yields_nothing(self):
        from horovod_tpu.data.loader import AsyncDataLoader

        ld = AsyncDataLoader(list(range(3)), async_loader_queue_size=2)
        ld.seek(7)
        assert list(ld) == []
        ld.close()

    def test_seek_charges_replay_phase(self, monkeypatch):
        from horovod_tpu.data.loader import AsyncDataLoader

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        step_stats.reset_recovery_ledger()
        ld = AsyncDataLoader(list(range(6)), async_loader_queue_size=0)
        ld.seek(4)
        assert list(ld) == [4, 5]
        ledger = step_stats.recovery_ledger()
        assert ledger.recovery_snapshot().get("replay", 0) >= 0
        assert "replay" in ledger.recovery_snapshot()


# ---------------------------------------------------------------------------
# CLI / knob wiring
# ---------------------------------------------------------------------------

class TestCliWiring:
    def test_goodput_flags_forward_as_env(self):
        from horovod_tpu.runner.launch import knob_env_for, parse_args

        args = parse_args(["--async-ckpt", "--peer-store",
                           "--ckpt-snapshot-budget-s", "2.5",
                           "-np", "2", "--", "python", "train.py"])
        env = knob_env_for(args)
        assert env["HVDT_ASYNC_CKPT"] == "1"
        assert env["HVDT_PEER_STORE"] == "1"
        assert env["HVDT_CKPT_SNAPSHOT_BUDGET_S"] == "2.5"

    def test_yaml_resilience_section(self, tmp_path):
        from horovod_tpu.runner.config_parser import (apply_config_file,
                                                      env_from_args)
        from horovod_tpu.runner.launch import parse_args

        cfg = os.path.join(str(tmp_path), "c.yaml")
        with open(cfg, "w") as f:
            f.write("resilience:\n  async_ckpt: true\n  peer_store: true\n")
        args = parse_args(["--config-file", cfg, "--", "python", "t.py"])
        file_values = apply_config_file(args, cfg)
        env = env_from_args(args, file_values, base_env={})
        assert env["HVDT_ASYNC_CKPT"] == "1"
        assert env["HVDT_PEER_STORE"] == "1"

    def test_goodput_knobs_registered(self):
        from horovod_tpu.common import config

        for name in ("HVDT_ASYNC_CKPT", "HVDT_PEER_STORE",
                     "HVDT_CKPT_SNAPSHOT_BUDGET_S"):
            assert name in config.KNOBS
        assert config.KNOBS["HVDT_CKPT_SNAPSHOT_BUDGET_S"].default == 1.0


# ---------------------------------------------------------------------------
# Multiprocess acceptance scenarios
# ---------------------------------------------------------------------------

def _records(log_path):
    """Parsed log lines of tests/data/goodput_main.py."""
    out = []
    with open(log_path) as f:
        for ln in f:
            parts = ln.split()
            if not parts:
                continue
            out.append(parts)
    return out


def _scenario_env(tmp_path, extra):
    env = dict(os.environ)
    env.pop("HVDT_TELEMETRY", None)
    env.update({
        "ELASTIC_TEST_LOG": os.path.join(tmp_path, "progress.log"),
        "ELASTIC_TEST_STATE": os.path.join(tmp_path, "state.pkl"),
        "GOODPUT_CKPT_DIR": os.path.join(tmp_path, "ckpts"),
        "ELASTIC_TEST_BATCHES": "16",
        "ELASTIC_TEST_SLEEP": "0.08",
        "ELASTIC_TEST_HB_TIMEOUT": "5",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "HVDT_ASYNC_CKPT": "1",
        "HVDT_PEER_STORE": "1",
        "HVDT_FAULT_JOURNAL": os.path.join(tmp_path, "fault_journal"),
        "HVDT_ELASTIC_BLACKLIST_COOLDOWN_S": "1",
    })
    env.update(extra)
    return env


def _run_scenario(spawn, tmp_path, env, discover_lines, port, min_np,
                  max_np, timeout=200):
    discover = os.path.join(str(tmp_path), "discover.sh")
    with open(discover, "w") as f:
        f.write("#!/bin/sh\n" + discover_lines + "\n")
    os.chmod(discover, 0o755)
    proc = spawn(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", str(min_np), "--max-np", str(max_np),
         "--host-discovery-script", discover,
         "--coordinator-port", str(port),
         "--", sys.executable, os.path.join(REPO, "tests", "data",
                                            "goodput_main.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"goodput scenario hung:\n{out.decode()[-3000:]}")
    return proc.returncode, out.decode()


def _assert_goodput_invariants(records, text, total, budget_s=30.0,
                               killed_ranks=(1,), crash_batch=10):
    data = [(int(r[1]), int(r[3]), int(r[4]))
            for r in records if r[0] == "data"]
    restores = [(int(r[1]), r[2], int(r[3]), int(r[4]))
                for r in records if r[0] == "restore"]
    # Every restore after the kill came from the peer RAM tier — the
    # disk tier was never needed (hvdt_peer_restore_total > 0 rides the
    # restore record's counter column).
    assert restores, "no rank ever recorded a restore"
    assert all(tier == "peer" for _, tier, _, _ in restores), restores
    assert not any(tier == "disk" for _, tier, _, _ in restores)
    assert any(total_col > 0 for _, _, _, total_col in restores)
    # Committed batch ids are gap-free and replay-free per rank: each
    # bid processed at most twice overall (the at-most-one-uncommitted
    # batch a crash window may legitimately replay), every bid covered,
    # and the id stream never goes backwards by more than that window.
    by_rank = {}
    for rank, bid, ts in data:
        by_rank.setdefault(rank, []).append((ts, bid))
    for rank, rows in by_rank.items():
        bids = [b for _, b in sorted(rows)]
        assert sorted(set(bids)) == list(range(total)), (
            f"rank {rank} bid coverage broken: {bids}")
        from collections import Counter

        dupes = {b: c for b, c in Counter(bids).items() if c > 2}
        assert not dupes, f"rank {rank} replayed committed batches: {dupes}"
    # Recovery budget: kill -> first-new-committed-batch wall clock for
    # the killed rank stays under the 30 s SLO.
    for rank in killed_ranks:
        rows = sorted(by_rank[rank])
        pre = [ts for ts, b in rows if b == crash_batch - 1]
        post = [ts for ts, b in rows if b == crash_batch]
        assert pre and post, f"rank {rank} never crossed the crash point"
        recovery_s = (min(post) - min(pre)) / 1000.0
        assert recovery_s < budget_s, (
            f"rank {rank} recovery took {recovery_s:.1f}s "
            f"(budget {budget_s}s)")
    # The async writer landed a verified LAST_GOOD under the launcher.
    ckpt = [int(r[2]) for r in records if r[0] == "ckpt"]
    assert ckpt and max(ckpt) >= 5, f"async checkpoint never landed: {ckpt}"
    # Loss continuity: every batch applied exactly once across the kill.
    assert f"final: batches={total} w0={total / 10:.1f}" in text


def test_kill_rank1_recovers_from_peer_ram_within_budget(tmp_path, spawn):
    """Acceptance scenario 1: crash@step=10:rank=1 under
    HVDT_ASYNC_CKPT=1 + HVDT_PEER_STORE=1 — recovery restores both
    ranks from the peer RAM tier (zero disk restores), inside the 30 s
    budget, with gap-free replay-free committed batches."""
    env = _scenario_env(str(tmp_path), {
        "HVDT_FAULT_PLAN": "crash@step=10:rank=1",
    })
    rc, text = _run_scenario(spawn, tmp_path, env, "echo localhost:2",
                             port=29791, min_np=2, max_np=2)
    assert rc == 0, text[-3000:]
    records = _records(env["ELASTIC_TEST_LOG"])
    _assert_goodput_invariants(records, text, total=16)
    # The driver attributes the rendezvous leg of the recovery budget.
    assert "rendezvous took" in text


@pytest.mark.slow
def test_pod_kill_recovers_from_peer_ram(tmp_path, spawn):
    """Acceptance scenario 2 (pod variant): pod_crash@step=10:pod=podB
    kills both ranks of pod B; every respawned rank restores from the
    peer RAM tier and the committed batch stream stays gap-free.

    Marked ``slow``: the rank-kill scenario above covers the same
    goodput machinery inside tier-1's 870 s budget; this whole-pod leg
    runs in the pre-merge smoke service (docker-compose test-smoke /
    ci/gen-matrix.sh --smoke), which carries no ``-m 'not slow'``
    filter."""
    env = _scenario_env(str(tmp_path), {
        "HVDT_FAULT_PLAN": "pod_crash@step=10:pod=podB",
        "ELASTIC_TEST_SLEEP": "0.1",
    })
    rc, text = _run_scenario(
        spawn, tmp_path, env,
        "echo localhost:2@podA\necho 127.0.0.1:2@podB",
        port=29796, min_np=2, max_np=4)
    assert rc == 0, text[-3000:]
    records = _records(env["ELASTIC_TEST_LOG"])
    _assert_goodput_invariants(records, text, total=16,
                               killed_ranks=(2, 3))
    # The two pod-B exits collapsed into ONE pod-removal event.
    assert text.count("pod-removal event for pod podB") == 1
