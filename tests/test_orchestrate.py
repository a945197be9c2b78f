"""Orchestrator tests: Executor pool, RayExecutor adapter (JaxEstimator:
tests/test_orchestrate_estimators.py).

Real subprocess workers on localhost — the analog of the reference's
test/integration tier (test_static_run.py, test_ray.py local-mode runs).
Worker processes are lightweight (no JAX import unless the dispatched fn
does), so the pool spins up in ~a second.
"""

import os

import numpy as np
import pytest

from horovod_tpu.orchestrate import Executor, RayExecutor
from horovod_tpu.orchestrate.executor import WorkerError


def _rank_size():
    return (int(os.environ["HVDT_RANK"]), int(os.environ["HVDT_SIZE"]))


def _square(x):
    return int(os.environ["HVDT_RANK"]) * x


def _boom():
    raise RuntimeError("intentional worker failure")


@pytest.fixture(scope="module")
def pool2():
    """One two-worker pool for the tests that only dispatch to one: a
    pool's start (each worker imports the package) is nearly all of such
    a test's seconds.  Tests that kill a worker or need another size
    start their own."""
    with Executor(num_workers=2, start_timeout=30) as ex:
        yield ex


class TestExecutor:
    def test_run_collects_rank_ordered_results(self):
        with Executor(num_workers=3, start_timeout=30) as ex:
            assert ex.run(_rank_size) == [(0, 3), (1, 3), (2, 3)]
            # Pool is persistent: second dispatch reuses the workers.
            assert ex.run(_square, args=(10,)) == [0, 10, 20]

    def test_worker_exception_propagates(self, pool2):
        with pytest.raises(WorkerError, match="intentional"):
            pool2.run(_boom)
        # Pool survives a failed call.
        assert pool2.run(_rank_size) == [(0, 2), (1, 2)]

    def test_run_single(self, pool2):
        assert pool2.run_single(_rank_size, rank=1) == (1, 2)

    def test_env_passthrough(self):
        with Executor(num_workers=1, env={"MY_FLAG": "42"},
                      start_timeout=30) as ex:
            out = ex.run(lambda: os.environ.get("MY_FLAG"))
            assert out == ["42"]


def _np_mean(x):
    return float(np.mean(x) + int(os.environ["HVDT_RANK"]))


class TestRayExecutorAdapter:
    def test_local_fallback_runs(self):
        ex = RayExecutor(num_workers=2)
        ex.start()
        try:
            assert ex.run(_rank_size) == [(0, 2), (1, 2)]
            assert ex.execute(_np_mean, np.ones(4)) == [1.0, 2.0]
        finally:
            ex.shutdown()

    def test_num_hosts_api(self):
        ex = RayExecutor(num_hosts=2, num_workers_per_host=2)
        assert ex.num_workers == 4

    def test_requires_worker_count(self):
        with pytest.raises(ValueError, match="num_workers"):
            RayExecutor()

    def test_run_remote_thunk(self):
        ex = RayExecutor(num_workers=1)
        ex.start()
        try:
            pending = ex.run_remote(_rank_size)
            assert pending() == [(0, 1)]
        finally:
            ex.shutdown()


def _die():
    os._exit(17)


class TestExecutorFailFast:
    def test_dead_worker_fails_fast_not_timeout(self):
        import time

        with Executor(num_workers=2, start_timeout=30) as ex:
            t0 = time.monotonic()
            with pytest.raises(WorkerError, match="exited with code 17"):
                ex.run(_die, timeout=120.0)
            assert time.monotonic() - t0 < 30, "should not wait full timeout"


def _take(tag, payload):
    return (int(os.environ["HVDT_RANK"]), tag, int(np.sum(payload)))


class TestPerRankArgs:
    def test_each_worker_gets_its_shard(self, pool2):
        shards = [np.full(3, r + 1) for r in range(2)]
        out = pool2.run(_take, args=("s",),
                        per_rank_args=[(s,) for s in shards])
        assert out == [(0, "s", 3), (1, "s", 6)]

    def test_length_mismatch_raises(self, pool2):
        with pytest.raises(ValueError, match="one entry per worker"):
            pool2.run(_take, per_rank_args=[(1,)])
