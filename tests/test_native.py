"""Native C++ core tests: TCP collective backend, Adasum VHDD, timeline.

Multi-rank coverage runs N ranks as N threads in this process — the
ctypes calls block in C++ with the GIL released, so a full socket mesh on
localhost exercises the real wire path (analog of the reference's
2-process mpirun tier, SURVEY.md §4, without spawning processes).
"""

import json
import os
import socket
import threading

import numpy as np
import pytest

from horovod_tpu import native


pytestmark = pytest.mark.skipif(
    not native.available(), reason="native core not built/available")


def _free_ports(n):
    socks = []
    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def run_ranks(size, fn):
    """Run fn(group, rank) on `size` connected ranks, return rank-ordered
    results; re-raises the first worker exception."""
    ports = _free_ports(size)
    addrs = [f"127.0.0.1:{p}" for p in ports]
    results = [None] * size
    errors = []

    def worker(rank):
        try:
            with native.TcpProcessGroup(rank, size, addrs,
                                        timeout_ms=15000) as g:
                results[rank] = fn(g, rank)
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append((rank, e))

    threads = [threading.Thread(target=worker, args=(r,), daemon=True)
               for r in range(size)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    if errors:
        raise errors[0][1]
    assert all(not t.is_alive() for t in threads), "worker hung"
    return results


@pytest.mark.parametrize("size", [2, 3, 4])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                   np.int64, np.uint8])
def test_allreduce_sum(size, dtype):
    n = 1000

    def fn(g, rank):
        x = (np.arange(n) % 17 + rank).astype(dtype)
        return g.allreduce(x)

    results = run_ranks(size, fn)
    base = np.arange(n) % 17
    expected = (base * size + sum(range(size))).astype(dtype)
    for r in results:
        np.testing.assert_array_equal(r, expected)


@pytest.mark.parametrize("op,npop", [("MIN", np.minimum), ("MAX", np.maximum)])
def test_allreduce_minmax(op, npop):
    from horovod_tpu.common.types import ReduceOp

    size = 3
    rng = np.random.default_rng(0)
    inputs = [rng.normal(size=37).astype(np.float32) for _ in range(size)]

    def fn(g, rank):
        return g.allreduce(inputs[rank], op=ReduceOp[op])

    results = run_ranks(size, fn)
    expected = inputs[0]
    for x in inputs[1:]:
        expected = npop(expected, x)
    for r in results:
        np.testing.assert_allclose(r, expected, rtol=1e-6)


def test_allreduce_average():
    from horovod_tpu.common.types import ReduceOp

    size = 4

    def fn(g, rank):
        return g.allreduce(np.full(5, rank + 1, np.float32),
                           op=ReduceOp.AVERAGE)

    for r in run_ranks(size, fn):
        np.testing.assert_allclose(r, np.full(5, 2.5, np.float32))


def test_allreduce_bfloat16():
    import ml_dtypes

    size = 2
    bf16 = np.dtype(ml_dtypes.bfloat16)

    def fn(g, rank):
        return g.allreduce(np.full(64, 1.5 + rank, bf16))

    for r in run_ranks(size, fn):
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.full(64, 4.0, np.float32))


def test_allreduce_small_count_more_ranks():
    # count < size exercises zero-length ring segments
    size = 4

    def fn(g, rank):
        return g.allreduce(np.array([float(rank)], np.float32))

    for r in run_ranks(size, fn):
        np.testing.assert_allclose(r, [6.0])


@pytest.mark.parametrize("size", [2, 3])
def test_allgather_variable_rows(size):
    def fn(g, rank):
        t = np.full((rank + 1, 3), rank, np.float32)
        return g.allgather(t)

    expected = np.concatenate(
        [np.full((r + 1, 3), r, np.float32) for r in range(size)])
    for r in run_ranks(size, fn):
        np.testing.assert_array_equal(r, expected)


def test_broadcast():
    size = 3
    payload = np.arange(11, dtype=np.int64) * 7

    def fn(g, rank):
        x = payload.copy() if rank == 1 else np.zeros(11, np.int64)
        return g.broadcast(x, root=1)

    for r in run_ranks(size, fn):
        np.testing.assert_array_equal(r, payload)


@pytest.mark.parametrize("size", [2, 3])
def test_alltoall_uneven_splits(size):
    # rank r sends (d+1) rows to destination d, each row stamped (src, dst)
    def fn(g, rank):
        rows = []
        splits = []
        for dst in range(size):
            k = dst + 1
            splits.append(k)
            rows.append(np.full((k, 2), [rank, dst], np.int32))
        return g.alltoall(np.concatenate(rows), splits=splits)

    results = run_ranks(size, fn)
    for rank, out in enumerate(results):
        expected = np.concatenate(
            [np.full((rank + 1, 2), [src, rank], np.int32)
             for src in range(size)])
        np.testing.assert_array_equal(out, expected)


def test_barrier_and_rank_size():
    size = 3

    def fn(g, rank):
        assert g.rank == rank and g.size == size
        g.barrier()
        return True

    assert run_ranks(size, fn) == [True] * size


# ---- Adasum ----


def test_adasum_combine_math():
    # orthogonal vectors -> plain sum; identical vectors -> average... of
    # the *pair*: a' = (1 - 1/2)a + (1 - 1/2)a = a  (scale invariance).
    a = np.array([1.0, 0.0], np.float32)
    b = np.array([0.0, 1.0], np.float32)
    np.testing.assert_allclose(native.adasum_combine(a, b), [1.0, 1.0])
    c = np.array([2.0, 3.0], np.float32)
    np.testing.assert_allclose(native.adasum_combine(c, c), c, rtol=1e-6)


@pytest.mark.parametrize("size", [2, 4])
def test_adasum_allreduce_matches_pairwise_tree(size):
    rng = np.random.default_rng(1)
    inputs = [rng.normal(size=64).astype(np.float32) for _ in range(size)]

    def fn(g, rank):
        return g.adasum_allreduce(inputs[rank])

    results = run_ranks(size, fn)
    # All ranks agree.
    for r in results[1:]:
        np.testing.assert_allclose(r, results[0], rtol=1e-5, atol=1e-6)
    # VHDD equals the recursive pairwise combine tree on full vectors.
    level = [x.astype(np.float64) for x in inputs]
    while len(level) > 1:
        level = [
            native.adasum_combine(level[i], level[i + 1])
            for i in range(0, len(level), 2)
        ]
    np.testing.assert_allclose(results[0], level[0].astype(np.float32),
                               rtol=1e-4, atol=1e-5)


def test_adasum_requires_power_of_two():
    def fn(g, rank):
        g.adasum_allreduce(np.ones(4, np.float32))

    with pytest.raises(native.NativeError, match="power-of-two"):
        run_ranks(3, fn)


# ---- timeline ----


def test_native_timeline_writes_chrome_trace(tmp_path):
    path = os.path.join(tmp_path, "tl.json")
    with native.NativeTimeline(path) as tl:
        tl.begin("grad/layer0", "NEGOTIATE_ALLREDUCE")
        tl.end("grad/layer0", "NEGOTIATE_ALLREDUCE")
        tl.complete("grad/layer0", "ALLREDUCE", 100, 250,
                    args={"bytes": 4096})
        tl.instant("grad/layer1", "CYCLE_START")
    raw = open(path).read().rstrip().rstrip(",")
    events = json.loads(raw + "]")
    names = [e["name"] for e in events]
    assert "process_name" in names  # pid metadata rows
    assert "NEGOTIATE_ALLREDUCE" in names and "ALLREDUCE" in names
    x = [e for e in events if e["ph"] == "X"][0]
    assert x["dur"] == 250 and x["args"]["bytes"] == 4096
    # two distinct tensors -> two pid rows
    pids = {e["pid"] for e in events if e["ph"] != "M"}
    assert len(pids) == 2


# ---- HVDT_CPU_OPERATIONS=tcp backend wiring ----


class _FakeProcessSet:
    """Stands in for common.process_sets.ProcessSet in backend tests."""

    def __init__(self, set_id, my_rank, ranks):
        self.id = set_id
        self.ranks = list(ranks)
        self._my = my_rank

    def rank(self):
        return self.ranks.index(self._my)

    def size(self):
        return len(self.ranks)


def test_tcp_backend_dispatch(monkeypatch):
    from horovod_tpu.ops import tcp_backend
    from horovod_tpu.ops import host_collectives as hostc
    from horovod_tpu.common.types import ReduceOp

    size = 2
    ports = _free_ports(size)
    monkeypatch.setenv("HVDT_CPU_OPERATIONS", "tcp")
    monkeypatch.setenv(
        "HVDT_TCP_ADDRS", ",".join(f"127.0.0.1:{p}" for p in ports))
    assert tcp_backend.enabled()

    results = [None] * size
    errors = []

    def worker(rank):
        try:
            ps = _FakeProcessSet(0, rank, range(size))
            r1 = hostc.host_allreduce(
                np.full(9, rank + 1.0, np.float32), ps, ReduceOp.SUM)
            r2 = hostc.host_broadcast(
                np.arange(4.0, dtype=np.float32) if rank == 0 else None,
                0, ps, (4,), np.float32)
            r3 = hostc.host_allgather(
                np.full((rank + 1, 2), rank, np.int32), ps,
                [1, 2])
            results[rank] = (r1, r2, r3)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,), daemon=True)
               for r in range(size)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    # Reset the cached groups before asserting (other tests run clean).
    tcp_backend.shutdown_groups()
    if errors:
        raise errors[0]
    for r1, r2, r3 in results:
        np.testing.assert_allclose(r1, np.full(9, 3.0, np.float32))
        np.testing.assert_allclose(r2, np.arange(4.0, dtype=np.float32))
        expected = np.concatenate([np.full((1, 2), 0, np.int32),
                                   np.full((2, 2), 1, np.int32)])
        np.testing.assert_array_equal(r3, expected)


def test_failed_build_is_an_error_even_with_a_binary_on_disk(monkeypatch):
    """The .so is a build artifact: when make fails in a source tree, the
    older library still lying in native/ must not stand in for it."""
    assert os.path.exists(native._LIB_PATH)     # built by the tests above
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", None)
    monkeypatch.setattr(native, "_build", lambda: "make failed (rc 2): boom")
    with pytest.raises(native.NativeError, match="build failed.*boom"):
        native.load()
    assert not native.available()
