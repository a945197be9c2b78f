"""Sparse allreduce + torch interop tests (ref analogs:
test_torch.py sparse_allreduce cases; torch binding API tests)."""

import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map


class TestSparseAllreduce:
    def test_eager_roundtrip_and_dense(self, hvd):
        from horovod_tpu.ops.sparse import sparse_allreduce

        g = sparse_allreduce(np.array([1, 3, 1]),
                             np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]],
                                      np.float32),
                             dense_shape=(5, 2), name="sp0")
        # size-1 world: average == identity; duplicates summed in dense
        dense = g.to_dense()
        np.testing.assert_allclose(dense[1], [6.0, 8.0])
        np.testing.assert_allclose(dense[3], [3.0, 4.0])
        np.testing.assert_allclose(dense[0], [0.0, 0.0])

    def test_async_resolver(self, hvd):
        from horovod_tpu.common.types import ReduceOp
        from horovod_tpu.ops.sparse import sparse_allreduce_async

        resolve = sparse_allreduce_async(
            np.array([0]), np.array([[2.0]], np.float32), (3, 1),
            name="sp1", op=ReduceOp.SUM)
        g = resolve()
        np.testing.assert_allclose(g.to_dense(), [[2.0], [0.0], [0.0]])

    def test_jit_path_gathers_and_averages(self, hvd):
        from horovod_tpu.ops.sparse import sparse_allreduce_jit

        mesh = hvd.mesh()
        n = mesh.devices.size

        def local(idx, val):
            return sparse_allreduce_jit(idx, val, axis="dp")

        idx = jnp.arange(n, dtype=jnp.int32)          # one row per shard
        val = jnp.ones((n, 2), jnp.float32) * 4.0
        gi, gv = jit_shard_map(
            local, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp")))(idx, val)
        assert gi.shape == (n * n,)  # each shard now holds all indices
        np.testing.assert_allclose(np.asarray(gv)[0], [0.5, 0.5])  # 4/8


class TestTorchInterop:
    def test_allreduce_roundtrip(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.arange(6, dtype=torch.float32).reshape(2, 3)
        out = hvd_torch.allreduce(t, name="t0")
        assert isinstance(out, torch.Tensor)
        assert torch.allclose(out, t)

    def test_broadcast_parameters_inplace(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        model = torch.nn.Linear(4, 2)
        before = {k: v.clone() for k, v in model.state_dict().items()}
        hvd_torch.broadcast_parameters(model.state_dict(), root_rank=0)
        for k, v in model.state_dict().items():
            assert torch.allclose(v, before[k])

    def test_broadcast_optimizer_state(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        model = torch.nn.Linear(3, 1)
        opt = torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9)
        loss = model(torch.ones(2, 3)).sum()
        loss.backward()
        opt.step()
        hvd_torch.broadcast_optimizer_state(opt, root_rank=0)

    def test_alltoall(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.arange(4, dtype=torch.float32)
        out, splits = hvd_torch.alltoall(t, name="a2a0")
        assert torch.allclose(out, t)
        assert splits == [4]

    def test_non_cpu_tensor_rejected(self, hvd):
        torch = pytest.importorskip("torch")
        from unittest import mock

        from horovod_tpu.interop.torch import _to_np

        fake = mock.Mock(spec=torch.Tensor)
        fake.device.type = "meta"
        with pytest.raises(ValueError, match="CPU tensors only"):
            _to_np(fake)
        # sanity: the happy path still converts
        assert _to_np(torch.ones(2)).shape == (2,)


class TestTorchInteropParity:
    """Reference torch/mpi_ops.py surface: in-place + async variants,
    grouped ops, sparse handle, join/barrier/poll, torch-typed
    synchronize (ref: torch/__init__.py import list)."""

    def test_async_synchronize_returns_torch(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.arange(4, dtype=torch.float32)
        h = hvd_torch.allreduce_async(t, name="p_async")
        assert hvd_torch.poll(h) in (True, False)
        out = hvd_torch.synchronize(h)
        assert isinstance(out, torch.Tensor)
        assert torch.allclose(out, t)

    def test_allreduce_inplace(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.arange(4, dtype=torch.float32)
        expected = t.clone()
        out = hvd_torch.allreduce_(t, name="p_inplace")
        assert out is t
        assert torch.allclose(t, expected)

    def test_broadcast_inplace_async(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.ones(3)
        h = hvd_torch.broadcast_async_(t, root_rank=0, name="p_bcast")
        out = hvd_torch.synchronize(h)
        assert out is t
        assert torch.allclose(t, torch.ones(3))

    def test_grouped_allreduce_variants(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        ts = [torch.ones(2), torch.full((3,), 2.0)]
        outs = hvd_torch.grouped_allreduce(ts, name="p_grp")
        assert all(isinstance(o, torch.Tensor) for o in outs)
        assert torch.allclose(outs[1], ts[1])

        ts2 = [torch.ones(2), torch.full((3,), 5.0)]
        outs2 = hvd_torch.grouped_allreduce_(ts2, name="p_grp_ip")
        assert outs2[0] is ts2[0] and outs2[1] is ts2[1]
        assert torch.allclose(ts2[1], torch.full((3,), 5.0))

    def test_alltoall_async(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.arange(4, dtype=torch.float32)
        h = hvd_torch.alltoall_async(t, name="p_a2a")
        out, splits = hvd_torch.synchronize(h)
        assert isinstance(out, torch.Tensor)
        assert torch.allclose(out, t)
        assert splits == [4]

    def test_sparse_allreduce_async(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        t = torch.sparse_coo_tensor([[0, 2]], [1.0, 2.0], (4,))
        resolve = hvd_torch.sparse_allreduce_async(t, name="p_sparse",
                                                   op=None)
        out = resolve()
        assert out.is_sparse
        dense = out.to_dense()
        assert torch.allclose(dense, torch.tensor([1.0, 0.0, 2.0, 0.0]))

    def test_join_barrier(self, hvd):
        pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        hvd_torch.barrier()
        assert hvd_torch.join() >= 0

    def test_object_helpers_and_compression(self, hvd):
        pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        assert hvd_torch.broadcast_object({"a": 1}, root_rank=0) == {"a": 1}
        assert hvd_torch.allgather_object([2, 3]) == [[2, 3]]
        assert hvd_torch.Compression.fp16 is not None

    def test_top_level_allgather_object(self, hvd):
        import horovod_tpu

        assert horovod_tpu.allgather_object(7) == [7]

    def test_bfloat16_tensor_roundtrip(self, hvd):
        """bf16 — THE TPU dtype — has no direct torch<->numpy conversion;
        the boundary reinterprets bits through ml_dtypes.bfloat16."""
        torch = pytest.importorskip("torch")
        import ml_dtypes
        from horovod_tpu.interop import torch as hvd_torch
        from horovod_tpu.interop.torch import _to_np

        t = torch.tensor([1.5, -2.25, 3.0], dtype=torch.bfloat16)
        arr = _to_np(t)
        assert arr.dtype == ml_dtypes.bfloat16
        out = hvd_torch.allreduce(t, name="p_bf16")
        assert out.dtype == torch.bfloat16
        assert torch.allclose(out, t)

    def test_requires_grad_param_broadcast_inplace(self, hvd):
        """broadcast_ on a requires_grad leaf (model parameter) must not
        raise (regression: resize_ on variables that require grad)."""
        torch = pytest.importorskip("torch")
        from horovod_tpu.interop import torch as hvd_torch

        p = torch.nn.Parameter(torch.ones(3))
        out = hvd_torch.broadcast_(p, root_rank=0, name="p_rg")
        assert out is p and p.requires_grad
