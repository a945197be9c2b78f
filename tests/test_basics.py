"""Core init/topology/process-set tests (ref analog: test_torch.py rank/size
assertions; test_process_sets_multi_comm.py)."""

import pytest


def test_init_and_topology(hvd):
    assert hvd.is_initialized()
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.num_devices() == 8
    assert hvd.is_homogeneous()


def test_not_initialized_raises():
    import horovod_tpu as hvd_mod
    from horovod_tpu.common.exceptions import NotInitializedError

    hvd_mod.shutdown()
    with pytest.raises(NotInitializedError):
        hvd_mod.rank()


def test_double_init_is_noop(hvd):
    hvd.init()
    assert hvd.size() == 1


def test_default_mesh(hvd):
    m = hvd.mesh()
    assert m.axis_names == ("dp",)
    assert m.devices.size == 8


def test_mesh_axes_env(monkeypatch):
    import horovod_tpu as hvd_mod

    hvd_mod.shutdown()
    monkeypatch.setenv("HVDT_MESH_AXES", "dp=4,tp=2")
    hvd_mod.init()
    try:
        m = hvd_mod.mesh()
        assert m.axis_names == ("dp", "tp")
        assert m.devices.shape == (4, 2)
    finally:
        hvd_mod.shutdown()


def test_process_sets(hvd):
    ps = hvd.global_process_set()
    assert ps.id == 0
    assert ps.ranks == [0]
    assert ps.included()
    assert ps.rank() == 0
    # single-process: only the trivial subset is valid
    ps2 = hvd.add_process_set([0])
    assert ps2.id >= 0
    # duplicate registration returns the same set
    ps3 = hvd.add_process_set([0])
    assert ps3.id == ps2.id
    with pytest.raises(Exception):
        hvd.add_process_set([0, 5])
    with pytest.raises(Exception):
        hvd.remove_process_set(0)


def test_knob_registry(monkeypatch):
    from horovod_tpu.common import config

    assert config.get_int("HVDT_FUSION_THRESHOLD") == 64 * 1024 * 1024
    monkeypatch.setenv("HVDT_FUSION_THRESHOLD", "1024")
    assert config.get_int("HVDT_FUSION_THRESHOLD") == 1024
    monkeypatch.setenv("HVDT_FUSION_THRESHOLD", "garbage")
    assert config.get_int("HVDT_FUSION_THRESHOLD") == 64 * 1024 * 1024
    assert "HVDT_TIMELINE" in config.registry_doc()


def test_capability_predicates():
    """ref: horovod/common/util.py:137-200 — same names, honest answers
    for this build (no MPI/NCCL transports; XLA + native TCP instead)."""
    import horovod_tpu as hvd

    for name in ("mpi_built", "gloo_built", "nccl_built", "ddl_built",
                 "ccl_built", "cuda_built", "rocm_built"):
        assert getattr(hvd, name)() is False
    assert hvd.mpi_enabled() is False
    assert hvd.mpi_threads_supported() is False
    assert hvd.xla_built() is True
    assert hvd.tpu_available() is False      # CPU-pinned test process
    assert hvd.native_built() in (True, False)
    assert hvd.tcp_enabled() in (True, False)


def test_reference_example_api_surface():
    """Every name the reference's example suite uses on `hvd.` resolves
    here too (grep over /root/reference/examples/pytorch + the core
    script surface), so ported scripts don't die on attribute errors."""
    import horovod_tpu as hvd

    for n in ("Adasum", "Average", "Sum", "Min", "Max", "Product",
              "Compression", "DistributedOptimizer", "allreduce",
              "broadcast", "broadcast_optimizer_state",
              "broadcast_parameters", "init", "local_rank", "local_size",
              "nccl_built", "rank", "size", "start_timeline",
              "stop_timeline", "join", "barrier", "poll", "synchronize",
              "elastic", "run", "is_initialized", "shutdown",
              "sparse_allreduce", "sparse_allreduce_async"):
        assert hasattr(hvd, n), n


def test_private_distributed_api_resolves():
    """The orderly-teardown barrier (common/basics.py
    _sync_distributed_teardown) leans on jax._src.distributed.global_state
    — a private API. If a jax upgrade moves it, teardown silently reverts
    to the racy exit path; fail HERE instead so the pin is visible."""
    from jax._src import distributed as _jd

    gs = _jd.global_state
    # `client` is None in a non-distributed process, but the attribute
    # access path itself must resolve (hasattr on the instance would hide
    # a renamed slot behind __getattr__-less AttributeError).
    assert hasattr(gs, "client")


def test_init_refuses_workers_sharing_a_hosts_chips(monkeypatch):
    """Several workers on one TPU host with nothing binding each to a
    chip of its own: hvd.init() fails at once, naming the cause, instead
    of leaving them waiting for a chip a sibling holds."""
    import jax
    from jax._src import hardware_utils

    from horovod_tpu.common import basics

    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (4, "0x0063"))
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    before = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu,cpu")
        with pytest.raises(RuntimeError, match="one process per host"):
            basics._refuse_shared_chips(3)
        basics._refuse_shared_chips(1)          # one process: fine
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
        basics._refuse_shared_chips(4)          # bound by the launcher
        monkeypatch.delenv("TPU_VISIBLE_CHIPS")
        jax.config.update("jax_platforms", "cpu")
        basics._refuse_shared_chips(3)          # CPU workers share nothing
    finally:
        jax.config.update("jax_platforms", before)
