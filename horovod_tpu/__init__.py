"""horovod_tpu — a TPU-native distributed training framework.

A brand-new framework with the capabilities of Horovod (the reference at
/root/reference, v0.23.0 — see SURVEY.md), re-architected for TPU:

* data plane = XLA collectives over ICI/DCN (``jax.lax.psum`` et al.) instead
  of NCCL/MPI/Gloo transports;
* rendezvous = the JAX coordination service instead of MPI init / Gloo HTTP;
* jit-native fused gradient path (DistributedOptimizer over optax) plus an
  eager negotiated path for Horovod-style named async collectives;
* parallelism substrate beyond the reference: mesh axes for dp/tp/sp/ep,
  reduce-scatter, ring attention (SURVEY.md §2.7, §5.7).

Typical use::

    import horovod_tpu as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.perf_counter()   # to _IMPORT_SECONDS, this file's end

__version__ = "0.1.0"

from .common.basics import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    num_devices,
    local_devices,
    global_devices,
    is_homogeneous,
    topology,
    mesh,
    set_mesh,
)
from .common.process_sets import (  # noqa: F401
    ProcessSet,
    add_process_set,
    remove_process_set,
    global_process_set,
    process_set_by_id,
)
from .common.types import ReduceOp, Status  # noqa: F401
from .common.exceptions import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
)

# Reduce-op aliases matching the reference's module-level constants
# (ref: torch/mpi_ops.py Average/Sum/Adasum/Min/Max/Product).
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT

from . import ops  # noqa: F401,E402
from .ops import device  # noqa: F401,E402


def __getattr__(name):
    # Lazy imports for heavier subsystems so `import horovod_tpu` stays fast.
    try:
        if name in ("allreduce", "allreduce_async", "allgather",
                    "allgather_async", "broadcast", "broadcast_async",
                    "alltoall", "alltoall_async", "reducescatter",
                    "reducescatter_async", "grouped_allreduce",
                    "grouped_allreduce_async", "synchronize", "poll", "join",
                    "barrier"):
            from .ops import eager

            return getattr(eager, name)
        if name == "DistributedOptimizer":
            from .optimizer import DistributedOptimizer

            return DistributedOptimizer
        if name in ("broadcast_parameters", "broadcast_optimizer_state",
                    "broadcast_object", "allgather_object"):
            from . import functions

            return getattr(functions, name)
        if name == "Compression":
            from .ops.compression import Compression

            return Compression
        if name in ("sparse_allreduce", "sparse_allreduce_async"):
            # ref: torch/mpi_ops.py:556-578 sparse_allreduce_async
            from .ops import sparse

            return getattr(sparse, name)
        if name in ("mpi_built", "mpi_enabled", "mpi_threads_supported",
                    "gloo_built", "gloo_enabled", "nccl_built", "ddl_built",
                    "ccl_built", "cuda_built", "rocm_built", "xla_built",
                    "tpu_available", "native_built", "tcp_enabled"):
            from .common import util

            return getattr(util, name)
        if name in ("start_timeline", "stop_timeline"):
            # Dynamic timeline control at top level (ref: horovod C API
            # horovod_start_timeline, operations.cc:1032-1064).
            from . import timeline as _tl

            return getattr(_tl, name)
        if name == "run":
            # Programmatic launcher (ref: horovod/runner/__init__.py:210
            # hvd.run) — run a function on np workers, results by rank.
            from .runner import run

            return run
        if name in ("fused_adam", "fused_sgd"):
            # Fused Pallas optimizer kernels (single-HBM-pass updates;
            # compose with DistributedOptimizer unchanged).
            from .ops import optim_kernels

            return getattr(optim_kernels, name)
        if name in ("enable_compilation_cache", "donated_step",
                    "overlap_step"):
            from . import step_pipeline as _sp

            return getattr(_sp, name)
        if name == "overlap":
            # Overlap scheduling layer (dependency-ordered gradient
            # exchange, async collectives, pipelined updates).
            from .ops import overlap

            return overlap
        if name == "zero":
            # ZeRO-sharded gradient exchange / optimizer state
            # (reduce-scatter wire, shard-local fused updates,
            # allgather-on-demand parameters).
            from .ops import zero

            return zero
        if name in ("elastic", "timeline", "models", "parallel", "runner",
                    "callbacks", "sync_batch_norm", "optimizer", "autotune",
                    "data", "native", "orchestrate", "interop",
                    "step_pipeline", "serve", "quant", "resilience",
                    "telemetry", "control"):
            import importlib

            return importlib.import_module(f".{name}", __name__)
    except ImportError as e:
        raise AttributeError(
            f"horovod_tpu.{name} is unavailable: {e}") from e
    raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")


# hvdt_startup_seconds{phase="import"}: what the statements above took
# (they pull in jax), handed to telemetry/compile_ledger at its install.
_IMPORT_SECONDS = _time.perf_counter() - _IMPORT_T0
