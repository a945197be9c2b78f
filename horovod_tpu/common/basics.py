"""Process model, topology, and lifecycle — the ``hvd.init()`` layer.

TPU-native re-conception of the reference's init path
(ref: common/basics.py:33-489 HorovodBasics; operations.cc:811-863
InitializeHorovodOnce; operations.cc:887-1353 C API).

Key design translation (SURVEY.md §7 step 1):

* rank / local_rank / cross_rank map onto JAX's process topology:
  ``rank`` = ``jax.process_index()``, ``cross_rank`` = host index,
  ``local_rank`` = position within the host.  The launcher provides these
  via the ``HVDT_*`` env contract (the analog of runner/gloo_run.py:65-76);
  without a launcher they are derived from JAX itself.
* Rendezvous = the JAX coordination service (``jax.distributed.initialize``),
  replacing the reference's MPI init / Gloo HTTP rendezvous
  (gloo/gloo_context.cc).
* There is no background C++ thread to spawn at init: under jit, collective
  scheduling is XLA's job.  The eager negotiated path (ops/eager.py) starts
  its controller thread lazily on first use.

Unlike the reference (one process per accelerator), JAX runs one process per
*host* controlling several local devices; chip-level parallelism is expressed
through sharded arrays over the mesh.  ``size()``/``rank()`` therefore count
processes (matching the reference's process semantics) while
``num_devices()``/``device_rank`` count chips.
"""

from __future__ import annotations

import atexit
import dataclasses
import os
import threading
import time
from typing import Any, List, Optional, Sequence

from . import config
from .exceptions import NotInitializedError
from .logging_util import get_logger

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "rank",
    "size",
    "local_rank",
    "local_size",
    "cross_rank",
    "cross_size",
    "num_devices",
    "local_devices",
    "global_devices",
    "is_homogeneous",
    "Topology",
    "topology",
]

log = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Static process/device topology, fixed at init.

    (ref: the rank/local_rank/cross_rank triple of SlotInfo,
    runner/common/util/hosts.py:155, consumed by controller
    DoInitialization mpi_controller.cc:28.)
    """

    rank: int
    size: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int
    num_devices: int          # global device (chip) count
    num_local_devices: int

    @property
    def is_homogeneous(self) -> bool:
        return self.num_devices == self.num_local_devices * self.cross_size * (
            self.local_size if self.local_size else 1
        ) or self.size == 1


class _GlobalState:
    """Process-wide framework state (ref: global_state.h:39-126
    HorovodGlobalState — minus the background thread, which on TPU only
    exists for the eager path and lives in ops/eager.py)."""

    def __init__(self) -> None:
        self.lock = threading.RLock()
        self.initialized = False
        self.topology: Optional[Topology] = None
        self.mesh = None  # jax.sharding.Mesh over all participating devices
        self.process_set_table = None  # built at init (process_sets.py)
        self.eager_controller = None   # lazy (ops/eager.py)

    def reset(self) -> None:
        self.initialized = False
        self.topology = None
        self.mesh = None
        self.process_set_table = None
        self.eager_controller = None


_state = _GlobalState()


def _global_state() -> _GlobalState:
    return _state


def _jax_distributed_initialized() -> bool:
    """True if the JAX distributed runtime is already connected.

    Must not initialize the XLA backend as a side effect (unlike
    jax.process_count()), since jax.distributed.initialize() has to run
    before backend init."""
    import jax

    return bool(jax.distributed.is_initialized())


def _refuse_shared_chips(local_size: int) -> None:
    """Fail now, with the cause, when several worker processes on this
    host would each open all of its TPU chips.

    A chip belongs to one process.  ``hvdtrun`` binds each local slot to
    a chip of its own for 2 or 4 slots per host
    (runner/hosts.local_chip_env); for any other count every worker
    opens every chip, one wins libtpu's lock, the rest fail on the lock
    file, and all of them then sit in the coordination service's
    timeouts.  Checked before anything connects or touches a backend, so
    the launcher sees a non-zero exit within seconds."""
    import jax

    if (local_size <= 1 or os.environ.get("TPU_VISIBLE_CHIPS")
            or os.environ.get("TPU_VISIBLE_DEVICES")):
        return
    platforms = (jax.config.jax_platforms or "").lower()
    if platforms and "tpu" not in platforms:
        return
    # The PCI scan JAX's own TPU start-up uses; it touches no backend.
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips:
        raise RuntimeError(
            f"hvd.init(): {local_size} worker processes were started on "
            f"this host (HVDT_LOCAL_SIZE) and nothing binds this one to a "
            f"TPU chip of its own (TPU_VISIBLE_CHIPS is unset), so each "
            f"would open all {chips} chip(s) and all but one would fail. "
            "A chip belongs to one process: run one process per host — "
            "it drives every local chip through the dp mesh — or 2 or 4 "
            "per host, for which hvdtrun exports the per-chip binding.")


def _build_default_mesh(devices: Sequence[Any]):
    """Build the default mesh: 1-D data-parallel over all devices, or the
    axes requested via HVDT_MESH_AXES (e.g. 'dp=4,tp=2')."""
    import numpy as np
    from jax.sharding import Mesh

    spec = config.get_str("HVDT_MESH_AXES")
    devs = np.asarray(devices, dtype=object)
    if not spec:
        return Mesh(devs, ("dp",))
    axes, sizes = [], []
    for part in spec.split(","):
        name, _, sz = part.strip().partition("=")
        axes.append(name)
        sizes.append(int(sz))
    total = 1
    for s in sizes:
        total *= s
    if total != len(devices):
        raise ValueError(
            f"HVDT_MESH_AXES product {total} != device count {len(devices)}")
    return Mesh(devs.reshape(sizes), tuple(axes))


def init(
    *,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    mesh=None,
    process_sets: Optional[Sequence[Sequence[int]]] = None,
) -> None:
    """Initialize the framework (ref: basics.py init → operations.cc:889
    horovod_init).

    Reads the launcher env contract (HVDT_RANK/SIZE/LOCAL_RANK/...) when
    present; connects the JAX distributed runtime for multi-process runs;
    builds the global device mesh and process-set table.

    Args:
      coordinator_address: host:port of the JAX coordination service.
        Defaults to HVDT_COORDINATOR_ADDR from the launcher.
      num_processes / process_id: override process topology (defaults from
        the env contract).
      mesh: optional pre-built jax.sharding.Mesh to adopt instead of the
        default 1-D data-parallel mesh.
      process_sets: optional list of rank lists to register as process sets
        at init (ref: horovod_init's ranks argument + init(comm=[...])).
    """
    import jax

    t_init = time.perf_counter()
    with _state.lock:
        if _state.initialized:
            log.debug("init() called twice; ignoring")
            return

        # Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR,
        # else HVDT_COMPILATION_CACHE): engage before anything compiles,
        # so launcher-forwarded env (hvdtrun --compilation-cache-dir)
        # takes effect in every worker.
        from ..step_pipeline import enable_compilation_cache

        enable_compilation_cache()

        # XLA latency-hiding / async-collective-fusion flags
        # (HVDT_XLA_LATENCY_HIDING, ops/overlap.py): engage BEFORE the
        # first jax computation below initializes the backend — libtpu
        # reads LIBTPU_INIT_ARGS once at TPU init.  auto (default) keeps
        # non-TPU environments untouched.
        from ..ops.overlap import enable_latency_hiding

        enable_latency_hiding()

        # Wire-compression env selection (HVDT_COMPRESSION / HVDT_QUANT):
        # resolve NOW so an unknown name fails at init with the valid
        # list, not at the first optimizer step on some worker.
        from ..ops.compression import Compression

        _env_comp = Compression.from_env()
        if _env_comp is not Compression.none:
            log.info("gradient wire compression from env: %s",
                     _env_comp.__name__)

        # Transport-policy env selection (HVDT_TRANSPORT): parse NOW so
        # unknown axis/algorithm/wire vocabulary or garbage thresholds
        # fail at init with the valid lists, not at the first traced
        # step on some worker (same idiom as HVDT_COMPRESSION above).
        from ..transport import validate_env as _transport_validate

        _env_transport = _transport_validate()
        if _env_transport is not None:
            log.info("transport policy from env: %s",
                     _env_transport.describe())

        # ZeRO stage env selection (HVDT_ZERO): validate NOW so an
        # unknown stage fails at init with the valid list, not at the
        # first optimizer build on some worker (same idiom as above).
        from ..ops import zero as _zero

        _env_zero_stage = _zero.validate_env()
        if _env_zero_stage is not None:
            log.info("ZeRO state sharding from env: stage=%s",
                     _env_zero_stage)

        _refuse_shared_chips(config.get_int("HVDT_LOCAL_SIZE"))

        env_size = config.get_int("HVDT_SIZE")
        env_rank = config.get_int("HVDT_RANK")
        coord = coordinator_address or config.get_str("HVDT_COORDINATOR_ADDR")
        n_proc = num_processes if num_processes is not None else (
            env_size if env_size > 0 else None)
        proc_id = process_id if process_id is not None else (
            env_rank if env_rank >= 0 else None)

        # jax.distributed.initialize must run before anything initializes the
        # XLA backend (jax.process_count() would), so the "already connected"
        # check must not touch the backend.
        if coord and (n_proc or 0) > 1 and not _jax_distributed_initialized():
            log.info("connecting JAX distributed runtime at %s (%s/%s)",
                     coord, proc_id, n_proc)
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=n_proc,
                process_id=proc_id,
            )

        # The XLA backend's first touch (the client's creation, libtpu's
        # start, the chips opened), timed apart from the rest of init():
        # hvdt_startup_seconds{phase="backend"} and {phase="init"}.
        t_backend = time.perf_counter()
        p_rank = jax.process_index()
        p_size = jax.process_count()

        local_rank_ = config.get_int("HVDT_LOCAL_RANK")
        local_size_ = config.get_int("HVDT_LOCAL_SIZE")
        cross_rank_ = config.get_int("HVDT_CROSS_RANK")
        cross_size_ = config.get_int("HVDT_CROSS_SIZE")
        if local_rank_ < 0:
            local_rank_, local_size_ = 0, 1
            cross_rank_, cross_size_ = p_rank, p_size

        devices = jax.devices()
        backend_s = time.perf_counter() - t_backend
        topo = Topology(
            rank=p_rank,
            size=p_size,
            local_rank=local_rank_,
            local_size=local_size_,
            cross_rank=cross_rank_,
            cross_size=cross_size_,
            num_devices=len(devices),
            num_local_devices=len(jax.local_devices()),
        )

        _state.topology = topo
        _state.mesh = mesh if mesh is not None else _build_default_mesh(devices)

        from . import process_sets as ps

        _state.process_set_table = ps.ProcessSetTable(topo, _state.mesh)
        if process_sets:
            for ranks in process_sets:
                _state.process_set_table.add(list(ranks))

        _state.initialized = True
        log.info("initialized: %s", topo)

        # Telemetry exporter (HVDT_TELEMETRY=1): per-worker /metrics +
        # /healthz on HVDT_METRICS_PORT + local_rank.  No-op when the
        # subsystem is off; never raises (observability must not sink
        # init).
        from ..telemetry.exporter import maybe_start_exporter

        maybe_start_exporter(topology=topo)

        # Predicted-vs-observed perf attribution: when an expected
        # schedule fingerprint is configured (HVDT_EXPECTED_SCHEDULE),
        # price it with the fitted cost model on the ambient topology
        # and publish hvdt_expected_step_comm_seconds /
        # hvdt_expected_wire_bytes{axis}; the StepTimer stream then
        # keeps hvdt_perf_deviation_ratio live.  No-op when telemetry
        # is off; never raises.
        from ..telemetry.step_stats import maybe_publish_expected_cost

        maybe_publish_expected_cost()

        from ..telemetry import compile_ledger

        # installed by enable_compilation_cache() above
        ledger = compile_ledger.get_ledger()
        ledger.note_startup("backend", backend_s)
        ledger.note_startup("init",
                            time.perf_counter() - t_init - backend_s)


def shutdown() -> None:
    """Tear down (ref: operations.cc horovod_shutdown)."""
    from ..telemetry import trace as _trace
    from ..telemetry.exporter import stop_exporter
    from ..timeline import stop_timeline

    from ..ops import tcp_backend

    try:
        # Final span flush: per-rank Chrome-trace file into
        # HVDT_TRACE_DIR + KV publish for the driver-side merge (no-op
        # when tracing is off; never sinks shutdown).
        _trace.flush()
    except Exception:   # pragma: no cover - defensive
        pass
    stop_exporter()
    with _state.lock:
        if not _state.initialized:
            stop_timeline()  # a timeline may exist without init
            return
        multi = _state.topology is not None and _state.topology.size > 1
        if _state.eager_controller is not None:
            _state.eager_controller.shutdown()
        _state.reset()
    tcp_backend.shutdown_groups()
    stop_timeline()
    if multi:
        _sync_distributed_teardown()


def _sync_distributed_teardown() -> None:
    """Barrier the processes before the coordination service dies.

    Rank 0's process hosts the JAX coordination service; if it exits
    while a slower rank's client still holds connections/heartbeats, the
    orphaned client's C++ threads abort the process ("terminate called
    after throwing an instance of ...", observed on a loaded 1-core box
    where rank skew at exit is seconds).  A bounded coordination-service
    barrier lines everyone up, then ``jax.distributed.shutdown``
    disconnects clients cleanly before interpreter exit.  Best-effort:
    a crashed peer must not turn OUR exit into a hang."""
    import jax

    try:
        from jax._src import distributed as _jd

        client = getattr(_jd.global_state, "client", None)
        if client is None:
            return
    except Exception as e:
        # The private-API lookup itself failed (a jax upgrade moved
        # jax._src.distributed.global_state): the orderly teardown is
        # silently gone, which is exactly the racy-exit regression this
        # barrier fixed — make that loudly visible.
        # tests/test_basics.py::test_private_distributed_api_resolves pins
        # the attribute against the installed jax.
        log.warning("shutdown barrier unavailable (private jax API "
                    "moved?): %s — exits may race", e)
        return
    try:
        client.wait_at_barrier("hvdt_shutdown", 10_000)  # ms
    except Exception as e:  # pragma: no cover - peer-crash path
        log.debug("shutdown barrier skipped (peer gone?): %s", e)
        return
    try:
        # Tear the local PJRT client (and its cross-process collective
        # threads) down NOW, while every peer is provably alive and idle
        # (post-barrier) — leaving it to interpreter finalization lets a
        # faster peer's exit reset sockets under blocked collective
        # threads, which aborts the process from a C++ destructor.
        import jax.extend as jex

        jex.backend.clear_backends()
    except Exception as e:  # pragma: no cover
        log.debug("clear_backends failed: %s", e)
    try:
        jax.distributed.shutdown()
    except Exception as e:  # pragma: no cover
        log.debug("jax.distributed.shutdown failed: %s", e)


atexit.register(shutdown)


def _topo() -> Topology:
    t = _state.topology
    if t is None:
        raise NotInitializedError()
    return t


def is_initialized() -> bool:
    return _state.initialized


def topology() -> Topology:
    return _topo()


def rank() -> int:
    return _topo().rank


def size() -> int:
    return _topo().size


def local_rank() -> int:
    return _topo().local_rank


def local_size() -> int:
    return _topo().local_size


def cross_rank() -> int:
    return _topo().cross_rank


def cross_size() -> int:
    return _topo().cross_size


def num_devices() -> int:
    return _topo().num_devices


def is_homogeneous() -> bool:
    return _topo().is_homogeneous


def local_devices() -> List[Any]:
    import jax

    _topo()
    return list(jax.local_devices())


def global_devices() -> List[Any]:
    import jax

    _topo()
    return list(jax.devices())


def mesh():
    """The global device mesh adopted at init."""
    m = _state.mesh
    if m is None:
        raise NotInitializedError()
    return m


def set_mesh(new_mesh) -> None:
    """Adopt a caller-provided mesh as the global mesh (axes for dp/tp/...)."""
    with _state.lock:
        _topo()
        _state.mesh = new_mesh
