"""Online inference serving: turn a checkpointed pytree into an endpoint.

The subsystem the training stack feeds (ROADMAP: "serves heavy traffic
from millions of users").  Layering, bottom up:

* :mod:`~horovod_tpu.serve.metrics` — Prometheus-text counters / gauges /
  latency summaries (no new dependencies);
* :mod:`~horovod_tpu.serve.engine`  — :class:`InferenceEngine`: jit per
  shape bucket, pad-to-bucket, persistent-compile-cache reuse, hot
  weight swap, optional mesh sharding;
* :mod:`~horovod_tpu.serve.batcher` — :class:`DynamicBatcher`: bounded
  admission queue + linger-based micro-batching ahead of the engine;
* :mod:`~horovod_tpu.serve.llm`     — :class:`ContinuousLLMEngine`:
  continuous-batching LLM decode (paged KV cache, per-iteration
  scheduler, interactive/batch tenant quotas), selected with
  ``HVDT_SERVE_ENGINE=continuous``;
* :mod:`~horovod_tpu.serve.reload`  — :class:`CheckpointWatcher`: polls a
  ``CheckpointManager`` directory and hot-swaps newer steps;
* :mod:`~horovod_tpu.serve.server`  — :class:`ModelServer`: stdlib HTTP
  front end (``/predict``, ``/healthz``, ``/metrics``) with 503
  backpressure and SIGTERM graceful drain;
* :mod:`~horovod_tpu.serve.replica` — :class:`ReplicaRegistrar`: KV
  heartbeats (load + p99) that wire one replica into the elastic
  serving control plane, plus the ``--replica-worker`` entry;
* :mod:`~horovod_tpu.serve.router`  — :class:`Router`: the front tier —
  discovers live replicas from the rendezvous KV, load-balances
  ``/predict`` with retries/hedging, ejects SLO-breaching replicas;
* :mod:`~horovod_tpu.serve.autoscale` — :class:`ServeDriver` +
  :class:`AutoscalePolicy`: the driver-side replica autoscaler on the
  pod-aware elastic machinery (discovery, blacklist-with-cooldown,
  drain-then-exit-83 clean removal).

Entry points: ``python -m horovod_tpu.serve`` and ``hvdtrun serve``
(:func:`main`; ``--replicas``/``--autoscale`` switch to the elastic
control plane); in-process embedding via :class:`ModelServer` directly
(the test rig does this).
"""

from .batcher import (BackpressureError, DispatcherDied,  # noqa: F401
                      DynamicBatcher, RequestDeadlineExceeded)
from .engine import InferenceEngine, parse_buckets  # noqa: F401
from .metrics import MetricsRegistry  # noqa: F401
from .reload import CheckpointWatcher  # noqa: F401
from .server import ModelServer  # noqa: F401

__all__ = [
    "InferenceEngine", "DynamicBatcher", "BackpressureError",
    "DispatcherDied", "RequestDeadlineExceeded",
    "CheckpointWatcher", "ModelServer", "MetricsRegistry",
    "parse_buckets", "ContinuousLLMEngine", "main",
]


def __getattr__(name):
    # Lazy: serve.llm pulls in jax at engine-build time; the fleet layer
    # (router/autoscale) must stay importable without touching it.
    if name == "ContinuousLLMEngine":
        from .llm import ContinuousLLMEngine

        return ContinuousLLMEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def main(argv=None) -> int:
    """CLI entry (``python -m horovod_tpu.serve`` / ``hvdtrun serve``)."""
    from .__main__ import main as _main

    return _main(argv)
