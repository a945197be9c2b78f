"""Static distributed-correctness analysis (``hvdt-lint``).

Three checkers over the codebase-as-artifact, wired as one CLI and one
CI gate (``python -m horovod_tpu.analysis --all`` / ``hvdtrun lint``):

* :mod:`~horovod_tpu.analysis.schedule` — trace a step function,
  extract its ordered collective schedule from the jaxpr into a
  canonical fingerprint, and statically verify the contracts runtime
  forensics can only diagnose after the fact: deterministic bucket
  plans, hot-swap-compatible autotune legs, psum-family post-pin
  collectives, no data-dependent collectives.  Exported fingerprints
  feed the flight recorder's static-expected-vs-runtime-observed
  desync reports (``HVDT_EXPECTED_SCHEDULE``).
* :mod:`~horovod_tpu.analysis.lint` — AST rule registry (knob drift,
  zero-overhead gates, set-order nondeterminism, bare sleep polls)
  with a ratcheting baseline, plus the generated knob table (``docs/knobs.md``) and its drift check.
* :mod:`~horovod_tpu.analysis.locks` — static lock-order graph over
  the threaded control plane; new acquisition-order cycles fail CI.
* :mod:`~horovod_tpu.analysis.costmodel` /
  :mod:`~horovod_tpu.analysis.topology` — the analytical alpha-beta
  topology cost model: constants fitted from measured
  ``bench_allreduce`` rows, evaluated over schedule fingerprints for
  declared topologies (256 chips on a 1-CPU container), ratcheted by
  the ``--perf`` static perf-regression gate against
  ``.hvdt-perf-baseline.json`` and consulted by autotune pre-seeding
  (``HVDT_AUTOTUNE_MODEL_SEED``).
"""

from __future__ import annotations

from typing import List, Optional

__all__ = ["main"]


def main(argv: Optional[List[str]] = None) -> int:
    """CLI entry point (``hvdtrun lint`` dispatches here)."""
    from .__main__ import main as _main

    return _main(argv)
