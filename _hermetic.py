"""Hermetic CPU-only child environments for driver entry points.

The accelerator env scrub used by ``__graft_entry__.py`` (it lives at the
repo root and must not import the framework — its parent process stays
JAX-free).

A CPU child must drop every var that selects a JAX platform or steers
an accelerator runtime, so that it can neither claim a chip its parent's
environment points at nor wait for one.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

PLATFORM_VARS = ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "XLA_FLAGS")
ACCEL_PREFIXES = ("TPU_", "LIBTPU", "PJRT_")


def scrubbed_cpu_env(host_device_count: Optional[int] = None,
                     base: Optional[Dict[str, str]] = None
                     ) -> Dict[str, str]:
    """Copy of ``base`` (default os.environ) pinned to the CPU platform
    with every accelerator-steering var removed; optionally forces
    ``host_device_count`` virtual CPU devices."""
    src = os.environ if base is None else base
    env = {k: v for k, v in src.items()
           if k not in PLATFORM_VARS and not k.startswith(ACCEL_PREFIXES)}
    env["JAX_PLATFORMS"] = "cpu"
    if host_device_count is not None:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={host_device_count}")
    return env
