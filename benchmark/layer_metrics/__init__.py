"""Per-layer metrics.  ``benchmark/layer_metrics/<reader>.py`` exposes
``read(ctx) -> number | None`` (``ctx`` is a ``harness.Context``); a reader
that finds nothing to read returns None and the metric is left out of the
line.  The helpers here are what several readers share."""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from benchmark import trace_reduce


def devices(ctx) -> list:
    """The traced devices; [] without a trace."""
    return list(ctx.trace.devices.values()) if ctx.trace else []


def worst(ctx, fn: Callable) -> Optional[float]:
    """The largest ``fn(DeviceTrace)`` over the traced devices, leaving
    out those where it is None; None where nothing is left."""
    readings = [r for r in map(fn, devices(ctx)) if r is not None]
    return max(readings) if readings else None


def steps_on(dev) -> int:
    return len(trace_reduce.step_modules(dev))


def per_step(ctx, pick) -> Tuple[Optional[float], Optional[float]]:
    """(milliseconds, events) per step of the leaf events ``pick``
    accepts, on the device where they take longest; (None, None) where
    there are none."""
    readings = []
    for dev in devices(ctx):
        seconds, events = trace_reduce.sum_seconds(dev, pick)
        if events and steps_on(dev):
            readings.append((1e3 * seconds / steps_on(dev),
                             events / steps_on(dev)))
    return max(readings) if readings else (None, None)


def roofline(ops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) for ``ops`` operations and ``nbytes``
    bytes of HBM traffic."""
    compute = ops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory else "hbm")
