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


# ---------------------------------------------------------------------------
# What one call of a flash-attention kernel needs, from its shapes.  Derived
# for the local kernels of ``ops/pallas_kernels.py`` on q, k, v, o (and dO,
# dq, dk, dv) of [B, L, H, D] in bf16, causal, queries and keys of one
# length: the benchmark's seq-4096 cell calls them at B=8, L=4096, H=16,
# D=64.  A product over the whole score square is 2 * B*H*L*L*D operations,
# over the causal half B*H*L*L*D.  The softmax statistics (lse, delta: f32
# [B, H, L]) are 2/D of a tensor's bytes and are left out.
# ---------------------------------------------------------------------------


def flash_fwd_call_cost(batch: int, seq: int, heads: int, head_dim: int):
    """(operations, bytes) of one forward call: the score and the value
    product over the causal half; q, k, v read and o written once."""
    ops = 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 4.0 * batch * seq * heads * head_dim * 2
    return ops, nbytes


def flash_bwd_call_cost(batch: int, seq: int, heads: int, head_dim: int):
    """(operations, bytes) of one backward call: five products over the
    causal half (the scores again, dP = dO v^T, dv = P^T dO, dq = dS k,
    dk = dS^T q); q, k, v, dO read and dq, dk, dv written once."""
    ops = 5.0 * batch * heads * seq * seq * head_dim
    nbytes = 7.0 * batch * seq * heads * head_dim * 2
    return ops, nbytes


def flash_roofline_pct(ctx, ms, calls, call_cost):
    """A flash kernel's share of its roofline, in percent: ``calls`` calls
    a step at the least time ``call_cost`` allows each, over the ``ms`` a
    step they took.  None where there is nothing to divide."""
    if not ms or "seq" not in ctx.traffic:
        return None
    cfg = ctx.config
    ops, nbytes = call_cost(ctx.traffic["per_chip_batch"],
                            ctx.traffic["seq"], cfg["heads"],
                            cfg["d_model"] // cfg["heads"])
    least, _bound = roofline(ops, nbytes, ctx.peaks)     # compute-bound
    return 100.0 * (1e3 * least * calls) / ms
