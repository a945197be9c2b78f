"""Pipeline parallelism: 1F1B microbatch schedule over a mesh axis.

Substrate beyond reference parity (SURVEY.md §2.7 — the reference has no
pipeline layer).  TPU-native design: all ``pp`` ranks run the same SPMD
program; activations hop stage→stage with ``lax.ppermute`` inside
``lax.scan`` clocks, so XLA sees one static program and can overlap the
permute with the next tick's compute.

The clock is the 1F1B shape: a **warmup** segment (the first ``p-1``
ticks — the pipeline fills, trailing stages idle), a **steady** segment
(every stage busy, one microbatch in / one out per tick), and a
**cooldown** segment (the last ``p-1`` ticks — the pipeline drains).
Differentiable end to end: ``jax.grad`` through the scans yields the
reverse clock automatically (ppermute transposes to the reverse
permute), i.e. the backward drains in mirrored cooldown/steady/warmup
order — the 1F1B-equivalent schedule with the same
``(p-1)/(m+p-1)`` bubble fraction the cost model prices
(analysis/costmodel.pipeline_bubble_fraction).

Telemetry (trace time, path=jit convention): each traced schedule books
per-stage phase histograms ``hvdt_phase_PIPELINE_STAGE<i>_{WARMUP,
ACTIVE,COOLDOWN}_seconds`` in tick units — idle ÷ total ticks across
stages IS the observed bubble fraction the CI perf gate checks against
the priced one — plus one flight-recorder send/recv event per clock
segment.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.device import _axis_size_static

__all__ = ["pipeline_1f1b", "bubble_fraction", "report_pipeline_mfu"]


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Idle ÷ total stage-ticks of the 1F1B clock: ``(p-1)/(m+p-1)``.

    Every stage is idle for exactly ``p-1`` of the ``m+p-1`` ticks
    (stage ``s``: ``s`` warmup ticks + ``p-1-s`` cooldown ticks), so the
    per-stage and schedule-wide fractions coincide."""
    p, m = int(num_stages), int(num_microbatches)
    if p < 1 or m < 1:
        raise ValueError(f"need p >= 1 and m >= 1, got ({p}, {m})")
    return (p - 1) / (m + p - 1)


def _record_schedule(axis: str, p: int, m: int, tick_bytes: int,
                     dtype: str = "float32") -> None:
    """Trace-time booking of one pipeline schedule (ops/device idiom):
    per-stage phase histograms in tick units + one flight-recorder
    send/recv event per clock segment."""
    from ..telemetry import flight_recorder as _frm
    from ..telemetry import instrument as _ti

    _rec = _ti.get_recorder()
    _flight = _frm.get_flight_recorder()
    if _rec is None and _flight is None:
        return
    ticks = m + p - 1
    warmup = p - 1
    steady = max(0, m - (p - 1))
    cooldown = ticks - warmup - steady
    if _rec is not None:
        for s in range(p):
            # Tick units: the static clock is known at trace time; the
            # idle/total ratio (the observed bubble fraction) is
            # unit-free, so histogram sums compare directly against
            # the cost model's priced fraction.
            _rec.observe_phase(f"PIPELINE_STAGE{s}_WARMUP", float(s))
            _rec.observe_phase(f"PIPELINE_STAGE{s}_ACTIVE", float(m))
            _rec.observe_phase(f"PIPELINE_STAGE{s}_COOLDOWN",
                               float(p - 1 - s))
        _rec.record_collective(
            "ppermute", dtype, "exact", tick_bytes * ticks,
            count=ticks, path="jit", axis=axis)
    if _flight is not None:
        for seg, n in (("warmup", warmup), ("steady", steady),
                       ("cooldown", cooldown)):
            if n <= 0:
                continue
            _flight.record(
                op="ppermute", name=f"pipeline.{seg}",
                dtype=dtype, shape=(int(tick_bytes),),
                nbytes=tick_bytes * n, wire="exact", path="jit",
                count=n, axis=axis)


def pipeline_1f1b(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any,
                  microbatches: jax.Array,
                  *,
                  axis: str = "pp",
                  broadcast_out: bool = True) -> jax.Array:
    """Run ``stage_fn`` as one pipeline stage per ``axis`` rank, on the
    1F1B warmup/steady/cooldown clock.

    Must be called inside shard_map with ``axis`` bound.  Stage activations
    must be shape-uniform across stages (do embedding before and the head
    after the pipeline — replicated over ``pp``).

    Args:
      stage_fn: ``(params, x) -> y`` mapping one microbatch activation
        through this rank's stage; same output shape as input.
      stage_params: this rank's stage parameters (slice the stacked
        [stages, ...] params over ``pp`` in your in_specs).
      microbatches: ``[M, mb, ...]`` activations, replicated over ``pp``.
      broadcast_out: if True, psum-broadcast the last stage's outputs to all
        ``pp`` ranks so the loss can be computed replicated (simplest
        composition with dp/tp). If False, non-final ranks return zeros.

    Returns ``[M, mb, ...]`` outputs of the final stage.
    """
    p = _axis_size_static(axis)
    me = lax.axis_index(axis)
    m = microbatches.shape[0]
    ticks = m + p - 1
    fwd = [(i, (i + 1) % p) for i in range(p)]

    mb_bytes = int(microbatches[0].size) * microbatches.dtype.itemsize
    _record_schedule(axis, p, m, mb_bytes,
                     dtype=jnp.dtype(microbatches.dtype).name)

    def tick(carry, t):
        recv, out_buf = carry
        mb_idx = t - me                      # microbatch this rank works on
        active = (mb_idx >= 0) & (mb_idx < m)
        x0 = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, m - 1), keepdims=False)
        x_in = jnp.where(me == 0, x0, recv)
        y = stage_fn(stage_params, x_in)
        # Zero the bubble so garbage never contaminates grads/outputs.
        y = jnp.where(active, y, jnp.zeros_like(y))
        is_last = me == p - 1
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf,
            jnp.where(active & is_last,
                      y,
                      lax.dynamic_index_in_dim(
                          out_buf, jnp.clip(mb_idx, 0, m - 1),
                          keepdims=False)),
            jnp.clip(mb_idx, 0, m - 1), axis=0)
        recv_next = lax.ppermute(y, axis, fwd)
        return (recv_next, out_buf), None

    # Initial carries must match the body's varying-manual-axes type
    # (inputs' vma plus the pipeline axis) for vma stability under scan.
    from .sharding import pcast_to_union

    def _varying(x):
        return pcast_to_union(x, microbatches,
                              *jax.tree.leaves(stage_params),
                              extra=(axis,))

    recv0 = _varying(jnp.zeros_like(microbatches[0]))
    out0 = _varying(jnp.zeros_like(microbatches))

    # The clock runs as one scan per 1F1B segment (fill / steady /
    # drain).  The tick body is identical — segment boundaries are a
    # property of the CLOCK, not the per-tick program — but separate
    # scans keep the segments distinct in the jaxpr (three ppermute
    # sites, named scopes hvdt.pipeline.<segment>), which is what the
    # schedule fingerprint and flight-recorder events key on.
    warmup = min(p - 1, ticks)
    steady = max(0, m - (p - 1))
    cooldown = ticks - warmup - steady
    carry = (recv0, out0)
    t0 = 0
    for seg, n in (("warmup", warmup), ("steady", steady),
                   ("cooldown", cooldown)):
        if n <= 0:
            continue
        with jax.named_scope(f"hvdt.pipeline.{seg}"):
            carry, _ = lax.scan(tick, carry, jnp.arange(t0, t0 + n))
        t0 += n
    _, out = carry
    if broadcast_out:
        # Only the last stage wrote non-zeros; psum = broadcast from it.
        out = lax.psum(jnp.where(me == p - 1, out, jnp.zeros_like(out)), axis)
    return out


def report_pipeline_mfu(flops_per_step: float, step_seconds: float,
                        peak_flops_per_sec: Optional[float] = None
                        ) -> float:
    """Host-side MFU reporter: achieved model FLOP/s ÷ peak, as the
    ``hvdt_pipeline_mfu`` gauge.

    ``peak_flops_per_sec`` defaults to ``HVDT_PEAK_FLOPS`` (per-chip
    peak × chips; on the CPU sim any consistent nominal peak works —
    MFU is a ratio).  Returns the computed MFU; no-op gauge write when
    telemetry is off."""
    import os

    if peak_flops_per_sec is None:
        from ..analysis.topology import NOMINAL_SIM_PEAK_FLOPS

        raw = os.environ.get("HVDT_PEAK_FLOPS", "")
        peak_flops_per_sec = float(raw) if raw else NOMINAL_SIM_PEAK_FLOPS
    mfu = float(flops_per_step) / (float(step_seconds)
                                   * float(peak_flops_per_sec))
    from ..telemetry import instrument as _ti

    _rec = _ti.get_recorder()
    if _rec is not None:
        _rec.registry.gauge(
            "hvdt_pipeline_mfu",
            "Model FLOPs utilization of the last reported pipeline "
            "step (achieved model FLOP/s / peak FLOP/s)").set(mfu)
    return mfu
