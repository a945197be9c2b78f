"""Local attention: the one way in, and the choice of what runs behind it.

``attention(q, k, v)`` is what a model calls when one device (or one
shard of a manual island) holds the whole sequence.  Which code runs is
decided here and nowhere else, from what can be observed at trace time:

* the ambient abstract mesh (:func:`kernel_plan`): a Mosaic kernel
  cannot be auto-partitioned by GSPMD, so under GSPMD-auto axes it runs
  inside a manual ``shard_map`` island over (``dp``, ``fsdp``) x ``tp``,
  and where no such island can be opened it does not run;
* the policy on the LOCAL shapes (:func:`kernel_enabled`):
  ``HVDT_FLASH_ATTENTION=auto|on|off``, whether the shapes tile, the
  sequence length against the measured crossover, the platform;
* then ``pallas_kernels.flash_attention`` (its blocks come from the
  shape), or the XLA path below.

A sequence sharded over ``sp`` is a layout the caller declared, not a
choice made here: the model calls ``parallel.ring_attention`` for it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..common import config
from . import pallas_kernels

__all__ = ["attention", "kernel_enabled", "kernel_plan"]


# The shortest sequence at which the kernel path is no slower than XLA
# attention.  Measured on a v5e (PERF.md section 6, PR 32, the crossover
# table): the whole 24-layer step at BERT-Large widths (16 heads of 64,
# bf16, full remat), kernels against XLA attention at sequence 128 to
# 2048, at 16,384 and at 65,536 tokens a step.  At either token count
# the kernels are 19-21% ahead at 512 (58% at 1024 x 16, 116% at 2048 x
# 8), level to 3% behind at 256 and 11-13% behind at 128; they are the
# lighter path at every point, and from 1024 x 64 the only one that fits.
_CROSSOVER_SEQ = 512


def kernel_enabled(seq_len: int, *, batch: int, heads: int) -> bool:
    """Flash kernel policy: HVDT_FLASH_ATTENTION=auto|on|off.

    'auto' (default) engages the kernel on TPU wherever the shapes tile
    and the sequence is at least ``_CROSSOVER_SEQ`` long.  The rule is a
    length, not a size: XLA attention's cost a token grows with L
    (``heads x L`` f32 scores a token, written and read several times a
    pass) and the kernels' cost a call does not, so where the two paths
    cross depends on L and hardly on the batch; the bytes of the
    ``[batch, heads, L, L]`` scores mix capacity with speed and could
    not separate the measured points.  'on' forces the kernel whenever
    shapes tile; 'off' is the master switch.

    ``batch``/``heads`` are the sizes the kernel will actually see —
    pass LOCAL (per-shard) sizes when the call site shards them.  The
    measured rule reads neither."""
    mode = config.get_str("HVDT_FLASH_ATTENTION").lower()
    if mode == "off":
        return False
    shapes_ok = seq_len % min(128, seq_len) == 0 and seq_len >= 8
    if mode == "on":
        return shapes_ok
    return (shapes_ok and seq_len >= _CROSSOVER_SEQ
            and jax.devices()[0].platform == "tpu")


def _island_local_sizes(am, dp_axes, tp_ax) -> Tuple[int, int]:
    """(dp_size, tp_size) of an island plan under abstract mesh ``am``:
    what the island divides batch and heads by, so the policy sees the
    shapes the kernel would."""
    dp_size = (int(np.prod([am.shape[a] for a in dp_axes]))
               if dp_axes else 1)
    tp_size = am.shape[tp_ax] if tp_ax else 1
    return dp_size, tp_size


def kernel_plan(b: int, l: int, h: int, hk: int):
    """Decide how the flash kernel can engage under the ambient mesh.

    Returns "direct" (call the kernel as-is: no mesh, or every mesh axis
    already manual here), a ``(dp_axes, tp_axis, names)`` island plan
    (the mesh has GSPMD-auto axes — run the kernel inside a
    partial-manual shard_map over ``names``; Mosaic kernels cannot be
    auto-partitioned by GSPMD), or None (fall back to XLA attention).
    The policy (:func:`kernel_enabled`) is evaluated once, on the
    per-shard shapes the kernel would actually see."""
    am = jax.sharding.get_abstract_mesh()
    auto = [n for n, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Auto]
    manual = [n for n, t in zip(am.axis_names, am.axis_types)
              if t == jax.sharding.AxisType.Manual]
    if not auto:
        return "direct" if kernel_enabled(l, batch=b, heads=h) else None
    if manual:
        # Already inside a shard_map (e.g. the pp/sp/ep pipeline island)
        # with auto axes remaining: nesting another partial-manual island
        # here fails shardy lowering on the BACKWARD (the residuals'
        # dimension shardings mix manual-after-free axes — verified on
        # jax 0.9: "manual axes must come before free axes").  Fall back
        # to XLA attention; pure-auto meshes (dp/fsdp/tp) still engage.
        return None
    # Shard batch over dp-like axes and heads over tp, where divisible.
    dp_axes: Tuple[str, ...] = tuple(a for a in ("dp", "fsdp")
                                     if a in auto)
    while dp_axes and b % _island_local_sizes(am, dp_axes, None)[0]:
        dp_axes = dp_axes[:-1]
    tp_ax = "tp" if "tp" in auto else None
    if tp_ax and (h % am.shape[tp_ax] or hk % am.shape[tp_ax]):
        tp_ax = None
    dp_size, tp_size = _island_local_sizes(am, dp_axes, tp_ax)
    # Any OTHER size>1 auto axis (e.g. an auto axis sharding the
    # sequence) means the island's replicated in_specs would force a
    # full-sequence all-gather per layer — don't engage the kernel there.
    # Size-1 leftovers are included in the island instead: Mosaic refuses
    # to lower while ANY auto axis is ambient, even a trivial one.
    leftover = [a for a in auto if a not in dp_axes and a != tp_ax]
    if any(am.shape[a] > 1 for a in leftover):
        return None
    if not kernel_enabled(l, batch=max(1, b // dp_size),
                          heads=max(1, h // tp_size)):
        return None
    names = frozenset(dp_axes) | ({tp_ax} if tp_ax else set()) | \
        frozenset(leftover)
    return (dp_axes, tp_ax, names)


def _xla_attention(q, k, v, causal: bool, window: Optional[int] = None,
                   block_diffusion: Optional[int] = None,
                   scale: Optional[float] = None):
    """Attention as XLA fuses it: the ``[B, H, L, L]`` scores exist, in
    f32 out of bf16 operands.  Not the f32 oracle
    (``pallas_kernels.attention_reference``) and not the paged-slot
    softmax of the serving path: this is what training runs wherever the
    kernel does not.  ``window`` is the kernels' mask: query i sees keys
    i - window < j <= i; ``block_diffusion`` theirs too
    (``pallas_kernels.block_diffusion_mask``); ``scale`` theirs too."""
    l, h, dh = q.shape[1], q.shape[2], q.shape[3]
    hk = k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    if h != hk:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if block_diffusion is not None:
        mask = pallas_kernels.block_diffusion_mask(l, block_diffusion)
        s = jnp.where(mask[None, None], s, -1e30)
    elif causal:
        mask = jnp.tril(jnp.ones((l, l), bool))
        if window is not None:
            mask = jnp.logical_and(mask, jnp.logical_not(
                jnp.tril(jnp.ones((l, l), bool), -window)))
        s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              block_diffusion: Optional[int] = None,
              scale: Optional[float] = None) -> jax.Array:
    """Self-attention of a sequence this device holds whole.

    q: ``[B, L, H, D]``; k, v: ``[B, L, Hkv, D]`` with ``Hkv`` dividing
    ``H`` (GQA); returns ``[B, L, H, D]``.  Shapes are the global ones
    under a GSPMD-auto mesh and the local ones inside a manual island.
    ``window`` (causal only): a sliding window, query i sees the
    ``window`` keys i - window < j <= i; the same mask on every path.
    ``block_diffusion`` (a block length, in place of both): the L rows
    are two streams of one sequence, [noisy ; clean], under
    ``pallas_kernels.block_diffusion_mask``, on every path; the policy
    is asked of the L rows the call sees.  ``scale`` multiplies the scores
    (None: ``D ** -0.5``)."""
    if window is not None and not causal:
        raise ValueError("a window is a causal mask's: pass causal=True")
    if block_diffusion is not None and window is not None:
        raise ValueError("block diffusion has no window")
    b, l, h, _ = q.shape
    plan = kernel_plan(b, l, h, k.shape[2])
    if plan is None or (block_diffusion is not None and not
                        pallas_kernels.block_diffusion_tiles(
                            l, block_diffusion)):
        return _xla_attention(q, k, v, causal, window, block_diffusion,
                              scale)
    # Pallas fused attention: O(L·D) HBM traffic instead of a
    # materialized [B,H,L,L] score matrix (ops/pallas_kernels.py).
    kernel = functools.partial(pallas_kernels.flash_attention,
                               causal=causal, window=window,
                               block_diffusion=block_diffusion, scale=scale)
    if plan == "direct":
        return kernel(q, k, v)
    # GSPMD-auto mesh: Mosaic kernels can't be auto-partitioned, so
    # open a manual shard_map island over the batch (dp/fsdp) and
    # heads (tp) axes and run the kernel on the local shard — the
    # multi-chip engagement the auto gate alone would refuse (the
    # role of the reference's in-graph custom-call path, ref:
    # tensorflow/xla_mpi_ops.cc:165-235 "collectives/kernels live
    # inside the compiled program").
    dp_axes, tp_ax, names = plan
    spec = P(dp_axes if dp_axes else None, None, tp_ax, None)
    return jax.shard_map(
        kernel, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=names)(q, k, v)
