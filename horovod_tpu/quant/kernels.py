"""Block-scaled symmetric int8/int4 quantize/dequantize kernels.

The wire-format primitives of the quantized-collective subsystem
(EQuARX, arxiv 2506.17615: block-scaled quantization inside the
allreduce roughly halves wire bytes vs bf16 at negligible quality
loss, and a 4-bit grid roughly halves that again when error feedback
absorbs the coarser rounding).  int8 format: a flat float vector is
cut into fixed-size blocks (``HVDT_QUANT_BLOCK`` elements); each block
carries one f32 scale ``absmax / 127`` and its elements as symmetric
int8 ``round(x / scale)`` clipped to [-127, 127].  Wire bytes per
element: 1 + 4/block (vs 4 for f32) — ~3.9x smaller at the default
block 256.  int4 format: same block grid, scale ``absmax / 7``,
elements clipped to [-7, 7] and packed two lanes per int8 byte —
0.5 + 4/block B/elem, ~0.51x of the int8 wire at block 256.

int4 packing is half-split, not adjacent-pair: byte ``j`` of a block
carries element ``j`` in its low nibble and element ``j + block/2`` in
its high nibble, so pack/unpack are contiguous half-block slices plus
lane-local shifts — Mosaic-friendly (no strided sublane gathers).

Two lowerings with identical math (the optim_kernels pattern):

* Pallas kernels (:func:`_quantize_pallas` / :func:`_dequantize_pallas`)
  — one VMEM-resident pass computes per-block absmax, scale and the
  int8 payload together, no separate HBM pass for the statistics.
  Tiling: blocks are ``[nblocks, block]`` 2D with ``block`` a multiple
  of 128 lanes; the int8 payload needs the (32, 128) int8 sublane tile,
  so block-rows-per-program is clamped to a power-of-2 divisor of
  ``nblocks`` >= 32 (:func:`quant_kernel_eligible` gates exactly this,
  platform-independently, so CPU exercises the same eligible/fallback
  split as TPU).  Off-TPU the kernels run under ``interpret=True``.
* Pure-XLA fallback (:func:`_quantize_xla` / :func:`_dequantize_xla`)
  — same formulas; the default on CPU (``HVDT_QUANT_KERNELS=auto``)
  where interpret-mode would be needlessly slow on the hot path.

``HVDT_QUANT_KERNELS``: ``auto`` (Pallas on TPU, XLA elsewhere), ``on``
(force Pallas — interpret mode off-TPU; what the kernel-equivalence
tests use), ``off`` (XLA everywhere).

API-guarded for older JAX (container runs jax 0.4.37): no
``jax.typeof`` / vma kwargs are required here — quantize runs on
already-flat bucket values inside the collective, and the pallas_call
carries no out-shape vma (``pallas_kernels._vma_kw`` degrades to ``{}``
on such builds).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import config
from ..ops.pallas_kernels import _use_interpret, _vma_kw
from ..telemetry.compile_ledger import kernel_scope

__all__ = [
    "quant_block_size",
    "quant_kernel_eligible",
    "quant_kernel_eligible_int4",
    "quantize_flat",
    "dequantize_flat",
    "quantize_dequantize",
    "quantize_flat_int4",
    "dequantize_flat_int4",
    "quantize_dequantize_int4",
    "wire_bytes",
    "wire_bytes_int4",
]

_LANES = 128
# int8 payload tile is (32, 128); f32 operands need only (8, 128) — the
# int8 floor dominates.
_INT8_SUBLANE = 32
# Block-rows per grid program cap: 32 rows x 4096-elem blocks x 4 B (f32
# view) = 512 KiB/operand — comfortable VMEM with double buffering.
_BLOCK_ROWS = 32


def quant_block_size() -> int:
    """The block-scaling granularity (``HVDT_QUANT_BLOCK``, default 256
    elements: 1.6% scale overhead, fine-grained enough that one outlier
    only coarsens its own 256 neighbours)."""
    block = config.get_int("HVDT_QUANT_BLOCK")
    return block if block > 0 else 256


def _kernels_on() -> bool:
    mode = config.get_str("HVDT_QUANT_KERNELS").lower()
    if mode == "on":
        return True
    if mode == "off":
        return False
    return not _use_interpret()  # auto: real Mosaic lowering only


def quant_kernel_eligible(size: int, block: int) -> bool:
    """True when a ``size``-element flat vector in ``block``-element
    blocks can take the Pallas lowering: whole blocks only, lane-aligned
    block, and a power-of-2 block-row divisor clearing the int8 sublane
    tile.  Platform-independent on purpose (see module docstring)."""
    if block <= 0 or block % _LANES or size <= 0 or size % block:
        return False
    nblocks = size // block
    return (nblocks & -nblocks) >= _INT8_SUBLANE


def _block_rows(nblocks: int) -> int:
    return min(_BLOCK_ROWS, nblocks & -nblocks)


# ---- shared math ---------------------------------------------------------


def _scale_and_q(x2):
    """Per-block-row scale + int8 payload; identical text in both
    lowerings so they can only differ by reduction association."""
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = absmax * (1.0 / 127.0)
    # All-zero block: scale 0 — force q = 0 instead of 0/0.
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(x2 * inv), -127.0, 127.0).astype(jnp.int8)
    return scale, q


# ---- pure-XLA lowering ---------------------------------------------------


def _quantize_xla(x2):
    scale, q = _scale_and_q(x2)
    return q, scale[:, 0]


def _dequantize_xla(q2, scales):
    return q2.astype(jnp.float32) * scales[:, None]


# ---- Pallas lowering -----------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale, q = _scale_and_q(x)
    q_ref[...] = q
    # Scale output is lane-broadcast to [rows, 128] so the f32 output
    # keeps a legal Mosaic tile; the caller reads lane 0.
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[..., :1]


def _quantize_pallas(x2):
    import jax.experimental.pallas as pl

    nblocks, block = x2.shape
    br = _block_rows(nblocks)
    kw = _vma_kw(x2)
    spec = pl.BlockSpec((br, block), lambda i: (i, 0))
    sspec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    with kernel_scope("quantize"):
        q, s = pl.pallas_call(
            _quant_kernel,
            grid=(nblocks // br,),
            in_specs=[spec],
            out_specs=[spec, sspec],
            out_shape=(jax.ShapeDtypeStruct((nblocks, block), jnp.int8, **kw),
                       jax.ShapeDtypeStruct((nblocks, _LANES), jnp.float32,
                                            **kw)),
            interpret=_use_interpret(),
        )(x2)
    return q, s[:, 0]


def _dequantize_pallas(q2, scales):
    import jax.experimental.pallas as pl

    nblocks, block = q2.shape
    br = _block_rows(nblocks)
    s2 = jnp.broadcast_to(scales[:, None], (nblocks, _LANES))
    kw = _vma_kw(q2, scales)
    spec = pl.BlockSpec((br, block), lambda i: (i, 0))
    sspec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    with kernel_scope("dequantize"):
        return pl.pallas_call(
            _dequant_kernel,
            grid=(nblocks // br,),
            in_specs=[spec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((nblocks, block), jnp.float32,
                                           **kw),
            interpret=_use_interpret(),
        )(q2, s2)


# ---- public API ----------------------------------------------------------


def quantize_flat(flat, block_size: Optional[int] = None,
                  use_kernels: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Quantize a flat float vector whose size divides into whole
    blocks.  Returns ``(q, scales)``: int8 ``[size]`` and f32
    ``[size // block]``.  Callers own padding (the collective pads to
    rank-shard boundaries anyway; :func:`quantize_dequantize` pads for
    arbitrary shapes)."""
    block = block_size or quant_block_size()
    if flat.ndim != 1:
        raise ValueError(f"quantize_flat takes a 1-D vector, got "
                         f"shape {flat.shape}")
    if flat.size % block:
        raise ValueError(
            f"size {flat.size} is not a whole number of {block}-element "
            "blocks — pad first (quantize_dequantize does)")
    x2 = flat.astype(jnp.float32).reshape(-1, block)
    if use_kernels is None:
        use_kernels = _kernels_on()
    if use_kernels and quant_kernel_eligible(flat.size, block):
        q2, scales = _quantize_pallas(x2)
    else:
        q2, scales = _quantize_xla(x2)
    return q2.reshape(-1), scales


def dequantize_flat(q, scales, block_size: Optional[int] = None,
                    use_kernels: Optional[bool] = None) -> jax.Array:
    """Inverse of :func:`quantize_flat`; returns f32 ``[size]``."""
    block = block_size or quant_block_size()
    q2 = q.reshape(-1, block)
    if use_kernels is None:
        use_kernels = _kernels_on()
    if use_kernels and quant_kernel_eligible(q.size, block):
        out = _dequantize_pallas(q2, scales)
    else:
        out = _dequantize_xla(q2, scales)
    return out.reshape(-1)


def quantize_dequantize(x, block_size: Optional[int] = None,
                        use_kernels: Optional[bool] = None):
    """Round-trip an arbitrary-shape float array through the wire
    format (pad → quantize → dequantize → unpad), returning it in the
    input dtype.  This IS the value the wire would carry — error
    feedback subtracts it from the true gradient, and the host
    (eager/torch) path sends it in place of real int8 payloads."""
    block = block_size or quant_block_size()
    shape, dtype = x.shape, x.dtype
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    q, scales = quantize_flat(flat, block, use_kernels)
    out = dequantize_flat(q, scales, block, use_kernels)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def wire_bytes(size: int, block_size: Optional[int] = None) -> int:
    """Bytes the wire format occupies for ``size`` elements: 1 B/elem
    payload + one f32 scale per (padded) block.  The accounting the
    bench and BENCH trajectory use."""
    block = block_size or quant_block_size()
    nblocks = -(-size // block)
    return nblocks * block + nblocks * 4


# ---- int4 wire -----------------------------------------------------------


def quant_kernel_eligible_int4(size: int, block: int) -> bool:
    """int4 Pallas eligibility: the int8 conditions plus a lane-aligned
    *packed* half-block (``block % 256 == 0``) so the [rows, block/2]
    int8 payload keeps a legal tile.  The default block 256 qualifies;
    smaller blocks take the identical-math XLA fallback."""
    return (quant_kernel_eligible(size, block)
            and (block // 2) % _LANES == 0)


def _scale_and_q4(x2):
    """Per-block-row scale + unpacked 4-bit codes (int32 lanes, one
    element per lane — packing is a separate step so both lowerings
    share this text)."""
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = absmax * (1.0 / 7.0)
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(x2 * inv), -7.0, 7.0).astype(jnp.int32)
    return scale, q


def _pack4(q):
    """[..., block] int32 4-bit codes -> [..., block/2] int8 bytes:
    element j in the low nibble, element j + block/2 in the high one
    (half-split layout; see module docstring).  Two's-complement
    masking keeps negative codes exact: (-7 & 0xF) = 9."""
    half = q.shape[-1] // 2
    lo = q[..., :half] & 0xF
    hi = q[..., half:] & 0xF
    v = lo | (hi << 4)
    return jnp.where(v >= 128, v - 256, v).astype(jnp.int8)


def _unpack4(p):
    """Inverse of :func:`_pack4`; returns [..., block] int32 codes in
    [-7, 7] (well, [-8, 7] for arbitrary bytes)."""
    b = p.astype(jnp.int32)
    b = jnp.where(b < 0, b + 256, b)
    lo = b & 0xF
    hi = (b >> 4) & 0xF
    sext = lambda x: jnp.where(x >= 8, x - 16, x)  # noqa: E731
    return jnp.concatenate([sext(lo), sext(hi)], axis=-1)


def _quantize4_xla(x2):
    scale, q = _scale_and_q4(x2)
    return _pack4(q), scale[:, 0]


def _dequantize4_xla(p2, scales):
    return _unpack4(p2).astype(jnp.float32) * scales[:, None]


def _quant4_kernel(x_ref, p_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale, q = _scale_and_q4(x)
    p_ref[...] = _pack4(q)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant4_kernel(p_ref, s_ref, o_ref):
    o_ref[...] = _unpack4(p_ref[...]).astype(jnp.float32) * s_ref[..., :1]


def _quantize4_pallas(x2):
    import jax.experimental.pallas as pl

    nblocks, block = x2.shape
    br = _block_rows(nblocks)
    kw = _vma_kw(x2)
    spec = pl.BlockSpec((br, block), lambda i: (i, 0))
    pspec = pl.BlockSpec((br, block // 2), lambda i: (i, 0))
    sspec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    with kernel_scope("quantize4"):
        p, s = pl.pallas_call(
            _quant4_kernel,
            grid=(nblocks // br,),
            in_specs=[spec],
            out_specs=[pspec, sspec],
            out_shape=(jax.ShapeDtypeStruct((nblocks, block // 2), jnp.int8,
                                            **kw),
                       jax.ShapeDtypeStruct((nblocks, _LANES), jnp.float32,
                                            **kw)),
            interpret=_use_interpret(),
        )(x2)
    return p, s[:, 0]


def _dequantize4_pallas(p2, scales):
    import jax.experimental.pallas as pl

    nblocks, half = p2.shape
    br = _block_rows(nblocks)
    s2 = jnp.broadcast_to(scales[:, None], (nblocks, _LANES))
    kw = _vma_kw(p2, scales)
    pspec = pl.BlockSpec((br, half), lambda i: (i, 0))
    spec = pl.BlockSpec((br, 2 * half), lambda i: (i, 0))
    sspec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    with kernel_scope("dequantize4"):
        return pl.pallas_call(
            _dequant4_kernel,
            grid=(nblocks // br,),
            in_specs=[pspec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((nblocks, 2 * half), jnp.float32,
                                           **kw),
            interpret=_use_interpret(),
        )(p2, s2)


def quantize_flat_int4(flat, block_size: Optional[int] = None,
                       use_kernels: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """int4 sibling of :func:`quantize_flat`.  Returns ``(packed,
    scales)``: int8 ``[size // 2]`` (two 4-bit lanes per byte,
    half-split layout) and f32 ``[size // block]``."""
    block = block_size or quant_block_size()
    if flat.ndim != 1:
        raise ValueError(f"quantize_flat_int4 takes a 1-D vector, got "
                         f"shape {flat.shape}")
    if block % 2:
        raise ValueError(f"int4 wire needs an even block size, got {block}")
    if flat.size % block:
        raise ValueError(
            f"size {flat.size} is not a whole number of {block}-element "
            "blocks — pad first (quantize_dequantize_int4 does)")
    x2 = flat.astype(jnp.float32).reshape(-1, block)
    if use_kernels is None:
        use_kernels = _kernels_on()
    if use_kernels and quant_kernel_eligible_int4(flat.size, block):
        p2, scales = _quantize4_pallas(x2)
    else:
        p2, scales = _quantize4_xla(x2)
    return p2.reshape(-1), scales


def dequantize_flat_int4(packed, scales, block_size: Optional[int] = None,
                         use_kernels: Optional[bool] = None) -> jax.Array:
    """Inverse of :func:`quantize_flat_int4`; ``packed`` holds
    ``size // 2`` bytes, returns f32 ``[size]``."""
    block = block_size or quant_block_size()
    p2 = packed.reshape(-1, block // 2)
    if use_kernels is None:
        use_kernels = _kernels_on()
    if use_kernels and quant_kernel_eligible_int4(2 * packed.size, block):
        out = _dequantize4_pallas(p2, scales)
    else:
        out = _dequantize4_xla(p2, scales)
    return out.reshape(-1)


def quantize_dequantize_int4(x, block_size: Optional[int] = None,
                             use_kernels: Optional[bool] = None):
    """int4 sibling of :func:`quantize_dequantize`: the value the 4-bit
    wire would carry, in the input shape/dtype — what error feedback
    subtracts on the int4 leg."""
    block = block_size or quant_block_size()
    shape, dtype = x.shape, x.dtype
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    p, scales = quantize_flat_int4(flat, block, use_kernels)
    out = dequantize_flat_int4(p, scales, block, use_kernels)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def wire_bytes_int4(size: int, block_size: Optional[int] = None) -> int:
    """int4 wire accounting: 0.5 B/elem payload + one f32 scale per
    (padded) block — ~0.51x of :func:`wire_bytes` at block 256."""
    block = block_size or quant_block_size()
    nblocks = -(-size // block)
    return nblocks * (block // 2) + nblocks * 4
