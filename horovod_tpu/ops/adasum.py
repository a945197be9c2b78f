"""Adasum: scale-invariant gradient combination.

TPU-native re-conception of the reference's Adasum
(ref: ops/adasum/adasum.h — recursive vector-halving distance-doubling
with dot-product-based scale mixing; ops/adasum_mpi_operations.cc,
ops/adasum_gpu_operations.cc; docs/adasum_user_guide.rst).

The Adasum combination of two gradients a, b is::

    adasum(a, b) = (1 - (a·b)/(2·a·a)) · a  +  (1 - (a·b)/(2·b·b)) · b

which reduces to the sum for orthogonal gradients and to (a+b)/2 for
parallel ones, making the result robust to learning-rate scaling across
ranks.  Across N = 2^k ranks it is applied recursively in a binary tree
(ref: adasum.h:33 requires power-of-2 ranks).

Two implementations:

* ``adasum_allreduce`` — jit/shard_map path, sharded formulation:
  all_to_all distributes shard s of every rank's vector to rank s, the
  binary combination tree runs on 1/N shards with exact full-vector dots
  via one batched psum per level, and a psum-embed reassembles — O(G)
  wire and memory per rank, the bandwidth shape of the reference's
  recursive halving (nccl_operations.cc:249-517).
* ``host_adasum`` — eager-path version over host arrays (native C++ VHDD
  when the TCP backend is active).
"""

from __future__ import annotations

from typing import Any, List

import numpy as np

__all__ = ["adasum_allreduce", "host_adasum", "adasum_pair"]


def adasum_pair(a, b, dot_ab, dot_aa, dot_bb):
    """One Adasum combination given precomputed dots (works for np/jnp)."""
    eps = np.finfo(np.float32).tiny
    scale_a = 1.0 - dot_ab / (2.0 * (dot_aa + eps))
    scale_b = 1.0 - dot_ab / (2.0 * (dot_bb + eps))
    return scale_a * a + scale_b * b


def _np_adasum_tree(vectors: List[np.ndarray]) -> np.ndarray:
    """Reference-semantics binary-tree Adasum over a list of rank vectors."""
    vecs = [v.astype(np.float64) for v in vectors]
    n = len(vecs)
    if n & (n - 1):
        raise ValueError(f"Adasum requires a power-of-2 rank count, got {n}")
    while len(vecs) > 1:
        nxt = []
        for i in range(0, len(vecs), 2):
            a, b = vecs[i], vecs[i + 1]
            nxt.append(adasum_pair(a, b, float(a @ b), float(a @ a),
                                   float(b @ b)))
        vecs = nxt
    return vecs[0]


def host_adasum(flat: np.ndarray, process_set) -> np.ndarray:
    """Eager-path Adasum across the processes of ``process_set``.

    Correctness-first: allgather the flattened gradients, then every rank
    computes the identical tree reduction locally (deterministic).  The
    bandwidth-optimal path is the jit-side ``adasum_allreduce``."""
    from . import host_collectives as hostc
    from . import tcp_backend

    p = process_set.size()
    if p == 1:
        return flat
    if tcp_backend.enabled() and not (p & (p - 1)):
        # Native VHDD (native/src/adasum.cc) — bandwidth shape of the
        # reference's recursive halving, O(G) wire bytes per rank.
        return tcp_backend.tcp_adasum(np.ascontiguousarray(flat),
                                      process_set)
    orig_dtype = flat.dtype
    stacked = hostc.host_allgather(flat[None, :], process_set,
                                   [1] * p)  # (p, n)
    out = _np_adasum_tree([stacked[i] for i in range(p)])
    return out.astype(orig_dtype)


def adasum_allreduce(x, axis: str = "dp"):
    """Adasum allreduce inside shard_map/jit over a mesh axis — the
    sharded (reduce-scatter-shaped) formulation.

    Mirrors the bandwidth shape of the reference's recursive halving
    (ref: adasum.h FusedAllreduce; AdasumGpuAllreduceOp = local
    reduce-scatter → cross Adasum → local all-gather):

    1. all_to_all the flattened vector so rank s holds shard s of EVERY
       rank's gradient — O(G) wire, O(G) memory per rank (the previous
       all-gather formulation was O(p·G) both).
    2. run the binary combination tree on the local shards; the
       dot-products per pair are computed exactly as psums of per-shard
       partials (one batched psum per tree level, 3 scalars per pair).
    3. reassemble by zero-embedding each combined shard and psum-ing —
       one collective that both gathers and restores the VMA-invariant
       type (device.invariant_allgather_shards).

    bf16-safe: combination math in f32.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .device import _axis_size_static, invariant_allgather_shards

    def _one(t):
        n = _axis_size_static(axis)
        if n & (n - 1):
            raise ValueError(f"Adasum requires power-of-2 ranks, got {n}")
        orig_shape = t.shape
        orig_dtype = t.dtype
        flat = t.reshape(-1).astype(jnp.float32)
        if n == 1:
            return flat.reshape(orig_shape).astype(orig_dtype)
        pad = (-flat.size) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
        chunk = flat.size // n
        # rows after a2a: row j = rank j's values on MY shard's index range
        rows = lax.all_to_all(flat.reshape(n, chunk), axis, split_axis=0,
                              concat_axis=0, tiled=False)
        vecs = [rows[j] for j in range(n)]
        while len(vecs) > 1:
            pairs = [(vecs[i], vecs[i + 1]) for i in range(0, len(vecs), 2)]
            # exact full-vector dots: psum of per-shard partials, batched
            # into one collective per tree level
            partial = jnp.stack([
                jnp.stack([jnp.vdot(a, b), jnp.vdot(a, a), jnp.vdot(b, b)])
                for a, b in pairs])                       # [pairs, 3]
            dots = lax.psum(partial, axis)
            vecs = [adasum_pair(a, b, dots[k, 0], dots[k, 1], dots[k, 2])
                    for k, (a, b) in enumerate(pairs)]
        full = invariant_allgather_shards(vecs[0], axis)
        if pad:
            full = full[:-pad]
        return full.reshape(orig_shape).astype(orig_dtype)

    return jax.tree.map(_one, x)
