"""A/B: ring attention's per-step block primitives, Pallas vs jnp.

VERDICT r3 #3 evidence for the ``HVDT_RING_PALLAS`` default.  sp>=2
cannot run on the one real chip, but the ring's cost is sp repetitions
of exactly two per-device primitives (parallel/ring_attention.py):

  fwd step:  _block_update (jnp)        vs flash_block_update (Pallas)
  bwd step:  the blockwise jnp VJP body vs flash_grad_block (Pallas)

Both are pure per-device ops — measuring them on one chip at the
ring-local shard shapes IS the per-step cost a ring member pays; the
ppermute transfer rides ICI concurrently (np=8 CPU path covers the
schedule).  Prints one JSON line per shape.  Timing follows the repo
contract: each timed region ends with a host fetch of a scalar that
data-depends on the result.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import (flash_block_update,
                                            flash_grad_block)
from horovod_tpu.parallel.ring_attention import (_NEG_INF, _block_update,
                                                 _bwd_block_grads)


def bench(f, args_, iters, fetch):
    r = f(*args_)
    fetch(r)                               # compile + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(*args_)
    fetch(r)                               # host fetch ends the region
    return (time.perf_counter() - t0) / iters


def run_shape(b, l, h, d, iters):
    """l is the LOCAL (per-ring-member) sequence shard."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, l, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, l, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, l, h, d), jnp.bfloat16)
    do = jax.random.normal(ks[3], (b, l, h, d), jnp.bfloat16)
    acc = jnp.zeros((b, l, h, d), jnp.float32)
    m0 = jnp.full((b, h, l), _NEG_INF, jnp.float32)
    s0 = jnp.zeros((b, h, l), jnp.float32)
    scale = d ** -0.5
    full = jnp.ones((1, 1, 1, 1), bool)    # the sp-1 "fully visible" steps

    @jax.jit
    def fwd_jnp(q, k, v, acc, m, s):
        return _block_update(q, k, v, acc, m, s, full, scale)

    @jax.jit
    def fwd_pallas(q, k, v, acc, m, s):
        return flash_block_update(q, k, v, acc, m, s, q_offset=0,
                                  k_offset=0, causal=False, scale=scale)

    def fetch3(r):
        return float(r[0].ravel()[0].astype(jnp.float32))

    t_fj = bench(fwd_jnp, (q, k, v, acc, m0, s0), iters, fetch3)
    t_fp = bench(fwd_pallas, (q, k, v, acc, m0, s0), iters, fetch3)

    # Backward step inputs: out/lse from one full-visibility update.
    acco, mo, so = fwd_jnp(q, k, v, acc, m0, s0)
    so = jnp.maximum(so, 1e-30)
    out = (acco / so.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    lse = mo + jnp.log(so)
    delta = jnp.einsum("bqhd,bqhd->bqh", do, out,
                       preferred_element_type=jnp.float32)

    @jax.jit
    def bwd_jnp(q, k, v, do, lse, delta):
        # the PRODUCTION _ring_diff_bwd step body (imported, not copied
        # — ADVICE r4: an inline re-implementation can silently drift),
        # full-visibility case, no GQA (group=1).
        f32 = jnp.float32
        return _bwd_block_grads(q.astype(f32), do.astype(f32), k, v, lse,
                                delta.transpose(0, 2, 1), None, scale, 1)

    @jax.jit
    def bwd_pallas(q, k, v, do, out, lse, delta):
        return flash_grad_block(q, k, v, do, out, lse, causal=False,
                                scale=scale,
                                delta=delta.transpose(0, 2, 1))

    # Correctness gate (reduced on the device: the gradients are up to
    # 100 MB each): a wrong kernel must not publish a speedup.
    @jax.jit
    def rel_diff(r1, r2):
        rels = [jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)
                        ).max()
                / jnp.maximum(jnp.abs(a.astype(jnp.float32)).max(), 1e-9)
                for a, b_ in zip(r1, r2)]
        return jnp.stack(rels).max()

    rel = float(rel_diff(list(bwd_jnp(q, k, v, do, lse, delta)),
                         list(bwd_pallas(q, k, v, do, out, lse, delta))))
    correct = rel < 5e-2                   # bf16 inputs, f32 accumulation

    t_bj = bench(bwd_jnp, (q, k, v, do, lse, delta), iters, fetch3)
    t_bp = (bench(bwd_pallas, (q, k, v, do, out, lse, delta), iters,
                  fetch3) if correct else None)

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "ring_block_ab", "platform": dev.platform,
        "device_kind": dev.device_kind,
        "shape": {"batch": b, "local_seq": l, "heads": h, "dim": d},
        "fwd_jnp_ms": round(t_fj * 1000, 3),
        "fwd_pallas_ms": round(t_fp * 1000, 3),
        "fwd_pallas_speedup": round(t_fj / t_fp, 3),
        "bwd_rel_max_diff": rel,
        "bwd_correctness_ok": correct,
        "bwd_jnp_ms": round(t_bj * 1000, 3),
        "bwd_pallas_ms": round(t_bp * 1000, 3) if correct else None,
        "bwd_pallas_speedup": round(t_bj / t_bp, 3) if correct else None,
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--local-seqs", default="2048,4096,8192")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()
    for l in [int(x) for x in args.local_seqs.split(",")]:
        run_shape(args.batch, l, args.heads, args.dim, args.iters)


if __name__ == "__main__":
    main()
