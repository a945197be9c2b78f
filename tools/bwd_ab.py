"""A/B: Pallas flash backward kernels vs the blockwise-XLA backward.

Measures the backward-only cost of both paths at a given shape and
prints one JSON line — the evidence VERDICT r3 #3 asks for before the
HVDT_FLASH_BWD default can be flipped.  Timing follows the repo
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
import numpy as np

from horovod_tpu.ops.pallas_kernels import (_flash_fwd_core,
                                            flash_attention,
                                            flash_grad_block)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    b, L, h, d = args.batch, args.seq, args.heads, args.dim
    q = jax.random.normal(jax.random.PRNGKey(0), (b, L, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, L, h, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, L, h, d), jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(3), (b, L, h, d),
                           jnp.bfloat16)

    @jax.jit
    def xla_bwd(q, k, v, do):
        _, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return vjp(do)

    @jax.jit
    def pallas_bwd(q, k, v, do):
        out, lse = _flash_fwd_core(q, k, v, True, d ** -0.5, 512, 512)
        return flash_grad_block(q, k, v, do, out, lse, causal=True,
                                scale=d ** -0.5)

    def fetch(r):
        return float(jnp.asarray(r[0]).ravel()[0].astype(jnp.float32))

    def bench(f):
        r = f(q, k, v, do)
        fetch(r)                              # compile + sync
        t0 = time.perf_counter()
        for _ in range(args.iters):
            r = f(q, k, v, do)
        fetch(r)                              # host fetch ends the region
        return (time.perf_counter() - t0) / args.iters

    # correctness gate before timing: a numerically wrong kernel must
    # not publish a speedup that could flip the HVDT_FLASH_BWD default.
    # The diff reduces ON DEVICE — fetching the full gradient tensors to
    # the host (GBs at these shapes) is slower than both backwards
    # together.  It takes the ALREADY-COMPUTED gradients,
    # so neither backward is compiled or executed a second time.
    @jax.jit
    def rel_diff(r1, r2):
        rels = [jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
                / jnp.maximum(jnp.abs(a.astype(jnp.float32)).max(), 1e-9)
                for a, b in zip(r1, r2)]
        return jnp.stack(rels).max()

    def stage(msg):
        print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

    stage("compiling+running xla_bwd")
    rx = xla_bwd(q, k, v, do)
    stage("xla_bwd dispatched; fetching")
    fetch0 = float(jnp.asarray(rx[0]).ravel()[0].astype(jnp.float32))
    stage(f"xla_bwd done ({fetch0:.3g}); compiling+running pallas_bwd")
    rp = pallas_bwd(q, k, v, do)
    fetch1 = float(jnp.asarray(rp[0]).ravel()[0].astype(jnp.float32))
    stage(f"pallas_bwd done ({fetch1:.3g}); computing on-device diff")
    rel = float(rel_diff(list(rx), list(rp)))
    stage(f"rel diff {rel:.3g}")
    correct = rel < 5e-2       # bf16 inputs, f32 accumulation
    t_x = bench(xla_bwd)
    t_p = bench(pallas_bwd) if correct else None
    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "flash_bwd_ab", "platform": dev.platform,
        "device_kind": dev.device_kind,
        "shape": {"batch": b, "seq": L, "heads": h, "dim": d},
        "rel_max_diff": rel,
        "correctness_ok": correct,
        "xla_ms": round(t_x * 1000, 2),
        "pallas_ms": round(t_p * 1000, 2) if correct else None,
        "pallas_speedup": round(t_x / t_p, 3) if correct else None,
    }))
    if not correct:
        sys.exit(1)


if __name__ == "__main__":
    main()
