"""Below-XLA ResNet roofline probe (VERDICT r4 weak #3 closure).

The bs128 ResNet-50 step is HBM-roofline-pinned (hbm_util 1.0,
docs/performance.md); round 2 named two residual traffic levers — conv
layout copies and unfused BN passes — and neither was ever measured
beneath XLA.  This probe measures ONE lever end-to-end on the real chip:
for the bottleneck blocks' hot 1x1 convs (the matmul-shaped majority of
ResNet-50 conv FLOPs), does a Pallas matmul with the BN affine fused
into its epilogue (ops/conv_fused.py) move fewer HBM bytes than XLA's
scheduling of the same conv + affine + relu?

Three legs per shape, one within-window comparison (docs/performance.md
discipline):
  * xla_conv   — lax.conv_general_dilated NHWC + affine + relu, jitted
                 (the production path's shape: models/resnet.py _conv ->
                 _batch_norm normalized form -> relu)
  * xla_matmul — the same math expressed as reshape+dot, jitted (strips
                 any conv-layout handling; isolates the layout lever
                 from the fusion lever)
  * pallas     — ops/conv_fused.matmul_bn_relu (single fused write)

Timing follows the repo contract: each timed region ends with a host
fetch of a scalar that data-depends on the last result; >=30 calls per
region.
Correctness-gates Pallas against the f32 reference before timing —
a wrong kernel must not publish a speedup.  Prints one JSON line per
shape with ms/call, effective GB/s, and the speedup ratios.
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
from jax import lax

from horovod_tpu.ops.conv_fused import (conv1x1_bn_relu,
                                        conv1x1_bn_relu_reference,
                                        conv1x1_bn_train,
                                        conv1x1_bn_train_reference,
                                        matmul_bn_relu)

# The four hot 1x1 shapes of bs128 ResNet-50 stages 3/4 (NHWC,
# models/resnet.py bottleneck conv1/conv3; stage-2's 64-channel convs
# are excluded — N=64 is below the 128-lane tile).
SHAPES = [
    ("s3_contract", 128, 28, 28, 512, 128),
    ("s3_expand", 128, 28, 28, 128, 512),
    ("s4_contract", 128, 14, 14, 1024, 256),
    ("s4_expand", 128, 14, 14, 256, 1024),
]


def bench(f, args_, iters):
    r = f(*args_)                      # compile + first run
    float(jnp.sum(r[0, 0]))            # sync
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(*args_)
    float(jnp.sum(r[0, 0]))            # host fetch ends the region
    return (time.perf_counter() - t0) / iters


def run_shape(label, b, h, w_, cin, cout, iters):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, h, w_, cin), jnp.bfloat16)
    w = jax.random.normal(ks[1], (cin, cout), jnp.bfloat16) * (cin ** -0.5)
    scale = jax.random.uniform(ks[2], (cout,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(ks[3], (cout,), jnp.float32)

    @jax.jit
    def xla_conv(x, w, scale, bias):
        y = lax.conv_general_dilated(
            x, w.reshape(1, 1, cin, cout), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.maximum(y * scale + bias, 0.0).astype(x.dtype)

    @jax.jit
    def xla_matmul(x, w, scale, bias):
        y = jnp.dot(x.reshape(b * h * w_, cin), w,
                    preferred_element_type=jnp.float32)
        y = jnp.maximum(y * scale + bias, 0.0)
        return y.reshape(b, h, w_, cout).astype(x.dtype)

    @jax.jit
    def pallas(x, w, scale, bias):
        return conv1x1_bn_relu(x, w, scale, bias)

    # Correctness gate (on-device reduce; bf16 inputs, f32 accumulation).
    ref = conv1x1_bn_relu_reference(x, w, scale, bias)

    @jax.jit
    def rel(a, r):
        af, rf = a.astype(jnp.float32), r.astype(jnp.float32)
        return jnp.abs(af - rf).max() / jnp.maximum(jnp.abs(rf).max(), 1e-9)

    rels = {n: float(rel(f(x, w, scale, bias), ref))
            for n, f in (("xla_conv", xla_conv), ("xla_matmul", xla_matmul),
                         ("pallas", pallas))}
    ok = all(v < 2e-2 for v in rels.values())

    t = {n: bench(f, (x, w, scale, bias), iters)
         for n, f in (("xla_conv", xla_conv), ("xla_matmul", xla_matmul),
                      *((("pallas", pallas),) if ok else ()))}

    m = b * h * w_
    bytes_min = 2 * (m * cin + cin * cout + m * cout) + 8 * cout
    dev = jax.devices()[0]
    out = {"metric": "resnet_1x1_bn_probe", "shape": label,
           "platform": dev.platform, "device_kind": dev.device_kind,
           "m_k_n": [m, cin, cout], "iters": iters,
           "correctness_ok": ok, "rel_max_diff": rels,
           "min_traffic_mb": round(bytes_min / 2 ** 20, 1)}
    for n, dt in t.items():
        out[f"{n}_ms"] = round(dt * 1e3, 3)
        out[f"{n}_eff_gbps"] = round(bytes_min / dt / 1e9, 1)
    if ok:
        out["pallas_vs_conv"] = round(t["xla_conv"] / t["pallas"], 3)
        out["pallas_vs_matmul"] = round(t["xla_matmul"] / t["pallas"], 3)
        out["matmul_vs_conv"] = round(t["xla_conv"] / t["xla_matmul"], 3)
    print(json.dumps(out), flush=True)


def run_shape_train(label, b, h, w_, cin, cout, iters):
    """TRAIN-form leg: batch-stat BN forces (at least) two reads of the
    conv output under XLA; the fused kernel emits z + stat partials in
    one pass (ops/conv_fused.matmul_batch_stats) so z is read once."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, h, w_, cin), jnp.bfloat16)
    w = jax.random.normal(ks[1], (cin, cout), jnp.bfloat16) * (cin ** -0.5)
    gamma = jax.random.uniform(ks[2], (cout,), jnp.float32, 0.5, 1.5)
    beta = jax.random.normal(ks[3], (cout,), jnp.float32)
    eps = 1e-5

    @jax.jit
    def xla_train(x, w, gamma, beta):
        z = lax.conv_general_dilated(
            x, w.reshape(1, 1, cin, cout), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        zf = z.astype(jnp.float32)
        mean = zf.mean(axis=(0, 1, 2))
        var = zf.var(axis=(0, 1, 2))
        y = (zf - mean) * lax.rsqrt(var + eps) * gamma + beta
        return jnp.maximum(y, 0.0).astype(x.dtype), mean, var

    @jax.jit
    def pallas_train(x, w, gamma, beta):
        return conv1x1_bn_train(x, w, gamma, beta, eps=eps)

    ref = conv1x1_bn_train_reference(x, w, gamma, beta, eps=eps)

    @jax.jit
    def rel(out, r):
        rels = [jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)
                        ).max()
                / jnp.maximum(jnp.abs(b_.astype(jnp.float32)).max(), 1e-9)
                for a, b_ in zip(out, r)]
        return jnp.stack(rels).max()

    rels = {n: float(rel(list(f(x, w, gamma, beta)), list(ref)))
            for n, f in (("xla_train", xla_train),
                         ("pallas_train", pallas_train))}
    # bf16 z-write rounding bounds the fused y at ~1e-2 rel
    ok = all(v < 2e-2 for v in rels.values())

    # Time the y output only (y data-depends on mean/var, so the stats
    # cannot be dead-code-eliminated); bench's fetch needs an array.
    xla_y = jax.jit(lambda *a: xla_train(*a)[0])
    pallas_y = jax.jit(lambda *a: pallas_train(*a)[0])
    t = {n: bench(f, (x, w, gamma, beta), iters)
         for n, f in (("xla_train", xla_y),
                      *((("pallas_train", pallas_y),) if ok else ()))}

    m = b * h * w_
    bytes_min = 2 * (m * cin + cin * cout + 2 * m * cout) + 12 * cout
    dev = jax.devices()[0]
    out = {"metric": "resnet_1x1_bn_train_probe", "shape": label,
           "platform": dev.platform, "device_kind": dev.device_kind,
           "m_k_n": [m, cin, cout], "iters": iters,
           "correctness_ok": ok, "rel_max_diff": rels,
           "min_traffic_mb": round(bytes_min / 2 ** 20, 1)}
    for n, dt in t.items():
        out[f"{n}_ms"] = round(dt * 1e3, 3)
        out[f"{n}_eff_gbps"] = round(bytes_min / dt / 1e9, 1)
    if ok:
        out["pallas_vs_conv"] = round(t["xla_train"] / t["pallas_train"], 3)
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--shapes", default=",".join(s[0] for s in SHAPES))
    ap.add_argument("--form", choices=("affine", "train"),
                    default="affine")
    args = ap.parse_args()
    want = set(args.shapes.split(","))
    run = run_shape if args.form == "affine" else run_shape_train
    for spec in SHAPES:
        if spec[0] in want:
            run(*spec, iters=args.iters)


if __name__ == "__main__":
    main()
