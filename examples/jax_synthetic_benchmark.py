"""Synthetic throughput benchmark — images/sec with stddev.

Re-conception of ref: examples/pytorch/pytorch_synthetic_benchmark.py
(same CLI: --model/--batch-size/--num-iters/--num-batches-per-iter/
--num-warmup-batches/--use-adasum/--fp16-allreduce; same output shape:
per-iter img/sec lines, then totals).  TPU-native: bf16 compute, NHWC,
jitted train step with donated buffers, optional dp sharding over all
local devices via shard_map.

Single chip (or CPU sim):
    python examples/jax_synthetic_benchmark.py --num-iters 3

Scaling efficiency (the reference's headline metric — ref:
docs/benchmarks.rst:8-43, the 90%/68% @512-GPU table):
    python examples/jax_synthetic_benchmark.py --scaling-efficiency
measures rate(1) on one device and rate(n) dp-sharded over the whole
mesh, reporting ``rate(n) / (n * rate(1))``.
"""

import argparse
import time

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "vgg16", "mlp",
                            "transformer"])
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-device batch size")
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--use-adasum", action="store_true")
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--no-shard", action="store_true",
                   help="single-device step (no dp axis)")
    p.add_argument("--scaling-efficiency", action="store_true",
                   help="measure rate(n)/(n*rate(1)) over the dp mesh")
    p.add_argument("--autotune", action="store_true",
                   help="drive the fusion-knob autotuner from measured "
                        "step rates (ref: HOROVOD_AUTOTUNE)")
    p.add_argument("--fused-optimizer", action="store_true",
                   help="run the update through the fused Pallas "
                        "optimizer kernels (hvd.fused_sgd) — one HBM "
                        "pass per eligible parameter; also the starting "
                        "point for the autotuner's fused dimension "
                        "(HVDT_AUTOTUNE_FUSED_OPTIMIZER=1)")
    return p.parse_args(argv)


def measure(args, use_shard: bool, quiet: bool = False) -> float:
    """One full benchmark run; returns mean images(samples)/sec total."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    mesh = hvd.mesh()
    n_dev = mesh.devices.size if use_shard else 1
    global_batch = args.batch_size * n_dev

    key = jax.random.PRNGKey(0)
    if args.model in ("resnet50", "resnet101"):
        from horovod_tpu.models import (ResNetConfig, resnet50_init,
                                        resnet_loss)

        cfg = ResNetConfig(num_classes=1000, dtype=jnp.bfloat16,
                           depth=int(args.model[6:]))
        params, stats = resnet50_init(key, cfg)
        data = jax.random.normal(
            key, (global_batch, args.image_size, args.image_size, 3),
            jnp.bfloat16)
        labels = jnp.zeros((global_batch,), jnp.int32)

        def loss_fn(p, xb, yb):
            loss, _ = resnet_loss(p, stats, xb, yb, cfg)
            return loss
    elif args.model == "vgg16":
        from horovod_tpu.models import VGGConfig, vgg16_init, vgg_loss

        cfg = VGGConfig(num_classes=1000, dtype=jnp.bfloat16,
                        image_size=args.image_size)
        params = vgg16_init(key, cfg)
        data = jax.random.normal(
            key, (global_batch, args.image_size, args.image_size, 3),
            jnp.bfloat16)
        labels = jnp.zeros((global_batch,), jnp.int32)

        def loss_fn(p, xb, yb):
            return vgg_loss(p, xb, yb, cfg)
    elif args.model == "transformer":
        from horovod_tpu.models import (TransformerConfig, transformer_init,
                                        transformer_loss)

        cfg = TransformerConfig(vocab=32000, layers=12, d_model=768,
                                heads=12, kv_heads=12, d_ff=3072,
                                max_seq=512, dtype=jnp.bfloat16)
        params = transformer_init(key, cfg)
        data = jax.random.randint(key, (global_batch, 512), 0, 32000)
        labels = None

        def loss_fn(p, xb, yb):
            return transformer_loss(p, xb, cfg)
    else:
        from horovod_tpu.models import mlp_init, mlp_loss

        params = mlp_init(key)
        data = jax.random.normal(key, (global_batch, 784))
        labels = jnp.zeros((global_batch,), jnp.int32)
        loss_fn = mlp_loss

    def build_step(threshold_bytes=None, fused=None):
        """(Re-)jit the train step for a fusion-bucket threshold — the
        autotuner's 'apply' operation (thresholds are trace-time
        constants under XLA).  ``fused`` picks the update lowering
        (fused Pallas kernels vs stock optax) — the autotuner's second
        A/B dimension."""
        from horovod_tpu.step_pipeline import donated_step

        if fused is not None:
            # Autotuner-driven A/B: both legs use the fused
            # transformation (use_kernels flips the lowering) so the
            # opt-state structure survives mid-run knob changes.
            inner = hvd.fused_sgd(0.01, momentum=0.9,
                                  use_kernels=bool(fused))
        elif args.fused_optimizer:
            inner = hvd.fused_sgd(0.01, momentum=0.9)
        else:
            inner = optax.sgd(0.01, momentum=0.9)
        opt = hvd.DistributedOptimizer(
            inner,
            op=hvd.Adasum if args.use_adasum else hvd.Average,
            compression=(hvd.Compression.bf16 if args.fp16_allreduce
                         else hvd.Compression.none),
            threshold_bytes=threshold_bytes)

        def local_step(params, opt_state, xb, yb):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, xb, yb))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            if use_shard:
                loss = jax.lax.pmean(loss, "dp")
            return optax.apply_updates(params, updates), opt_state, loss

        # donated_step = jit + params/opt-state donation + the persistent
        # compilation cache (env-transparent, HVDT_COMPILATION_CACHE).
        if not use_shard:
            return opt, donated_step(local_step, donate_argnums=(0, 1))
        return opt, donated_step(jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P("dp"), P() if labels is None else P("dp")),
            out_specs=(P(), P(), P())),
            donate_argnums=(0, 1))

    from horovod_tpu.autotune import autotuned_step

    # Env-transparent autotune: `hvdtrun --autotune` exports
    # HVDT_AUTOTUNE=1 and the wrapper engages by itself (zero-overhead
    # passthrough otherwise); --autotune here just forces it on.  The
    # builder records the optimizer each (re-)build so opt always is the
    # instance the live step closes over.
    built = {}

    def builder(tb, fused=None):
        built["opt"], step_fn = build_step(tb, fused)
        return step_fn

    step = autotuned_step(builder, tree_example=params,
                          enabled=(True if args.autotune and use_shard
                                   else None if use_shard else False),
                          steps_per_sample=args.num_batches_per_iter)
    opt = built["opt"]
    opt_state = opt.init(params)
    if use_shard:
        data = jax.device_put(data, NamedSharding(mesh, P("dp")))
        if labels is not None:
            labels = jax.device_put(labels, NamedSharding(mesh, P("dp")))

    dev = jax.devices()[0]
    verbose = hvd.rank() == 0 and not quiet
    if verbose:
        print(f"Model: {args.model}")
        print(f"Batch size: {global_batch} ({args.batch_size}/device, "
              f"{n_dev} devices)")
        print(f"Device: {dev.platform}:{dev.device_kind}")

    def run_batches(n):
        nonlocal params, opt_state
        for _ in range(n):
            params, opt_state, loss = step(params, opt_state, data, labels)
        # Host fetch so the timed region covers real device work.
        float(jnp.sum(loss))

    run_batches(args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        rate = global_batch * args.num_batches_per_iter / dt
        if verbose:
            print(f"Iter #{i}: {rate:.1f} img/sec total")
            if step.enabled and step.bucket_bytes:
                print(f"  autotune bucket {step.bucket_bytes // 2**20} MiB")
        img_secs.append(rate)

    if verbose:
        mean, std = np.mean(img_secs), np.std(img_secs)
        print(f"Img/sec total: {mean:.1f} +- {1.96 * std:.1f}")
        print(f"Img/sec/device: {mean / n_dev:.1f}")
        if step.enabled:
            print(f"Autotune: {step.summary()}")
    return float(np.mean(img_secs))


def main():
    args = parse_args()

    import horovod_tpu as hvd

    hvd.init()
    if not args.scaling_efficiency:
        measure(args, use_shard=not args.no_shard)
        return

    n = hvd.mesh().devices.size
    rate1 = measure(args, use_shard=False, quiet=True)
    raten = measure(args, use_shard=True, quiet=True)
    eff = raten / (n * rate1) if n and rate1 else 0.0
    if hvd.rank() == 0:
        print(f"rate(1)     : {rate1:.1f} samples/sec")
        print(f"rate({n})    : {raten:.1f} samples/sec "
              f"({raten / n:.1f}/device)")
        print(f"scaling efficiency rate({n})/({n}*rate(1)) = {eff:.3f}")
        import json

        print(json.dumps({"metric": "scaling_efficiency",
                          "value": round(eff, 4), "n_devices": n,
                          "model": args.model,
                          "rate1": round(rate1, 2),
                          "raten": round(raten, 2)}))


if __name__ == "__main__":
    main()
