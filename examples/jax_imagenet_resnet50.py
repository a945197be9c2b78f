"""ImageNet ResNet-50 training — the reference's headline workload.

Re-conception of ref: examples/pytorch/pytorch_imagenet_resnet50.py —
same program shape: warmup+staircase LR schedule scaled by world size,
DistributedOptimizer with optional bf16 wire compression, rank-0
checkpointing with broadcast-on-restart, per-epoch metric averaging.

TPU-native: bf16 compute, NHWC layout, jitted shard_map step over the
'dp' mesh axis, device prefetch of the input pipeline.  Real data plugs
in via --train-dir with `.npy` shards (or swap `synthetic_batches` for a
tf.data/grain pipeline); without it the script runs on synthetic data so
the full loop (schedule, checkpoint, metrics) is exercisable anywhere.
"""

import argparse
import os
import time

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--train-dir", default=None,
                   help="directory of {images,labels}_*.npy shards")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-device batch size")
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="LR for a single device (scaled by world size)")
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--steps-per-epoch", type=int, default=20,
                   help="synthetic-mode steps per epoch")
    p.add_argument("--checkpoint", default="/tmp/resnet50_ckpt.npz")
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.callbacks import warmup_schedule
    from horovod_tpu.data import prefetch_to_device
    from horovod_tpu.models import ResNetConfig, resnet50_init, resnet_loss

    hvd.init()
    mesh = hvd.mesh()
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev

    cfg = ResNetConfig(num_classes=1000, dtype=jnp.bfloat16)
    params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)

    # Linear-warmup then staircase decay, scaled by world size
    # (ref: pytorch_imagenet_resnet50.py adjust_learning_rate).
    steps_per_epoch = args.steps_per_epoch
    staircase = optax.piecewise_constant_schedule(
        args.base_lr * n_dev,
        {int(e * steps_per_epoch): d for e, d in ((30, 0.1), (60, 0.1),
                                                  (80, 0.1))})
    sched = warmup_schedule(base_lr=args.base_lr, scale=n_dev,
                            warmup_steps=int(args.warmup_epochs
                                             * steps_per_epoch),
                            after=staircase)
    opt = hvd.DistributedOptimizer(
        optax.sgd(sched, momentum=0.9),
        compression=(hvd.Compression.bf16 if args.fp16_allreduce
                     else hvd.Compression.none))
    opt_state = opt.init(params)

    # Resume: rank 0 loads, everyone receives via broadcast
    # (ref: checkpoint-broadcast pattern, SURVEY.md §5.4).
    start_epoch = 0
    if os.path.exists(args.checkpoint) and hvd.rank() == 0:
        ck = np.load(args.checkpoint, allow_pickle=True)
        flat = list(ck["params"])
        params = jax.tree.unflatten(jax.tree.structure(params), flat)
        start_epoch = int(ck["epoch"])
        print(f"resumed from {args.checkpoint} at epoch {start_epoch}")
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(opt_state, root_rank=0)
    start_epoch = int(np.asarray(hvd.broadcast(
        np.int64(start_epoch), root_rank=0, name="start_epoch")))

    def local_step(params, stats, opt_state, x, y):
        def loss_fn(p):
            loss, new_stats = resnet_loss(p, stats, x, y, cfg)
            return loss, new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # Cross-replica running-stat averaging (SyncBatchNorm analog).
        new_stats = jax.tree.map(lambda s: jax.lax.pmean(s, "dp"), new_stats)
        return params, new_stats, opt_state, jax.lax.pmean(loss, "dp")

    # donated_step: params/stats/opt-state buffers donated through the
    # pipeline + the persistent compilation cache engaged when
    # HVDT_COMPILATION_CACHE names a directory.
    step = hvd.donated_step(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P(), P(), P())),
        donate_argnums=(0, 1, 2))

    def synthetic_batches(n):
        rng = np.random.default_rng(1)
        for _ in range(n):
            yield (rng.normal(size=(global_batch, 224, 224, 3))
                   .astype(np.float32),
                   rng.integers(0, 1000, global_batch).astype(np.int32))

    def disk_batches():
        import glob

        files = sorted(glob.glob(os.path.join(args.train_dir,
                                              "images_*.npy")))
        for f in files:
            images = np.load(f)
            labels = np.load(f.replace("images_", "labels_"))
            for s in range(len(images) // global_batch):
                sl = slice(s * global_batch, (s + 1) * global_batch)
                yield images[sl], labels[sl]

    sharding = NamedSharding(mesh, P("dp"))
    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        batches = (disk_batches() if args.train_dir
                   else synthetic_batches(steps_per_epoch))
        n_steps = 0
        for xb, yb in prefetch_to_device(batches, size=2,
                                         sharding=sharding):
            params, stats, opt_state, loss = step(params, stats, opt_state,
                                                  xb, yb)
            n_steps += 1
        # Host fetch: the timed epoch must cover real device work.
        float(loss)
        dt = time.perf_counter() - t0
        rate = n_steps * global_batch / dt
        avg_loss = float(np.asarray(hvd.allreduce(
            np.float32(loss), name="epoch_loss")))
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={avg_loss:.4f} "
                  f"{rate:.1f} img/sec ({rate / n_dev:.1f}/device)")
            flat = [np.asarray(l) for l in jax.tree.leaves(params)]
            np.savez(args.checkpoint, params=np.array(flat, dtype=object),
                     epoch=epoch + 1)

    if hvd.rank() == 0:
        print("training complete.")


if __name__ == "__main__":
    main()
