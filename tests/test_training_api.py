"""DistributedOptimizer / functions / compression / sync-BN / callbacks.

Reference analogs: optimizer wrapper correctness via autograd
(test_torch.py DistributedOptimizer tests), broadcast_parameters/object
(test_torch.py test_broadcast_state), keras callback tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map as shard_map


def test_distributed_optimizer_converges(hvd, mesh8):
    """DP training with DistributedOptimizer reaches the same solution as
    single-device training with the mean gradient."""
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))

    w0 = jnp.zeros((3,))
    x = jnp.asarray(np.random.RandomState(0).randn(64, 3), jnp.float32)
    true_w = jnp.asarray([1.0, -2.0, 0.5])
    y = x @ true_w

    def step(w, opt_state, x, y):
        def per_shard(w, opt_state, xs, ys):
            g = jax.grad(lambda w: jnp.mean((xs @ w - ys) ** 2))(w)
            updates, opt_state = opt.update(g, opt_state, w)
            return optax.apply_updates(w, updates), opt_state
        return shard_map(per_shard, mesh=mesh8,
                         in_specs=(P(), P(), P("dp"), P("dp")),
                         out_specs=(P(), P()))(w, opt_state, x, y)

    opt_state = opt.init(w0)
    w = w0
    stepj = jax.jit(step)
    for i in range(1500):
        w, opt_state = stepj(w, opt_state, x, y)
        if i % 50 == 0:
            jax.block_until_ready(w)  # 1-core CPU: bound in-flight execs
    np.testing.assert_allclose(np.asarray(w), np.asarray(true_w), atol=1e-2)


def test_distributed_optimizer_grad_equivalence(hvd, mesh8):
    """One wrapped step == mean-of-shard-grads step."""
    opt = hvd.DistributedOptimizer(optax.sgd(1.0))
    w = jnp.asarray([1.0, 2.0])
    x = jnp.arange(16.0).reshape(8, 2)

    def loss(w, xs):
        return jnp.mean(jnp.sum(xs * w, axis=-1))

    def per_shard(w, opt_state, xs):
        g = jax.grad(loss)(w, xs)
        updates, opt_state = opt.update(g, opt_state, w)
        return optax.apply_updates(w, updates), opt_state

    opt_state = opt.init(w)
    w2, _ = shard_map(per_shard, mesh=mesh8,
                      in_specs=(P(), P(), P("dp")),
                      out_specs=(P(), P()))(w, opt_state, x)
    g_full = jax.grad(loss)(w, x)  # global mean gradient
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w - g_full),
                               rtol=1e-6)


def test_backward_passes_per_step(hvd, mesh8):
    """MultiSteps aggregation: params move only every k-th step."""
    opt = hvd.DistributedOptimizer(optax.sgd(0.5), backward_passes_per_step=2)
    w = jnp.asarray([0.0])
    opt_state = opt.init(w)

    def per_shard(w, opt_state, g):
        updates, opt_state = opt.update(g[0], opt_state, w)
        return optax.apply_updates(w, updates), opt_state

    step = jax.jit(lambda w, s, g: shard_map(
        per_shard, mesh=mesh8, in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P()))(w, s, g))

    g = jnp.ones((8, 1))
    w1, opt_state = step(w, opt_state, g)
    np.testing.assert_allclose(np.asarray(w1), [0.0])  # accumulating
    w2, opt_state = step(w1, opt_state, g)
    np.testing.assert_allclose(np.asarray(w2), [-0.5])  # applied mean grad


def test_compression_bf16_wire(hvd, mesh8):
    from horovod_tpu.ops.compression import Compression

    opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                   compression=Compression.bf16)
    w = jnp.asarray([0.0, 0.0])
    opt_state = opt.init(w)

    def per_shard(w, opt_state, g):
        updates, opt_state = opt.update(g[0], opt_state, w)
        return optax.apply_updates(w, updates), opt_state

    g = jnp.full((8, 2), 0.5)
    w2, _ = shard_map(per_shard, mesh=mesh8,
                      in_specs=(P(), P(), P("dp")), out_specs=(P(), P()))(
        w, opt_state, g)
    assert w2.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(w2), [-0.5, -0.5], rtol=1e-2)


def test_compressor_roundtrip():
    from horovod_tpu.ops.compression import Compression

    x = np.random.RandomState(0).randn(16).astype(np.float32)
    c, ctx = Compression.fp16.compress(x)
    assert c.dtype == np.float16
    out = Compression.fp16.decompress(c, ctx)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, x, atol=1e-2)
    i = np.arange(4)
    c, ctx = Compression.fp16.compress(i)
    assert ctx is None and c.dtype == i.dtype  # ints pass through


def test_broadcast_parameters(hvd):
    params = {"w": jnp.ones((4, 3)), "b": np.zeros(3, np.float32)}
    out = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones((4, 3)))
    np.testing.assert_allclose(np.asarray(out["b"]), np.zeros(3))


def test_broadcast_optimizer_state(hvd):
    opt = optax.adam(1e-3)
    params = {"w": jnp.ones((2, 2))}
    state = opt.init(params)
    out = hvd.broadcast_optimizer_state(state, root_rank=0)
    chex = jax.tree.leaves(out)
    assert len(chex) == len(jax.tree.leaves(state))


def test_broadcast_object(hvd):
    obj = {"epoch": 7, "name": "resnet", "lr": 0.1}
    out = hvd.broadcast_object(obj, root_rank=0)
    assert out == obj


def test_allgather_object(hvd):
    from horovod_tpu.functions import allgather_object

    out = allgather_object({"rank": hvd.rank()})
    assert out == [{"rank": 0}]


def test_average_metrics(hvd):
    from horovod_tpu.callbacks import average_metrics

    out = average_metrics({"loss": 2.0, "acc": 0.5})
    assert out == {"loss": 2.0, "acc": 0.5}


def test_warmup_schedule(hvd):
    from horovod_tpu.callbacks import warmup_schedule

    sched = warmup_schedule(0.1, warmup_steps=10, scale=8.0)
    np.testing.assert_allclose(float(sched(0)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(sched(10)), 0.8, rtol=1e-6)
    np.testing.assert_allclose(float(sched(100)), 0.8, rtol=1e-6)
    mid = float(sched(5))
    assert 0.1 < mid < 0.8


def test_sync_batch_norm_stats(mesh8):
    from horovod_tpu.sync_batch_norm import sync_batch_stats

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 5), jnp.float32)
    mean, var = shard_map(
        lambda t: sync_batch_stats(t, "dp"), mesh=mesh8,
        in_specs=(P("dp"),), out_specs=(P(), P()))(x)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x).mean(0),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(x).var(0),
                               atol=1e-5)


def test_sync_batch_norm_module(mesh8):
    import flax.linen as nn

    from horovod_tpu.sync_batch_norm import SyncBatchNorm

    bn = SyncBatchNorm(use_running_average=False, axis_name="dp")
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 6), jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x[:4])

    def per_shard(xs):
        y, _ = bn.apply(variables, xs, mutable=["batch_stats"])
        return y

    y = shard_map(per_shard, mesh=mesh8, in_specs=(P("dp"),),
                  out_specs=P("dp"))(x)
    # normalized with GLOBAL stats → global mean 0, var 1
    np.testing.assert_allclose(np.asarray(y).mean(0), np.zeros(6), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y).std(0), np.ones(6), atol=1e-2)


def test_best_model_checkpoint(hvd, tmp_path):
    from horovod_tpu.callbacks import BestModelCheckpoint

    ckpt = BestModelCheckpoint(str(tmp_path / "best.pkl"), monitor="loss")
    assert ckpt({"loss": 1.0}, {"w": jnp.ones(2)})
    assert not ckpt({"loss": 2.0}, {"w": jnp.zeros(2)})
    assert ckpt({"loss": 0.5}, {"w": jnp.full((2,), 3.0)})
    import pickle

    with open(tmp_path / "best.pkl", "rb") as f:
        best = pickle.load(f)
    np.testing.assert_allclose(best["w"], [3.0, 3.0])


def test_microbatch_gradients(hvd, mesh8):
    """k micro-batches, one collective: equals the full-batch mean grad."""
    from horovod_tpu.optimizer import microbatch_gradients

    w = jnp.asarray([1.0, -1.0])
    x = jnp.asarray(np.random.RandomState(3).randn(64, 2), jnp.float32)

    def loss(w, xs):
        return jnp.mean((xs @ w) ** 2)

    def grad_fn(w, xs):
        return jax.grad(loss)(w, xs)

    def per_shard(w, xs):
        return microbatch_gradients(grad_fn, w, xs, num_microbatches=4)

    g = shard_map(per_shard, mesh=mesh8, in_specs=(P(), P("dp")),
                  out_specs=P())(w, x)
    g_full = jax.grad(loss)(w, x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_full), rtol=1e-5)


def test_distributed_optimizer_adasum_jit_path(hvd):
    """End-to-end Adasum through DistributedOptimizer under shard_map:
    per-rank gradients stay varying (pvary_tree), the combine runs in
    jit, and outputs are replicated (VMA-invariant)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd_mod
    from horovod_tpu.optimizer import pvary_tree

    mesh = hvd_mod.mesh()
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1), op=hvd_mod.Adasum)
    params = {"w": jnp.ones(4)}
    opt_state = opt.init(params)

    def local_step(params, opt_state, x):
        def loss_fn(p):
            return jnp.sum(p["w"] * x)

        grads = jax.grad(loss_fn)(pvary_tree(params, "dp"))
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2

    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh, in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P())))
    # Identical per-rank grads x=1: adasum of identical vectors is the
    # vector itself (scale invariance) -> w goes 1.0 -> 1.0 - 0.1*1.
    x = jnp.ones(8)
    new_params, _ = step(params, opt_state, x)
    import numpy as np

    np.testing.assert_allclose(np.asarray(new_params["w"]),
                               np.full(4, 0.9), rtol=1e-6)
