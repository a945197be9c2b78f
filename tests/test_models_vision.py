"""Model zoo tests, the image models: ResNet (26/50/101 layouts, SyncBN
over dp, the fused 1x1-conv + BN route against the XLA one) and VGG-16.
Split from tests/test_models.py so that neither file is a worker's whole
share of the run under --dist loadfile."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map
from horovod_tpu.models import (ResNetConfig, resnet50_init, resnet_apply,
                                resnet_loss)
from horovod_tpu.parallel import make_mesh


class TestResNet:
    def test_forward_and_stats_update(self):
        # depth=26 is one bottleneck per stage: every stage boundary and
        # stride of the 50-layer layout, a quarter of its compile.
        cfg = ResNetConfig(num_classes=10, dtype=jnp.float32, depth=26)
        params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        apply = jax.jit(resnet_apply, static_argnums=(3, 4))
        logits, new_stats = apply(params, stats, x, cfg, True)
        assert logits.shape == (2, 10)
        assert bool(jnp.isfinite(logits).all())
        # Running stats must move.
        assert not np.allclose(
            np.asarray(new_stats["bn_stem"]["mean"]),
            np.asarray(stats["bn_stem"]["mean"]))
        # Eval mode: stats unchanged.
        _, same = apply(params, stats, x, cfg, False)
        np.testing.assert_array_equal(np.asarray(same["bn_stem"]["mean"]),
                                      np.asarray(stats["bn_stem"]["mean"]))

    def test_train_step_decreases_loss(self):
        cfg = ResNetConfig(num_classes=4, dtype=jnp.float32, depth=26)
        params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
        y = jnp.array([0, 1, 2, 3])
        opt = optax.sgd(0.005, momentum=0.9)
        st = opt.init(params)

        @jax.jit
        def step(p, bs, st):
            (l, new_bs), g = jax.value_and_grad(
                resnet_loss, has_aux=True)(p, bs, x, y, cfg)
            u, st = opt.update(g, st, p)
            return optax.apply_updates(p, u), new_bs, st, l

        l0 = None
        for _ in range(6):
            params, stats, st, l = step(params, stats, st)
            if l0 is None:
                l0 = float(l)
        assert float(l) < l0

    def test_sync_bn_across_dp(self):
        # depth=26 (one block/stage): same BN-sync plumbing as ResNet-50
        # at ~4x less CPU compile time (this was the suite's slowest
        # test at 110 s).
        cfg = ResNetConfig(num_classes=4, dtype=jnp.float32, bn_axis="dp",
                           depth=26)
        params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3))
        mesh = make_mesh(dp=2, devices=jax.devices()[:2])
        _, new_stats = jit_shard_map(
            lambda p, s, xx: resnet_apply(p, s, xx, cfg, True),
            mesh=mesh, in_specs=(P(), P(), P("dp")),
            out_specs=(P("dp"), P()))(params, stats, x)
        # Synced stats equal global-batch stats (unsharded run).
        cfg0 = ResNetConfig(num_classes=4, dtype=jnp.float32, depth=26)
        _, want = jax.jit(resnet_apply, static_argnums=(3, 4))(
            params, stats, x, cfg0, True)
        np.testing.assert_allclose(
            np.asarray(new_stats["bn_stem"]["mean"]),
            np.asarray(want["bn_stem"]["mean"]), rtol=1e-4, atol=1e-5)


class TestFusedConv1x1:
    """HVDT_FUSED_CONV1X1: the fused Pallas conv+BN route must be a
    pure lowering change — forward, grads, and running-stat updates
    matching the XLA path (models/resnet.py _conv_bn) to numerical
    tolerance.  One documented gradient-convention exception: the
    fused kernel takes relu'(0)=0 where jnp.maximum's autodiff splits
    the tie at 0.5 — exactly-zero pre-activations (measure zero under
    the random inputs here) would differ."""

    def _bottleneck_setup(self):
        from horovod_tpu.models import resnet as rn

        cfg = rn.ResNetConfig(num_classes=10, dtype=jnp.float32)
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        p = {"conv1": rn._conv_init(ks[0], 1, 1, 128, 128, cfg.dtype),
             "conv2": rn._conv_init(ks[1], 3, 3, 128, 128, cfg.dtype),
             "conv3": rn._conv_init(ks[2], 1, 1, 128, 512, cfg.dtype),
             "conv_proj": rn._conv_init(ks[3], 1, 1, 128, 512, cfg.dtype),
             "bn1": rn._bn_init(128, cfg.dtype),
             "bn2": rn._bn_init(128, cfg.dtype),
             "bn3": rn._bn_init(512, cfg.dtype),
             "bn_proj": rn._bn_init(512, cfg.dtype)}
        s = {"bn1": rn._bn_stats(128), "bn2": rn._bn_stats(128),
             "bn3": rn._bn_stats(512), "bn_proj": rn._bn_stats(512)}
        x = jax.random.normal(ks[4], (2, 8, 8, 128), cfg.dtype)
        return rn, cfg, p, s, x

    @pytest.mark.parametrize("train", [True, False])
    def test_bottleneck_fused_matches_xla(self, monkeypatch, train):
        rn, cfg, p, s, x = self._bottleneck_setup()

        def run():     # a fresh jit each: the route is read while tracing
            return jax.jit(lambda x, p, s: rn._bottleneck(
                x, p, s, cfg, train, stride=1))(x, p, s)

        monkeypatch.delenv("HVDT_FUSED_CONV1X1", raising=False)
        y_ref, s_ref = run()
        monkeypatch.setenv("HVDT_FUSED_CONV1X1", "1")
        y_fused, s_fused = run()
        np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        for k in s_ref:
            for stat in ("mean", "var"):
                np.testing.assert_allclose(
                    np.asarray(s_fused[k][stat]),
                    np.asarray(s_ref[k][stat]), rtol=1e-4, atol=1e-5)

    def test_bottleneck_fused_grads_match(self, monkeypatch):
        rn, cfg, p, s, x = self._bottleneck_setup()

        def loss(p):
            y, _ = rn._bottleneck(x, p, s, cfg, True, stride=1)
            return jnp.mean(y.astype(jnp.float32) ** 2)

        # a fresh jit each: the route is read from the environment while
        # tracing
        monkeypatch.delenv("HVDT_FUSED_CONV1X1", raising=False)
        g_ref = jax.jit(jax.grad(loss))(p)
        monkeypatch.setenv("HVDT_FUSED_CONV1X1", "1")
        g_fused = jax.jit(jax.grad(loss))(p)
        ref_flat = {jax.tree_util.keystr(k): v for k, v in
                    jax.tree_util.tree_leaves_with_path(g_ref)}
        fused_flat = {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_leaves_with_path(g_fused)}
        assert set(ref_flat) == set(fused_flat)
        for k, va in ref_flat.items():
            np.testing.assert_allclose(np.asarray(fused_flat[k]),
                                       np.asarray(va),
                                       rtol=2e-3, atol=1e-4, err_msg=k)

    def test_eligibility_gate(self, monkeypatch):
        from horovod_tpu.models import resnet as rn

        monkeypatch.setenv("HVDT_FUSED_CONV1X1", "1")
        cfg_ok = rn.ResNetConfig(num_classes=4, dtype=jnp.float32)
        w = jnp.zeros((1, 1, 128, 128))
        assert rn._fused_1x1_eligible(w, 1, cfg_ok)
        # SyncBN is eligible too (psum'd stat partials)
        assert rn._fused_1x1_eligible(
            w, 1, rn.ResNetConfig(num_classes=4, dtype=jnp.float32,
                                  bn_axis="dp"))
        assert not rn._fused_1x1_eligible(w, 2, cfg_ok)
        assert not rn._fused_1x1_eligible(
            jnp.zeros((3, 3, 128, 128)), 1, cfg_ok)
        assert not rn._fused_1x1_eligible(
            jnp.zeros((1, 1, 128, 64)), 1, cfg_ok)
        # stage-0 shapes (Cin=64) are outside the probe-validated set
        assert not rn._fused_1x1_eligible(
            jnp.zeros((1, 1, 64, 256)), 1, cfg_ok)
        # M = B*H*W tiling gate (ADVICE r5): batch 1 at 14x14 → M=196,
        # largest power-of-2 divisor 4 < the f32 sublane floor (8) —
        # must fall back to the XLA path instead of crashing at trace.
        assert not rn._fused_1x1_eligible(
            w, 1, cfg_ok, jnp.zeros((1, 14, 14, 128), jnp.float32))
        # bf16 floor is 16 rows: M=8·8·2=... use B2 H8 W8 → M=128, ok.
        assert rn._fused_1x1_eligible(
            w, 1, cfg_ok, jnp.zeros((2, 8, 8, 128), jnp.bfloat16))
        # ...but M=8 (B2 H2 W2) tiles only to 8 < 16 for bf16.
        assert not rn._fused_1x1_eligible(
            w, 1, cfg_ok, jnp.zeros((2, 2, 2, 128), jnp.bfloat16))
        monkeypatch.delenv("HVDT_FUSED_CONV1X1")
        assert not rn._fused_1x1_eligible(w, 1, cfg_ok)

    def test_odd_spatial_falls_back_not_crashes(self, monkeypatch):
        """Batch 1 at 14x14 (M=196) with the flag ON must route through
        the XLA conv path (ADVICE r5) — not raise at trace time."""
        from horovod_tpu.models import resnet as rn

        cfg = rn.ResNetConfig(num_classes=4, dtype=jnp.float32)
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        w = rn._conv_init(k1, 1, 1, 128, 128, cfg.dtype)
        p, s = rn._bn_init(128, cfg.dtype), rn._bn_stats(128)
        x = jax.random.normal(k2, (1, 14, 14, 128), cfg.dtype)

        monkeypatch.delenv("HVDT_FUSED_CONV1X1", raising=False)
        y_ref, s_ref = rn._conv_bn(x, w, p, s, cfg, True, relu=True)
        monkeypatch.setenv("HVDT_FUSED_CONV1X1", "1")
        y, s_new = rn._conv_bn(x, w, p, s, cfg, True, relu=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_new["mean"]),
                                   np.asarray(s_ref["mean"]),
                                   rtol=1e-5, atol=1e-6)

    def test_sync_bn_fused_matches_unfused(self, monkeypatch):
        """SyncBN under dp2 shard_map: the fused kernel's psum'd stat
        partials must reproduce the unfused synced path — forward,
        running stats, and parameter grads."""
        from functools import partial

        from horovod_tpu.models import resnet as rn
        from horovod_tpu.parallel import make_mesh

        rn_, cfg, p, s, _ = self._bottleneck_setup()
        cfg = rn.ResNetConfig(num_classes=10, dtype=jnp.float32,
                              bn_axis="dp")
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 8, 8, 128),
                              cfg.dtype)
        mesh = make_mesh(dp=2, devices=jax.devices()[:2])

        def sharded_loss_and_stats(p):
            def local(p, xx):
                y, out_s = rn._bottleneck(xx, p, s, cfg, True, 1)
                from jax import lax

                return (lax.pmean(jnp.mean(y.astype(jnp.float32) ** 2),
                                  "dp"), out_s)

            loss, out_s = jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P("dp")),
                out_specs=(P(), P()))(p, x)
            return loss, out_s

        def run(p):
            # a fresh jit each time: the route is read from the
            # environment while tracing
            (l, out_s), g = jax.jit(jax.value_and_grad(
                sharded_loss_and_stats, has_aux=True))(p)
            return l, out_s, g

        monkeypatch.delenv("HVDT_FUSED_CONV1X1", raising=False)
        l_ref, s_ref, g_ref = run(p)
        monkeypatch.setenv("HVDT_FUSED_CONV1X1", "1")
        l_fused, s_fused, g_fused = run(p)
        np.testing.assert_allclose(float(l_fused), float(l_ref),
                                   rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(s_fused["bn1"]["mean"]),
            np.asarray(s_ref["bn1"]["mean"]), rtol=1e-5, atol=1e-6)
        ref_flat = {jax.tree_util.keystr(k): v for k, v in
                    jax.tree_util.tree_leaves_with_path(g_ref)}
        fused_flat = {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_leaves_with_path(g_fused)}
        for k, va in ref_flat.items():
            np.testing.assert_allclose(np.asarray(fused_flat[k]),
                                       np.asarray(va),
                                       rtol=2e-3, atol=1e-5, err_msg=k)


class TestResNet101AndVGG:
    """The reference's published benchmark trio (docs/benchmarks.rst:8-43)
    is ResNet-101 / VGG-16 / Inception — depth-101 layouts and VGG-16
    here complete the zoo's benchmark parity (ResNet-101 is the model
    behind the reference's headline images/s figure there)."""

    def test_resnet101_forward_and_param_count(self):
        from horovod_tpu.models import (ResNetConfig, resnet101_init,
                                        resnet_apply)

        cfg = ResNetConfig(num_classes=10, dtype=jnp.float32, depth=101)
        # Shapes only (jax.eval_shape): the 101-layer layout is the count
        # of its parameters and a forward that walks all 33 blocks; the
        # values are checked at depth 26, which runs the same code.
        params, stats = jax.eval_shape(
            lambda: resnet101_init(jax.random.PRNGKey(0), cfg))
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        # torchvision resnet101: 44.55M params at 1000 classes; ours at
        # 10 classes drops most of the fc: ~42.5M.
        assert 40e6 < n < 46e6
        x = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32)
        logits, new_stats = jax.eval_shape(
            lambda p, s, xx: resnet_apply(p, s, xx, cfg, train=True),
            params, stats, x)
        assert logits.shape == (2, 10)
        assert jax.tree.structure(new_stats) == jax.tree.structure(stats)

    def test_vgg16_forward_loss_and_grads(self):
        from horovod_tpu.models import (VGGConfig, vgg16_init, vgg_apply,
                                        vgg_loss)

        cfg = VGGConfig(num_classes=10, dtype=jnp.float32, image_size=32)
        params = vgg16_init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        # 13 convs (~14.7M) + FCs for 32px input (1*1*512 -> 4096 ...).
        assert 30e6 < n < 45e6
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3)) * 0.1
        y = jnp.array([1, 2])
        # one program for the forward and the gradient
        logits, (loss, grads) = jax.jit(lambda p: (
            vgg_apply(p, x, cfg),
            jax.value_and_grad(vgg_loss)(p, x, y, cfg)))(params)
        assert logits.shape == (2, 10)
        assert bool(jnp.isfinite(loss))
        assert all(bool(jnp.isfinite(g).all())
                   for g in jax.tree.leaves(grads))
