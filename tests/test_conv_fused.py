"""The fused 1x1-conv + BN kernels (ops/conv_fused.py); interpret mode on
the CPU.  Its own file so that tests/test_pallas.py is not a worker's whole
share of the run under --dist loadfile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


class TestConvFused:
    """ops/conv_fused.py — the below-XLA ResNet probe kernel (fused
    1x1-conv matmul + BN affine epilogue), interpret mode vs the f32
    oracle."""

    @pytest.mark.parametrize("cin,cout,relu", [(256, 128, True),
                                               (128, 512, False)])
    def test_matches_reference(self, cin, cout, relu):
        from horovod_tpu.ops.conv_fused import (conv1x1_bn_relu,
                                                conv1x1_bn_relu_reference)

        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (2, 7, 8, cin), jnp.bfloat16)
        w = jax.random.normal(ks[1], (cin, cout),
                              jnp.bfloat16) * (cin ** -0.5)
        s = jax.random.uniform(ks[2], (cout,), jnp.float32, 0.5, 1.5)
        b = jax.random.normal(ks[3], (cout,), jnp.float32)
        got = conv1x1_bn_relu(x, w, s, b, relu=relu)
        ref = conv1x1_bn_relu_reference(x, w, s, b, relu=relu)
        assert got.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-2, atol=1e-2)

    def test_multi_k_block_accumulation(self):
        """K larger than block_k exercises the zero/accumulate/epilogue
        grid carry."""
        from horovod_tpu.ops.conv_fused import matmul_bn_relu

        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        a = jax.random.normal(ks[0], (64, 1024), jnp.float32)
        w = jax.random.normal(ks[1], (1024, 128), jnp.float32) * 0.03
        s = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        got = matmul_bn_relu(a, w, s, b, relu=False, block_k=256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(a @ w),
                                   rtol=1e-5, atol=1e-5)

    def test_train_form_stats_and_output(self):
        """matmul_batch_stats + conv1x1_bn_train: z, batch mean/var and
        the normalized output all match the f32 oracle (the train-mode
        BN lever — z written once, read once)."""
        from horovod_tpu.ops.conv_fused import (conv1x1_bn_train,
                                                conv1x1_bn_train_reference)

        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        x = jax.random.normal(ks[0], (2, 7, 8, 256), jnp.bfloat16)
        w = jax.random.normal(ks[1], (256, 128), jnp.bfloat16) * 0.06
        g = jax.random.uniform(ks[2], (128,), jnp.float32, 0.5, 1.5)
        b = jax.random.normal(ks[3], (128,), jnp.float32)
        got = conv1x1_bn_train(x, w, g, b)
        ref = conv1x1_bn_train_reference(x, w, g, b)
        for a_, r_ in zip(got, ref):
            af = np.asarray(a_, np.float32)
            rf = np.asarray(r_, np.float32)
            rel = np.abs(af - rf).max() / max(np.abs(rf).max(), 1e-9)
            assert rel < 2e-2, rel

    @pytest.mark.parametrize("relu", [True, False])
    def test_train_form_gradients_match_reference(self, relu):
        """Batch-stat BN custom_vjp vs autodiff through the oracle —
        the loss also consumes mean/var so their cotangent paths are
        exercised (running-stat consumers differentiate through them
        only if they choose to)."""
        from horovod_tpu.ops.conv_fused import conv1x1_bn_train

        ks = jax.random.split(jax.random.PRNGKey(11), 4)
        x = jax.random.normal(ks[0], (2, 4, 4, 128), jnp.float32)
        w = jax.random.normal(ks[1], (128, 128), jnp.float32) * 0.1
        gm = jax.random.uniform(ks[2], (128,), jnp.float32, 0.5, 1.5)
        bt = jax.random.normal(ks[3], (128,), jnp.float32)
        eps = 1e-5

        def loss_kernel(x, w, gm, bt):
            y, mean, var = conv1x1_bn_train(x, w, gm, bt, eps=eps,
                                            relu=relu)
            return (jnp.sum(y ** 2) + jnp.sum(mean * 0.3)
                    + jnp.sum(var * 0.7))

        def loss_ref(x, w, gm, bt):
            z = jnp.einsum("bhwc,cd->bhwd", x, w)
            mean = z.mean(axis=(0, 1, 2))
            var = z.var(axis=(0, 1, 2))
            y = (z - mean) * jax.lax.rsqrt(var + eps) * gm + bt
            if relu:
                y = jnp.maximum(y, 0.0)
            return (jnp.sum(y ** 2) + jnp.sum(mean * 0.3)
                    + jnp.sum(var * 0.7))

        got = jax.grad(loss_kernel, argnums=(0, 1, 2, 3))(x, w, gm, bt)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, gm, bt)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-4, atol=5e-4)

    def test_train_form_rejects_wrong_param_shapes(self):
        from horovod_tpu.ops.conv_fused import conv1x1_bn_train

        x = jnp.zeros((1, 4, 8, 128), jnp.float32)
        w = jnp.zeros((128, 128), jnp.float32)
        with pytest.raises(ValueError, match="gamma/beta"):
            conv1x1_bn_train(x, w, jnp.ones((1,)), jnp.zeros(128))

    def test_train_form_multi_m_block_partials(self):
        """M larger than block_m exercises the per-M-block partial-sum
        outputs (one [1, N] row per M block, finalized outside)."""
        from horovod_tpu.ops.conv_fused import matmul_batch_stats

        ks = jax.random.split(jax.random.PRNGKey(8), 2)
        a = jax.random.normal(ks[0], (256, 128), jnp.float32)
        w = jax.random.normal(ks[1], (128, 128), jnp.float32) * 0.1
        z, s1, s2 = matmul_batch_stats(a, w, block_m=64)
        assert s1.shape == (4, 128)
        zf = np.asarray(a @ w)
        np.testing.assert_allclose(np.asarray(z), zf, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1).sum(0), zf.sum(0),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s2).sum(0),
                                   (zf * zf).sum(0), rtol=1e-5,
                                   atol=1e-3)

    def test_bad_shapes_fail_loudly(self):
        from horovod_tpu.ops.conv_fused import matmul_bn_relu

        a = jnp.zeros((8, 64), jnp.float32)
        w = jnp.zeros((64, 64), jnp.float32)
        with pytest.raises(ValueError, match="tile floor"):
            matmul_bn_relu(a, w, jnp.ones(64), jnp.zeros(64))
        with pytest.raises(ValueError, match="scale/bias"):
            matmul_bn_relu(jnp.zeros((8, 64)), jnp.zeros((64, 128)),
                           jnp.ones(64), jnp.zeros(128))

    @pytest.mark.parametrize("relu", [True, False])
    def test_gradients_match_reference(self, relu):
        """custom_vjp: a/w/scale/bias grads vs autodiff through the jnp
        oracle (the backward RECOMPUTES z = a @ w — see
        test_zero_init_gamma_still_trains for why recovery from the
        saved output is not an option)."""
        from horovod_tpu.ops.conv_fused import matmul_bn_relu

        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        a = jax.random.normal(ks[0], (32, 128), jnp.float32)
        w = jax.random.normal(ks[1], (128, 128), jnp.float32) * 0.1
        s = jax.random.uniform(ks[2], (128,), jnp.float32, 0.5, 1.5)
        b = jax.random.normal(ks[3], (128,), jnp.float32)

        def loss_kernel(a, w, s, b):
            return jnp.sum(matmul_bn_relu(a, w, s, b, relu=relu) ** 2)

        def loss_ref(a, w, s, b):
            y = jnp.dot(a, w) * s + b
            if relu:
                y = jnp.maximum(y, 0.0)
            return jnp.sum(y ** 2)

        got = jax.grad(loss_kernel, argnums=(0, 1, 2, 3))(a, w, s, b)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(a, w, s, b)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-4, atol=2e-4)

    def test_zero_init_gamma_still_trains(self):
        """scale == 0 (zero-init gamma) must produce the exact dscale —
        the backward recomputes z rather than recovering it from the
        zeroed output.  Exercised in its REAL placement: a residual
        block's last BN runs the kernel with relu=False (the add
        precedes the relu), so the relu'(0)=0 convention never zeroes
        the gradient path."""
        from horovod_tpu.ops.conv_fused import matmul_bn_relu

        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        a = jax.random.normal(ks[0], (16, 128), jnp.float32)
        w = jax.random.normal(ks[1], (128, 128), jnp.float32) * 0.1
        shortcut = jax.random.normal(ks[2], (16, 128), jnp.float32)
        s = jnp.zeros((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)

        def loss_k(s):
            block = matmul_bn_relu(a, w, s, b, relu=False)
            return jnp.sum(jnp.maximum(block + shortcut, 0.0) ** 2)

        def loss_r(s):
            block = jnp.dot(a, w) * s + b
            return jnp.sum(jnp.maximum(block + shortcut, 0.0) ** 2)

        got = jax.grad(loss_k)(s)
        ref = jax.grad(loss_r)(s)
        assert float(jnp.abs(got).max()) > 0          # gamma can train
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
