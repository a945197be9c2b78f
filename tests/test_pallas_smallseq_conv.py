"""The head-batched small-sequence attention kernel with its routing
policy, and the fused 1x1-conv + BN kernels (ops/conv_fused.py); interpret
mode on the CPU.  Split from tests/test_pallas.py so that neither file is
a worker's whole share of the run under --dist loadfile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import attention_reference


class TestSmallseqKernel:
    """flash_attention_smallseq — the head-batched single-block kernel
    for the short-seq regime (ops/pallas_kernels.py)."""

    def _qkv(self, b=2, l=128, h=4, hkv=None, d=16, dtype=jnp.float32,
             seed=0):
        hkv = hkv or h
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, l, h, d), dtype)
        k = jnp.asarray(rng.randn(b, l, hkv, d), dtype)
        v = jnp.asarray(rng.randn(b, l, hkv, d), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv()
        out = flash_attention_smallseq(q, k, v, causal=causal,
                                       heads_per_block=2)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv(h=4, hkv=2)
        out = flash_attention_smallseq(q, k, v, causal=True,
                                       heads_per_block=4)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv(dtype=jnp.bfloat16)
        out = flash_attention_smallseq(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_heads_per_block_fits(self):
        from horovod_tpu.ops.pallas_kernels import _fit_heads_per_block

        assert _fit_heads_per_block(16, 1, 8) == 8
        assert _fit_heads_per_block(4, 1, 8) == 4
        assert _fit_heads_per_block(6, 1, 4) == 3   # 4,5 don't divide 6
        assert _fit_heads_per_block(8, 4, 8) == 8
        assert _fit_heads_per_block(8, 4, 6) == 4   # must be group multiple
        # A request below the GQA group clamps UP to one kv group per
        # program (regression: decremented to 0 -> ZeroDivisionError).
        assert _fit_heads_per_block(32, 16, 8) == 16
        assert _fit_heads_per_block(16, 8, 0) == 8  # nonsense knob value

    def test_wide_gqa_group_exceeds_requested_hb(self):
        # group=4 > heads_per_block=2: clamps up and stays correct.
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv(h=8, hkv=2, seed=5)
        out = flash_attention_smallseq(q, k, v, causal=True,
                                       heads_per_block=2)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_reference(self, causal):
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv(seed=3)
        w = jnp.cos(jnp.arange(16.0))

        def grads(fn):
            return jax.grad(
                lambda q, k, v: ((fn(q, k, v, causal=causal) * w) ** 2
                                 ).sum(), argnums=(0, 1, 2))(q, k, v)

        got = grads(lambda q, k, v, **kw: flash_attention_smallseq(
            q, k, v, heads_per_block=2, **kw))
        ref = grads(attention_reference)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    def test_gqa_grads_accumulate_groups(self):
        from horovod_tpu.ops.pallas_kernels import flash_attention_smallseq

        q, k, v = self._qkv(h=4, hkv=2, seed=4)

        def grads(fn):
            return jax.grad(
                lambda q, k, v: fn(q, k, v, causal=True).sum(),
                argnums=(0, 1, 2))(q, k, v)

        got = grads(lambda q, k, v, causal: flash_attention_smallseq(
            q, k, v, causal=causal, heads_per_block=4))
        ref = grads(attention_reference)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)


class TestSmallseqPolicy:
    """HVDT_FLASH_SMALLSEQ routing in models/transformer._flash_fn."""

    def _spy(self, monkeypatch):
        import horovod_tpu.ops.pallas_kernels as pk

        calls = []
        orig = pk.flash_attention_smallseq

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(pk, "flash_attention_smallseq", spy)
        return calls

    def test_env_on_routes_model_attention(self, monkeypatch):
        from horovod_tpu.models import (TransformerConfig, transformer_init,
                                        transformer_apply)

        calls = self._spy(monkeypatch)
        cfg = TransformerConfig(vocab=64, layers=2, d_model=32, heads=2,
                                kv_heads=2, d_ff=64, max_seq=128,
                                dtype=jnp.float32)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)

        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ", "off")
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "auto")
        ref = transformer_apply(params, tokens, cfg)
        assert not calls
        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ", "on")
        got = transformer_apply(params, tokens, cfg)
        assert calls   # the smallseq kernel actually ran
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_master_off_and_streaming_force_precedence(self, monkeypatch):
        from horovod_tpu.models.transformer import _flash_fn

        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ", "on")
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "off")
        assert _flash_fn(128, 32, batch=8, heads=8) is None
        # =on keeps its A/B meaning: force the STREAMING kernel.
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
        fn = _flash_fn(128, 32, batch=8, heads=8)
        assert fn is not None
        assert fn.func.__name__ == "flash_attention"
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "auto")
        fn = _flash_fn(128, 32, batch=8, heads=8)
        assert fn.func.__name__ == "flash_attention_smallseq"

    def test_on_forces_every_tiling_shape(self, monkeypatch):
        """'on' is the A/B force switch: it must pick the kernel for any
        tiling shape — including the lm_smallseq_hb16_bs128 leg's shape,
        which the auto path's 12 MiB VMEM MODEL would reject (a forced
        leg silently measuring the baseline corrupts the A/B)."""
        from horovod_tpu.models.transformer import _smallseq_enabled

        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ", "on")
        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ_HB", "16")
        assert _smallseq_enabled(512, 64, batch=128, heads=16)
        # non-tiling / long shapes still never route to the kernel
        assert not _smallseq_enabled(2048, 64, batch=128, heads=16)
        assert not _smallseq_enabled(130, 64, batch=128, heads=16)

    def test_auto_stays_disengaged_and_gates_on_platform(self, monkeypatch):
        import horovod_tpu.models.transformer as tr

        monkeypatch.setenv("HVDT_FLASH_SMALLSEQ", "auto")
        assert not tr._smallseq_enabled(512, 64, batch=128, heads=16)
        # even with a threshold set, the CPU platform must not engage
        monkeypatch.setattr(tr, "_SMALLSEQ_AUTO_MIN_PROGRAMS", 16)
        assert not tr._smallseq_enabled(512, 64, batch=128, heads=16)
        # the VMEM model only constrains auto
        monkeypatch.setattr(tr, "_SMALLSEQ_AUTO_MIN_PROGRAMS", None)
        assert not tr._smallseq_vmem_ok(512, 64, hb=16)
        assert tr._smallseq_vmem_ok(512, 64, hb=4)


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
