"""Pallas flash-attention kernel tests (interpret mode on CPU — the same
kernel code that compiles for TPU; analog of the reference's CUDA-kernel
correctness tests in test/parallel/test_torch.py fusion cases)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                            flash_attention,
                                            flash_block_update)


def _rand_qkv(key, b=2, l=128, h=4, hkv=None, d=32, dtype=jnp.float32):
    hkv = hkv or h
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(kq, (b, l, h, d), dtype)
    k = jax.random.normal(kk, (b, l, hkv, d), dtype)
    v = jax.random.normal(kv, (b, l, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(0)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa():
    q, k, v = _rand_qkv(1, h=8, hkv=2)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = _rand_qkv(2, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


# (seq, block_q, block_k, head_dim, rows per chunk or None for the call's
# own choice, which is the whole tile at these sizes).  Causal seq 256 at
# 64 x 128 and at 128 x 64 meets fully visible, straddling and skipped
# tiles in one run (and the K/V index clamp on the skipped ones); 192 only
# tiles at 64, so _fit_block has to shrink the 128s.  head_dim 16 has a
# power-of-two scale (folded into q in bf16 too), head_dim 32 has not.  The
# last three work a tile through in chunks of rows: square tiles, where a
# chunk on the diagonal stops at its own last key; 64 x 128, where the
# diagonal crosses a tile at an offset; and the whole sequence as one tile,
# the form the cell's shape takes.
LOCAL_FORWARD_SHAPES = [(256, 64, 128, 32, None), (256, 128, 64, 16, None),
                        (192, 128, 128, 32, None), (256, 128, 128, 32, 32),
                        (256, 64, 128, 16, 16), (256, 256, 256, 32, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize(
    "seq, block_q, block_k, d, rows", LOCAL_FORWARD_SHAPES,
    ids=["256_64x128", "256_128x64", "192_fit", "256_128x128_rows32",
         "256_64x128_rows16", "256_one_tile_rows64"])
def test_local_forward_out_and_lse_match_reference(seq, block_q, block_k, d,
                                                   rows, causal, group,
                                                   dtype):
    """The self-contained call (_flash_fwd_core -> _flash_local_call):
    ``out`` in the input dtype and the f32 logsumexp, both against the
    plain reference."""
    from horovod_tpu.ops.pallas_kernels import (_fit_block, _flash_fwd_core,
                                                _flash_local_call)

    q, k, v = _rand_qkv(7, l=seq, h=4, hkv=4 // group, d=d, dtype=dtype)
    bq = _fit_block(seq, block_q, dtype)
    bk = _fit_block(seq, block_k, dtype)
    if rows is None:
        assert seq // bq > 1 and seq // bk > 1      # several tiles each way
        out, lse = _flash_fwd_core(q, k, v, causal, d ** -0.5, bq, bk)
    else:
        out, lse = _flash_local_call(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=causal,
            scale=d ** -0.5, block_q=bq, block_k=bk, rows=rows)
        out, lse = out.transpose(0, 2, 1, 3), lse[:, :, 0, :]
    assert out.shape == q.shape and out.dtype == q.dtype
    assert lse.shape == (2, 4, seq) and lse.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    ref_out, ref_lse = attention_reference(q, k, v, causal=causal,
                                           with_lse=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5 if dtype == jnp.float32
                               else 2e-3)


def test_flash_clamps_ragged_blocks():
    # L=100 does not divide the requested 64 — the block clamp halves
    # down to a divisor (4 here) and the kernel stays correct.
    q, k, v = _rand_qkv(3, l=100)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_block_update_streams_to_full_attention():
    """Composing flash_block_update over K/V blocks (the ring schedule,
    executed sequentially here) must equal full attention."""
    b, l, h, d = 2, 128, 4, 32
    shards = 4
    lk = l // shards
    q, k, v = _rand_qkv(4, b=b, l=l, h=h, d=d)
    acc = jnp.zeros((b, l, h, d), jnp.float32)
    row_max = jnp.full((b, h, l), -1e30, jnp.float32)
    row_sum = jnp.zeros((b, h, l), jnp.float32)
    for s in range(shards):
        k_blk = k[:, s * lk:(s + 1) * lk]
        v_blk = v[:, s * lk:(s + 1) * lk]
        acc, row_max, row_sum = flash_block_update(
            q, k_blk, v_blk, acc, row_max, row_sum,
            q_offset=0, k_offset=s * lk, causal=True, scale=d ** -0.5,
            block_q=32, block_k=32)
    out = (acc / jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
           ).astype(q.dtype)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_block_update_fully_masked_block_is_identity():
    """A K/V block entirely in the causal future must not change the
    carry (the ring visits such blocks; exp(-inf) rows must not NaN)."""
    b, l, h, d = 1, 32, 2, 16
    q, k, v = _rand_qkv(5, b=b, l=l, h=h, d=d)
    acc = jnp.ones((b, l, h, d), jnp.float32)
    row_max = jnp.full((b, h, l), 3.0, jnp.float32)
    row_sum = jnp.full((b, h, l), 2.0, jnp.float32)
    acc2, m2, l2 = flash_block_update(
        q, k, v, acc, row_max, row_sum,
        q_offset=0, k_offset=10_000, causal=True, scale=d ** -0.5,
        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(acc2), np.asarray(acc), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(row_max))
    np.testing.assert_allclose(np.asarray(l2), np.asarray(row_sum))
    assert not np.isnan(np.asarray(acc2)).any()


def test_transformer_uses_flash_when_on(monkeypatch):
    """HVDT_FLASH_ATTENTION=on routes model attention through the Pallas
    kernel; logits must match the jnp path."""
    from horovod_tpu.models import (TransformerConfig, transformer_init,
                                    transformer_apply)

    cfg = TransformerConfig(vocab=64, layers=2, d_model=32, heads=2,
                            kv_heads=2, d_ff=64, max_seq=32,
                            dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)

    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "off")
    ref = transformer_apply(params, tokens, cfg)
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    got = transformer_apply(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fit_block_divisibility():
    from horovod_tpu.ops.pallas_kernels import _fit_block

    import jax.numpy as jnp

    f32 = jnp.float32
    assert _fit_block(768, 512, f32) == 256   # 512 does not divide 768
    assert _fit_block(768, 1024, f32) == 768  # min() clamp divides exactly
    assert _fit_block(2048, 512, f32) == 512
    assert _fit_block(64, 512, f32) == 64
    fitted = _fit_block(100, 512, f32)
    assert fitted >= 1 and 100 % fitted == 0


def test_flash_non_power_of_two_seq():
    # L=768 is a multiple of 128 but not of the tuned 512/1024 defaults;
    # the block clamp must make it work (regression: models gate on
    # seq % 128 == 0).
    import jax

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 768, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 768, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 768, 2, 64))
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


class TestFlashGradients:
    """The flash kernel's custom_vjp (pallas_call has no AD rule of its
    own — without this, any training path that engaged the kernel died
    with NotImplementedError)."""

    def _qkv(self, h=2, hkv=2, lq=128, d=16, dtype=jnp.float32, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(2, lq, h, d), dtype)
        k = jnp.asarray(rng.randn(2, lq, hkv, d), dtype)
        v = jnp.asarray(rng.randn(2, lq, hkv, d), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_reference(self, causal):
        from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                                    flash_attention)

        q, k, v = self._qkv()
        w = jnp.cos(jnp.arange(16.0))

        def loss(fn):
            return jax.grad(
                lambda q, k, v: (fn(q, k, v, causal=causal) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)

        for a, b in zip(loss(flash_attention), loss(attention_reference)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_multiblock_backward_matches_reference(self, causal):
        """lq=512 with 128-blocks: nblk=ntq=4 — exercises the blockwise
        scan, the causal-pruning cond, cross-block dq accumulation, and
        dk/dv block reassembly (a single-block run covers none of
        them)."""
        from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                                    flash_attention)

        q, k, v = self._qkv(lq=512, seed=6)

        def grads(fn, **kw):
            return jax.grad(
                lambda q, k, v: (fn(q, k, v, causal=causal, **kw) ** 2
                                 ).sum(), argnums=(0, 1, 2))(q, k, v)

        got = grads(flash_attention, block_q=128, block_k=128)
        ref = grads(attention_reference)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_gqa_grads_match_reference(self):
        from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                                    flash_attention)

        q, k, v = self._qkv(h=4, hkv=2, lq=256)

        def grads(fn):
            return jax.grad(lambda q, k, v: fn(q, k, v, causal=True).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        for a, b in zip(grads(flash_attention), grads(attention_reference)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    def test_transformer_trains_with_flash_on(self, monkeypatch):
        """End to end: grad of the LM loss with the kernel FORCED on
        (regression: the token shift made attention seq-1, silently
        disabling flash; and without the vjp this raised)."""
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
        from horovod_tpu.models import (TransformerConfig, transformer_init,
                                        transformer_loss)
        import horovod_tpu.models.transformer as tr

        gate_args = []
        orig = tr._flash_enabled

        def spy(l, dh, **kw):
            gate_args.append(l)
            return orig(l, dh, **kw)

        monkeypatch.setattr(tr, "_flash_enabled", spy)
        cfg = TransformerConfig(vocab=128, layers=1, d_model=32, heads=2,
                                kv_heads=2, d_ff=64, max_seq=128,
                                dtype=jnp.float32)
        p = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
        loss, g = jax.value_and_grad(transformer_loss)(p, toks, cfg)
        assert np.isfinite(float(loss))
        # attention ran on the FULL power-of-two seq -> gate engaged
        # (evaluated once by _flash_plan and once picking the kernel in
        # _flash_fn — the count is an implementation detail, the seq the
        # gate saw is the regression being pinned)
        assert gate_args and set(gate_args) == {128}, gate_args
        leaves = jax.tree.leaves(g)
        assert all(np.all(np.isfinite(np.asarray(x))) for x in leaves)

    def test_ring_default_is_differentiable(self):
        """The default ring path must survive jax.grad (behavioral: a
        pallas default would raise NotImplementedError here)."""
        from jax.sharding import Mesh, PartitionSpec as P

        from horovod_tpu.parallel import ring_attention

        devs = np.array(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devs, ("sp",))
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)

        def loss(q, k, v):
            def local(q, k, v):
                return ring_attention(q, k, v, axis="sp", causal=True)
            out = jax.shard_map(local, mesh=mesh,
                                in_specs=(P(None, "sp"), P(None, "sp"),
                                          P(None, "sp")),
                                out_specs=P(None, "sp"))(q, k, v)
            return (out * out).sum()

        g = jax.grad(loss)(q, k, v)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_ring_explicit_pallas_optin_warns_when_ignored(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from horovod_tpu.parallel import ring_attention

        devs = np.array(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devs, ("sp",))
        # 192/rank: >128 and not 128-divisible -> kernel can't tile
        q = jnp.ones((1, 384, 2, 16), jnp.float32)

        def local(q):
            return ring_attention(q, q, q, axis="sp", causal=True,
                                  use_pallas=True)

        with pytest.warns(UserWarning, match="use_pallas=True. ignored"):
            jax.shard_map(local, mesh=mesh, in_specs=P(None, "sp"),
                          out_specs=P(None, "sp"))(q)


class TestFlashMeshGate:
    def test_auto_mesh_axes_route_to_island(self, monkeypatch):
        """Mosaic kernels can't be GSPMD-auto-partitioned: under a
        partially-manual context (auto dp axis present) the plan must
        route through a shard_map island — never "direct" — and from a
        fully-manual context the kernel may run directly."""
        from jax.sharding import Mesh, PartitionSpec as P

        import horovod_tpu.models.transformer as tr

        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
        assert tr._flash_plan(2, 128, 4, 4, 32) == "direct"   # no mesh

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "sp"))
        seen = {}

        def probe(x):
            seen["plan"] = tr._flash_plan(2, 128, 4, 4, 32)
            return x

        jax.jit(jax.shard_map(probe, mesh=mesh, in_specs=P(),
                              out_specs=P(), axis_names={"sp"}))(
            jnp.ones(4))
        # Nested partial-manual (sp already manual, dp auto): the island
        # would fail shardy lowering on the backward — must refuse.
        assert seen["plan"] is None

        with jax.set_mesh(jax.make_mesh(
                (1, 1), ("dp", "tp"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)):
            plan = tr._flash_plan(2, 128, 4, 4, 32)
        # Pure-auto mesh: island engages (size-1 axes absorbed).
        assert plan not in (None, "direct")
        dp_axes, tp_ax, names = plan
        assert names == frozenset({"dp", "tp"})

        def probe2(x):
            seen["manual"] = tr._flash_plan(2, 128, 4, 4, 32)
            return x

        jax.jit(jax.shard_map(probe2, mesh=mesh, in_specs=P(),
                              out_specs=P()))(jnp.ones(4))
        assert seen["manual"] == "direct"          # fully manual: direct


class TestFlashBwdKernelKnob:
    def test_kernel_backward_matches_xla_backward(self, monkeypatch):
        """HVDT_FLASH_BWD=kernel swaps the blockwise-XLA backward for the
        Pallas grad kernels; grads must agree with the default path."""
        from horovod_tpu.ops.pallas_kernels import flash_attention

        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 1, 16), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 1, 16), jnp.float32)
        w = jnp.asarray(rng.randn(16), jnp.float32)

        def loss(q, k, v):
            return ((flash_attention(q, k, v, causal=True) * w) ** 2).sum()

        monkeypatch.setenv("HVDT_FLASH_BWD", "xla")
        ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setenv("HVDT_FLASH_BWD", "kernel")
        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)


class TestRingPallasEnvKnob:
    def test_env_engages_kernel_ring(self, monkeypatch):
        from jax.sharding import Mesh, PartitionSpec as P

        from horovod_tpu.parallel import ring_attention
        import horovod_tpu.ops.pallas_kernels as pk

        monkeypatch.setenv("HVDT_RING_PALLAS", "1")
        calls = []
        orig = pk.flash_block_update

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(pk, "flash_block_update", spy)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("sp",))
        q = jnp.asarray(np.random.RandomState(0).randn(1, 256, 2, 16),
                        jnp.float32)
        jax.shard_map(
            lambda q: ring_attention(q, q, q, axis="sp", causal=True),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(q)
        assert calls   # the per-step kernel actually ran


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


def test_ring_ab_tool_correctness_gate(capsys):
    """tools/ring_ab.py re-states the jnp ring-step math inline (so the
    A/B times exactly what ring_attention runs); if that copy drifts
    from the kernels, its correctness gate must catch it — and this test
    catches the drift at suite time."""
    import importlib
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    ring_ab = importlib.import_module("tools.ring_ab")
    ring_ab.run_shape(1, 128, 2, 16, iters=1)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["bwd_correctness_ok"], rec
    assert rec["fwd_pallas_ms"] > 0 and rec["bwd_jnp_ms"] > 0
