"""The flash kernels' gradients (custom_vjp, the kernel backward beside the
blockwise one, the ring's) and the mesh gate that routes attention to them;
interpret mode on the CPU.  Split from tests/test_pallas.py so that
neither file is a worker's whole share of the run under --dist loadfile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                            flash_attention)


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
        """lq=512 with 128-blocks: a 4 x 4 grid of tiles — exercises the
        backward kernel's skipped, straddling and fully visible tiles,
        dq accumulated across K blocks and dk/dv across q tiles (a
        single-block run covers none of them)."""
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
        import horovod_tpu.ops.attention as att

        gate_args = []
        orig = att.kernel_enabled

        def spy(l, **kw):
            gate_args.append(l)
            return orig(l, **kw)

        monkeypatch.setattr(att, "kernel_enabled", spy)
        cfg = TransformerConfig(vocab=128, layers=1, d_model=32, heads=2,
                                kv_heads=2, d_ff=64, max_seq=128,
                                dtype=jnp.float32)
        p = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
        loss, g = jax.value_and_grad(transformer_loss)(p, toks, cfg)
        assert np.isfinite(float(loss))
        # attention ran on the FULL power-of-two seq -> gate engaged
        # (the seq the gate saw is the regression being pinned)
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

        g = jax.jit(jax.grad(loss))(q, k, v)
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
            jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(None, "sp"),
                                  out_specs=P(None, "sp")))(q)


class TestFlashMeshGate:
    def test_auto_mesh_axes_route_to_island(self, monkeypatch):
        """Mosaic kernels can't be GSPMD-auto-partitioned: under a
        partially-manual context (auto dp axis present) the plan must
        route through a shard_map island — never "direct" — and from a
        fully-manual context the kernel may run directly."""
        from jax.sharding import Mesh, PartitionSpec as P

        import horovod_tpu.ops.attention as att

        monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
        assert att.kernel_plan(2, 128, 4, 4) == "direct"      # no mesh

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "sp"))
        seen = {}

        def probe(x):
            seen["plan"] = att.kernel_plan(2, 128, 4, 4)
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
            plan = att.kernel_plan(2, 128, 4, 4)
        # Pure-auto mesh: island engages (size-1 axes absorbed).
        assert plan not in (None, "direct")
        dp_axes, tp_ax, names = plan
        assert names == frozenset({"dp", "tp"})

        def probe2(x):
            seen["manual"] = att.kernel_plan(2, 128, 4, 4)
            return x

        jax.jit(jax.shard_map(probe2, mesh=mesh, in_specs=P(),
                              out_specs=P()))(jnp.ones(4))
        assert seen["manual"] == "direct"          # fully manual: direct


class TestFlashBackwardPaths:
    @pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
    def test_kernel_backward_matches_xla_backward(self, window):
        """The two backwards of flash_attention's custom_vjp, each called
        directly on the forward's residuals: the Pallas call every shape
        takes that fits in VMEM, and the blockwise XLA recompute kept for
        the one that does not.  GQA, so both sum dk/dv over a group; with
        and without a sliding window, which both take."""
        from horovod_tpu.ops import pallas_kernels as pk

        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 1, 16), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 1, 16), jnp.float32)
        do = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
        args = (True, 0.25, 128, 128)       # causal, scale, block_q, block_k
        out, res = pk._flash_attn_fwd(q, k, v, *args, window, None)
        got = jax.jit(lambda res, do: pk._flash_attn_bwd(
            *args, window, None, res, do))(res, do)
        lse_rows = res[4]
        ref = jax.jit(lambda res, do: pk._flash_bwd_blockwise(
            *args, res[:4] + (lse_rows[:, :, 0, :],), do, window))(res, do)
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
        jax.jit(jax.shard_map(
            lambda q: ring_attention(q, q, q, axis="sp", causal=True),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))(q)
        assert calls   # the per-step kernel actually ran
