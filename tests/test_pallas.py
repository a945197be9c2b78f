"""Pallas flash-attention kernel tests (interpret mode on CPU — the same
kernel code that compiles for TPU; analog of the reference's CUDA-kernel
correctness tests in test/parallel/test_torch.py fusion cases)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_kernels as pk
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


# ---------------------------------------------------------------------------
# hvdt.kernel.rope: the rotary embedding on [B, L, H*D] rows.
# ---------------------------------------------------------------------------

# (head_dim, heads, rotated dimensions, the call's block): a block of 128
# lanes that holds two heads of 64, one head of 128, one head of 256 with
# 64 rotated, blocks over several sequences' rows.
ROPE_CASES = {
    "two_heads_of_64": (64, 4, 64, (1, 32, 128)),
    "a_head_of_128": (128, 3, 64, (2, 64, 128)),
    "a_head_of_256": (256, 2, 64, (1, 64, 256)),
    "plain_128_batch_rows": (128, 2, 128, (4, 64, 128)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(ROPE_CASES))
def test_rope_kernel_is_the_jnp_form_bit_for_bit(case, dtype):
    """The Mosaic call (in the interpreter here) against ``rope``'s plain
    ``jnp`` form, which is what a CPU and a shape with no block run:
    forward, and the ``custom_vjp`` backward (the same call at the negated
    angle) against JAX's own transpose of the ``jnp`` form.  x, the
    cotangent and the tables hold bf16 values, so each product is exact in
    float32 and a sum of two is rounded once however the backend
    contracts it: the two agree to the last bit."""
    from horovod_tpu.models import transformer as tfm

    d, heads, dim, block = ROPE_CASES[case]
    b, l = 4, 64
    x, w = (jax.random.normal(jax.random.PRNGKey(i), (b, l, heads * d),
                              jnp.bfloat16).astype(dtype) for i in (0, 1))
    pos = jnp.stack([jnp.arange(l) + 37 * i for i in range(b)])
    cos, sin, half = tfm._rope_tables(
        pos, tfm.Rope(theta=1e4, dim=dim % d, attention_factor=1.5), d)
    cos, sin = (t.astype(jnp.bfloat16).astype(jnp.float32)
                for t in (cos, sin))
    assert half == dim // 2 and pk._rope_block(x, d) is None   # off the TPU

    def both(f):
        y, pull = jax.vjp(f, x)
        return y, pull(w)[0]

    y, g = jax.jit(lambda: both(
        lambda x: pk._rope_rows(x, cos, sin, half, block)))()
    y_want, g_want = jax.jit(lambda: both(
        lambda x: pk.rope(x, cos, sin, half)))()
    assert y.dtype == g.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_want, np.float32))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(g, g_want)
    else:       # JAX's transpose rounds a lane's two cotangents apart
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(g_want, np.float32),
            rtol=2 ** -7, atol=2 ** -5)


def test_rope_block_reads_the_platform_and_the_shape(monkeypatch):
    """On a TPU: blocks of 128 lanes (two heads of 64) or a head, as many
    rows (a power of two) as make a 2 MiB float32 slab, of several
    sequences where one is shorter; no block for a decode step's L = 1, an odd L, or heads with
    no 128-lane block, which keep the ``jnp`` form."""
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    rows = lambda b, l, h, d, t=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (b, l, h * d), t)
    assert pk._rope_block(rows(8, 4096, 16, 64), 64) == (1, 4096, 128)
    assert pk._rope_block(rows(128, 512, 16, 64), 64) == (8, 512, 128)
    assert pk._rope_block(rows(2, 8192, 48, 128), 128) == (1, 4096, 128)
    assert pk._rope_block(rows(1, 16384, 16, 256), 256) == (1, 2048, 256)
    assert pk._rope_block(rows(3, 48, 2, 64), 64) == (1, 16, 128)
    for shape in ((4, 1, 16, 64), (4, 7, 16, 64), (2, 512, 3, 64),
                  (2, 512, 4, 96), (2, 24, 2, 64)):
        assert pk._rope_block(rows(*shape), shape[3]) is None
    assert pk._rope_block(rows(2, 24, 2, 64, jnp.float32), 64) == (
        1, 8, 128)
