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
