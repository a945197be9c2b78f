"""A sliding window in the local flash kernels (flash_attention(...,
window=w): _flash_local_call / _flash_local_bwd_call with a shortened
last grid axis) and in the XLA path, against attention_reference under
the same mask, in interpret mode.  Each case is a program to trace and
compile (about a second); the file is its own so that it is no other
kernel file's share of a worker under --dist loadfile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention as attn
from horovod_tpu.ops import pallas_kernels as pk
from test_pallas import _rand_qkv


def _grads(fn, q, k, v, do):
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                * do.astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)


# (seq, heads, kv heads, head_dim, window, block_q, block_k).  The first
# four are the path the benchmark's cell takes: head_dim 128 with grouped
# queries (one head a block, the kv head picked in the index map, dk / dv
# per query head summed by the caller), at a window smaller than the block,
# equal to it, not a multiple of it (the edge crosses tiles at an offset)
# and with the call's own blocks (from the window: 256).  Then blocks of
# unlike size either way, the head pairs of head_dim 64, the folded layout
# (grouped queries under head_dim 128), and a window of one key.
WINDOWED = [
    (512, 6, 2, 128, 64, 128, 128), (512, 6, 2, 128, 128, 128, 128),
    (512, 8, 2, 128, 200, 128, 128), (512, 2, 1, 128, 300, None, None),
    (512, 2, 1, 128, 130, 256, 128), (512, 2, 1, 128, 130, 128, 256),
    (256, 4, 4, 64, 48, 128, 128), (256, 3, 1, 64, 100, 128, 128),
    (256, 2, 2, 128, 1, 128, 128)]
IDS = ["gqa128_w<b", "gqa128_w=b", "gqa128_w200", "gqa128_auto_blocks",
       "bq>bk", "bq<bk", "d64_pairs", "d64_folded", "window_1"]


@pytest.mark.parametrize("seq, h, hk, d, window, bq, bk", WINDOWED, ids=IDS)
def test_windowed_forward_and_backward_match_the_masked_reference(
        seq, h, hk, d, window, bq, bk):
    q, k, v = _rand_qkv(11, b=1, l=seq, h=h, hkv=hk, d=d, dtype=jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(5), q.shape, q.dtype)

    def kernel(q, k, v):
        return pk.flash_attention(q, k, v, window=window, block_q=bq,
                                  block_k=bk)

    def reference(q, k, v):
        return pk.attention_reference(q, k, v, window=window)

    np.testing.assert_allclose(jax.jit(kernel)(q, k, v),
                               jax.jit(reference)(q, k, v),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(_grads(kernel, q, k, v, do),
                         _grads(reference, q, k, v, do)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_windowed_kernels_in_bf16_at_the_cells_head_layout():
    q, k, v = _rand_qkv(3, b=2, l=512, h=8, hkv=2, d=128,
                        dtype=jnp.bfloat16)
    got = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v, window=128))(
        q, k, v)
    want = pk.attention_reference(q, k, v, window=128)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2e-2, atol=2e-2)


def test_the_reference_mask_is_the_windows_definition():
    # Query i sees keys i - window < j <= i: its own position counts.
    q, k, v = _rand_qkv(1, b=1, l=16, h=1, hkv=1, d=8, dtype=jnp.float32)
    out = pk.attention_reference(q, k, v, window=4)
    i = 9
    s = (q[0, i, 0] @ k[0, i - 3:i + 1, 0].T) * 8 ** -0.5
    want = jax.nn.softmax(s) @ v[0, i - 3:i + 1, 0]
    np.testing.assert_allclose(out[0, i, 0], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [512, 4096])
def test_a_window_that_reaches_the_whole_sequence_is_no_window(window):
    q, k, v = _rand_qkv(2, b=1, l=256, h=2, hkv=2, d=128, dtype=jnp.float32)
    full = jax.jit(pk.flash_attention)(q, k, v)
    got = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v, window=window)
                  )(q, k, v)
    np.testing.assert_array_equal(got, full)
    text = jax.jit(lambda q, k, v: pk.flash_attention(
        q, k, v, window=window)).lower(q, k, v).as_text(debug_info=True)
    assert "flash_win" not in text


def test_a_windowed_call_has_its_own_scope_names_and_a_short_grid():
    q, k, v = _rand_qkv(2, b=1, l=1024, h=2, hkv=1, d=128,
                        dtype=jnp.float32)
    loss = lambda q, k, v, w: jnp.sum(  # noqa: E731
        pk.flash_attention(q, k, v, window=w))
    windowed = jax.jit(jax.grad(lambda *a: loss(*a, 128))).lower(
        q, k, v).as_text(debug_info=True)
    full = jax.jit(jax.grad(lambda *a: loss(*a, None))).lower(
        q, k, v).as_text(debug_info=True)
    for name in ("hvdt.kernel.flash_win_fwd", "hvdt.kernel.flash_win_bwd"):
        assert name in windowed and name not in full
    for name in ("hvdt.kernel.flash_fwd", "hvdt.kernel.flash_bwd"):
        assert name in full and name not in windowed
    # Blocks of 128 from the window: a q tile reaches 2 of the 8 K/V
    # blocks and a K/V block is seen from 2 of the 8 q tiles, and the grid
    # is that short (a full-causal call's last axis counts every block).
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda *a: loss(*a, 128)))(q, k, v))
    assert jaxpr.count("grid=(1, 2, 8, 1, 2)") == 2, jaxpr.count("grid=")


@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_xla_attention_takes_the_same_mask(window):
    q, k, v = _rand_qkv(4, b=2, l=32, h=4, hkv=2, d=16, dtype=jnp.float32)
    np.testing.assert_allclose(
        attn._xla_attention(q, k, v, True, window),
        pk.attention_reference(q, k, v, window=window), rtol=1e-5, atol=1e-5)


def test_attention_dispatches_the_window_to_both_paths(monkeypatch):
    q, k, v = _rand_qkv(6, b=1, l=256, h=2, hkv=1, d=128, dtype=jnp.float32)
    want = pk.attention_reference(q, k, v, window=100)
    for mode in ("off", "on"):
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", mode)
        got = jax.jit(lambda q, k, v: attn.attention(q, k, v, window=100))(
            q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="causal"):
        attn.attention(q, k, v, causal=False, window=100)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, k, v, causal=False, window=100)


def test_window_blocks_come_from_the_window():
    assert pk._window_block(512) == 512 and pk._window_block(513) == 512
    assert pk._window_block(1000) == 512 and pk._window_block(16) == 128
