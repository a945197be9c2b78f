"""The mask family's third member, block diffusion
(``ops.pallas_kernels.flash_attention(block_diffusion=B)`` and
``ops.attention``): the local kernels in interpret mode and XLA attention
against a dense float32 softmax under the mask built here from its
three-term definition, forward and gradients; the order in which the
kernels visit tiles against the dense mask's tiles (none without a visible
pair, every one with a visible pair once); what the paths refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention as attn
from horovod_tpu.ops import pallas_kernels as pk


def dense_mask(length: int, block: int) -> np.ndarray:
    """[2 L, 2 L]: row i of [noisy ; clean] sees row j.  The three terms,
    spelled out: block-diagonal noisy on noisy, strictly earlier blocks
    noisy on clean, block-causal clean on clean."""
    i, j = np.arange(2 * length)[:, None], np.arange(2 * length)[None, :]
    bi, bj = (i % length) // block, (j % length) // block
    noisy_i, noisy_j = i < length, j < length
    return ((noisy_i & noisy_j & (bi == bj))
            | (noisy_i & ~noisy_j & (bj < bi))
            | (~noisy_i & ~noisy_j & (bj <= bi)))


def oracle(q, k, v, mask):
    h, hk = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // hk, 2) for x in (k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def operands(length, heads, kv_heads, head_dim, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (1, 2 * length, h, head_dim))
               for key, h in zip(ks, (heads, kv_heads, kv_heads)))
    return q, k, v, jax.random.normal(ks[3], q.shape)


def compare(fn, length, block, heads, kv_heads, head_dim, tol=2e-5):
    q, k, v, w = operands(length, heads, kv_heads, head_dim)
    mask = jnp.asarray(dense_mask(length, block))
    np.testing.assert_allclose(jax.jit(fn)(q, k, v), oracle(q, k, v, mask),
                               rtol=tol, atol=tol)
    g_got = jax.jit(jax.grad(lambda *a: (fn(*a) * w).sum(),
                             argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.grad(lambda *a: (oracle(*a, mask) * w).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=10 * tol, atol=10 * tol,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("length, tile", [(256, 128), (1024, None)])
@pytest.mark.parametrize("block", [4, 32])
def test_the_kernels_against_the_dense_softmax(block, length, tile, group,
                                               head_dim):
    """L 256 in tiles of 128: two tiles a stream, so the streams' edges
    cross tiles and the first block's noisy rows see no clean key in
    their "lt" tile.  L 1024 in the kernels' own tile (the whole stream):
    the forward's chunks of 512 rows and the backward's of 256 keys trim
    each diagonal tile to what its rows see."""
    heads = 2 * group if group == 1 else group
    compare(lambda q, k, v: pk.flash_attention(
        q, k, v, block_diffusion=block, block_q=tile, block_k=tile),
        length, block, heads, heads // group, head_dim)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("length", [256, 1024])
@pytest.mark.parametrize("block", [4, 32])
def test_xla_attention_against_the_dense_softmax(block, length, group,
                                                 head_dim):
    heads = 2 * group if group == 1 else group
    compare(lambda q, k, v: attn._xla_attention(
        q, k, v, True, None, block), length, block, heads, heads // group,
        head_dim)


@pytest.mark.parametrize("block, length", [(4, 64), (32, 256), (128, 512),
                                          (3, 48)])
def test_the_library_mask_is_the_three_term_definition(block, length):
    np.testing.assert_array_equal(
        np.asarray(pk.block_diffusion_mask(2 * length, block)),
        dense_mask(length, block))


@pytest.mark.parametrize("length, tile, block", [
    (8192, 512, 4), (8192, 4096, 4), (1024, 256, 32), (512, 128, 64),
    (256, 256, 4)])
def test_no_tile_without_a_visible_pair_is_visited(length, tile, block):
    """Both orders (``_bd_key_tile`` for the forward, ``_bd_query_tile`` for
    the backward) visit exactly the tiles of the dense mask that hold a
    visible pair, each once, and name an in-range tile on the steps they
    skip: 288 of 1,024 tiles at 512 x 512, L 8192."""
    n = length // tile
    # The mask reads a position by its block alone: in units of a block it
    # is the mask of blocks of 1, tile / block of them a tile.
    per = tile // block
    has_pair = dense_mask(length // block, 1).reshape(
        2 * n, per, 2 * n, per).any((1, 3))
    forward = np.zeros((2 * n, 2 * n), int)
    for iq in range(2 * n):
        for ik in range(n + 1):
            kt, work, _, _ = (int(x) for x in pk._bd_key_tile(
                jnp.int32(iq), jnp.int32(ik), n))
            assert 0 <= kt < 2 * n
            forward[iq, kt] += work
    backward = np.zeros((2 * n, 2 * n), int)
    for ik in range(2 * n):
        for iq in range(2 * n):
            qt, work, _, _ = (int(x) for x in pk._bd_query_tile(
                jnp.int32(ik), jnp.int32(iq), n))
            assert 0 <= qt < 2 * n
            backward[qt, ik] += work
    np.testing.assert_array_equal(forward, has_pair.astype(int))
    np.testing.assert_array_equal(backward, has_pair.astype(int))
    assert forward.sum() == n * (n + 2)
    if (length, tile) == (8192, 512):
        assert forward.sum() == 288 and forward.size == 1024


def test_attention_takes_the_mask_on_both_paths(monkeypatch):
    """``attention(block_diffusion=)``: the kernels where the policy says
    so, XLA attention elsewhere, one answer."""
    q, k, v, _ = operands(128, 4, 2, 64, seed=3)
    want = oracle(q, k, v, jnp.asarray(dense_mask(128, 4)))
    calls = []
    real = pk.flash_attention
    monkeypatch.setattr(pk, "flash_attention", lambda *a, **kw: (
        calls.append(kw), real(*a, **kw))[1])
    for mode, kernel in (("off", False), ("on", True)):
        monkeypatch.setenv("HVDT_FLASH_ATTENTION", mode)
        got = attn.attention(q, k, v, block_diffusion=4)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert bool(calls) == kernel
    assert calls[0]["block_diffusion"] == 4
    # A block the kernels' chunks cannot hold whole goes to XLA attention.
    calls.clear()
    q, k, v, _ = operands(96, 2, 2, 64, seed=4)
    got = attn.attention(q, k, v, block_diffusion=3)
    np.testing.assert_allclose(
        got, oracle(q, k, v, jnp.asarray(dense_mask(96, 3))), rtol=2e-5,
        atol=2e-5)
    assert not calls


def test_what_the_mask_refuses():
    q, k, v, _ = operands(128, 2, 2, 64)
    with pytest.raises(ValueError, match="no window"):
        attn.attention(q, k, v, window=8, block_diffusion=4)
    with pytest.raises(ValueError, match="power of two"):
        pk.flash_attention(q, k, v, block_diffusion=3)
    with pytest.raises(ValueError, match="whole blocks"):
        pk.block_diffusion_mask(2 * 100, 8)
    assert pk.block_diffusion_tiles(16384, 4)
    assert not pk.block_diffusion_tiles(16384, 128)     # a whole tile
    assert not pk.block_diffusion_tiles(2 * 8192 + 1, 4)


def test_the_kernels_lower_under_their_own_names():
    """``hvdt.kernel.flash_bd_fwd`` / ``flash_bd_bwd``, so that a reader of
    the causal or the windowed calls reads neither."""
    q, k, v, w = operands(128, 2, 2, 64)
    text = jax.jit(jax.grad(lambda q: (pk.flash_attention(
        q, k, v, block_diffusion=4) * w).sum())).lower(q).as_text(
            debug_info=True)
    assert "hvdt.kernel.flash_bd_fwd" in text
    assert "hvdt.kernel.flash_bd_bwd" in text
    assert "hvdt.kernel.flash_fwd" not in text
    assert "hvdt.kernel.flash_win" not in text
