"""The self-contained local flash backward (_flash_attn_bwd ->
_flash_local_bwd_call) against the gradients of the plain reference, over
its tile logic (24 cases) and over the head layouts a program can take from
the [B, L, H*D] rows (10 cases); interpret mode on the CPU, where each case
is a program of its own to trace and compile, which is all of its time:
66 s alone here.  A file of its own so that it is no other file's share of
the run under --dist loadfile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import attention_reference
from test_pallas import _rand_qkv


def _reference_grads(q, k, v, do, causal):
    """Gradients of sum(attention * do) by the plain reference, in f32."""
    f32 = jnp.float32

    def loss(q, k, v):
        return (attention_reference(q, k, v, causal=causal) * do.astype(f32)
                ).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
        q.astype(f32), k.astype(f32), v.astype(f32))


def _assert_grads_close(got, want, dtype):
    # bf16 operands of the five products, f32 accumulation: a few units in
    # the last place of a bf16 gradient of order 1.
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == dtype and g.shape == w.shape, name
        bound = tol * max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   rtol=0, atol=bound, err_msg=name)


# (batch, seq, block_q, block_k, head_dim, q heads per kv head, keys per
# chunk or None for the call's own choice, which is the whole block at these
# sizes).  Causal seq 256 at 64 x 128 and at 128 x 64 meets fully visible,
# straddling and skipped tiles in one run (and the q / dO / statistics index
# clamp on the skipped ones), with dq accumulated over two or four K blocks;
# 192 only tiles at 64, so _fit_block has to shrink the 128s.  head_dim 16
# has a power-of-two scale (folded into q in bf16 too), head_dim 32 has not
# (dk takes its scale in the flush).  The last three work a tile through in
# chunks of keys: square tiles, where a chunk on the diagonal starts at its
# own first row; the whole sequence as one tile at batch 1, like the
# benchmark's reference sample; and 64 x 128, where the diagonal crosses a
# tile at an offset.  Every other shape is GQA and folds its two heads into
# the batch (the transposed route); the ungrouped ones are one program's
# two heads, the whole 32- or 64-lane row its block.
LOCAL_BACKWARD_SHAPES = [(2, 256, 64, 128, 32, 1, None),
                         (2, 256, 128, 64, 16, 2, None),
                         (2, 192, 128, 128, 32, 2, None),
                         (2, 256, 128, 128, 32, 1, 32),
                         (1, 256, 256, 256, 16, 1, 64),
                         (2, 256, 64, 128, 16, 2, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize(
    "batch, seq, block_q, block_k, d, group, keys", LOCAL_BACKWARD_SHAPES,
    ids=["256_64x128", "256_128x64_gqa", "192_fit_gqa", "256_128x128_keys32",
         "b1_256_one_tile_keys64", "256_64x128_keys64_gqa"])
def test_local_backward_matches_reference_gradients(batch, seq, block_q,
                                                    block_k, d, group, keys,
                                                    causal, dtype):
    """dq, dk, dv in the operands' dtype against the reference's f32
    gradients: through flash_attention's custom_vjp (layout, delta, the
    GQA group sum) where the call chooses its chunks, through the call
    itself, on [B, L, H*D] operands, where the case names them."""
    from horovod_tpu.ops.pallas_kernels import flash_attention

    q, k, v = _rand_qkv(11, b=batch, l=seq, h=2, hkv=2 // group, d=d,
                        dtype=dtype)
    do = _rand_qkv(12, b=batch, l=seq, h=2, hkv=2, d=d, dtype=dtype)[0]
    want = _reference_grads(q, k, v, do, causal)
    if keys is None:
        got = jax.jit(jax.grad(
            lambda q, k, v: (flash_attention(
                q, k, v, causal=causal, block_q=block_q, block_k=block_k
            ).astype(jnp.float32) * do.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    else:
        got = _call_backward(q, k, v, do, causal, block_q, block_k, keys)
    _assert_grads_close(got, want, dtype)


def _call_backward(q, k, v, do, causal, block_q, block_k, keys):
    """_flash_local_bwd_call on the operands' kernel layout, the row
    statistics from the reference; -> dq, dk, dv as [B, L, H(kv), D]."""
    from horovod_tpu.ops.pallas_kernels import (_fit_block,
                                                _flash_local_bwd_call,
                                                _heads_layout,
                                                _heads_per_program,
                                                _rows_layout)

    f32 = jnp.float32
    b, seq, h, d = q.shape
    hkv = k.shape[2]
    fold = _heads_per_program(h, hkv, d) is None
    heads = 1 if fold else h
    out, lse = attention_reference(q, k, v, causal=causal, with_lse=True)
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(f32), out.astype(f32))
    grads = _flash_local_bwd_call(
        *(_rows_layout(x, fold) for x in (q, k, v, do)),
        *(x.reshape(b * h // heads, heads, 1, seq) for x in (lse, delta)),
        heads=heads, causal=causal, scale=d ** -0.5,
        block_q=_fit_block(seq, block_q, q.dtype),
        block_k=_fit_block(seq, block_k, q.dtype), keys=keys)
    assert grads[0].shape == ((b * h, seq, d) if fold else (b, seq, h * d))
    dq, dk, dv = (_heads_layout(x, q.shape, fold) for x in grads)
    # dk/dv leave the call per q head: sum each kv head's group.
    dk, dv = (x.reshape(b, seq, hkv, h // hkv, d).astype(f32).sum(3)
              .astype(q.dtype) for x in (dk, dv))
    return dq, dk, dv


# (q heads, kv heads, head_dim, heads a program takes or None for the
# transposed route, block_q, block_k, keys per chunk), seq 256, causal: the
# forward's HEAD_LAYOUTS.  Every head's gradients are its own reference's
# (the heads' inputs differ), so a product that took another head's lanes,
# or added into them, fails.
HEAD_LAYOUTS = [(2, 2, 64, 2, 128, 128, 32),
                (4, 4, 64, 2, 64, 128, None),
                (4, 4, 64, 2, 256, 256, 64),
                (2, 1, 128, 1, 128, 64, None),
                (3, 3, 64, None, 128, 128, None)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "h, hkv, d, per, block_q, block_k, keys", HEAD_LAYOUTS,
    ids=["h2_d64_pair_keys32", "h4_d64_pairs_tiles",
         "h4_d64_pairs_one_tile_keys64", "h2_d128_gqa", "h3_d64_folded"])
def test_local_backward_head_layouts(h, hkv, d, per, block_q, block_k, keys,
                                     dtype):
    """The causal backward at each way a program takes its heads from the
    [B, L, H*D] rows, against the reference's gradients."""
    from horovod_tpu.ops.pallas_kernels import _heads_per_program

    assert _heads_per_program(h, hkv, d) == per
    q, k, v = _rand_qkv(14, l=256, h=h, hkv=hkv, d=d, dtype=dtype)
    do = _rand_qkv(15, l=256, h=h, hkv=h, d=d, dtype=dtype)[0]
    got = _call_backward(q, k, v, do, True, block_q, block_k,
                         keys or min(block_k, 256))
    _assert_grads_close(got, _reference_grads(q, k, v, do, True), dtype)


def test_backward_blocks_come_from_the_shape_and_vmem_alone():
    """Square, the forward's choice or smaller; None, and with it the
    blockwise XLA backward, only for a sequence whose f32 dq cannot stay
    in VMEM beside the smallest blocks."""
    from horovod_tpu.ops.pallas_kernels import (_backward_blocks,
                                                _forward_blocks)

    for seq in (128, 512, 4096, 32768):
        for d, dtype in ((64, jnp.bfloat16), (128, jnp.bfloat16),
                         (64, jnp.float32)):
            bq, bk = _backward_blocks(seq, seq, d, dtype)
            fq, fk = _forward_blocks(seq, seq, d, dtype)
            assert bq == bk and bq <= fq and seq % bq == 0, (seq, d, dtype)
    assert _backward_blocks(1 << 20, 1 << 20, 64, jnp.bfloat16) is None


def test_a_sequence_the_kernel_cannot_hold_takes_the_blockwise_backward(
        monkeypatch):
    """_backward_blocks None -> _flash_bwd_blockwise, with the same
    gradients (the selection is by shape; the test forces it)."""
    from horovod_tpu.ops import pallas_kernels as pk

    q, k, v = _rand_qkv(13, l=256, h=2, hkv=1, d=16, dtype=jnp.float32)

    def grads():
        return jax.jit(jax.grad(
            lambda q, k, v: (pk.flash_attention(q, k, v, block_q=128,
                                                block_k=128) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    kernel = grads()
    called = []
    blockwise = pk._flash_bwd_blockwise
    monkeypatch.setattr(pk, "_backward_blocks", lambda *a: None)
    monkeypatch.setattr(pk, "_flash_bwd_blockwise",
                        lambda *a: called.append(1) or blockwise(*a))
    for a, b in zip(grads(), kernel):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-4)
    assert called
