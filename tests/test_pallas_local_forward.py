"""The self-contained local flash forward (_flash_fwd_core ->
_flash_local_call) against the plain reference, over its tile logic (48
cases) and over the head layouts a program can take from the [B, L, H*D]
rows (10 cases), each a program of its own to trace and compile in
interpret mode, which is all of their time: 81 s alone here, under the
driver's command its worker's share of 290 s.  Split from
tests/test_pallas.py so that neither file is a worker's whole share of the
run under --dist loadfile."""

import numpy as np
import pytest

import jax.numpy as jnp

from horovod_tpu.ops.pallas_kernels import attention_reference
from test_pallas import _rand_qkv


# (seq, block_q, block_k, head_dim, rows per chunk or None for the call's
# own choice, which is the whole tile at these sizes).  Causal seq 256 at
# 64 x 128 and at 128 x 64 meets fully visible, straddling and skipped
# tiles in one run (and the K/V index clamp on the skipped ones); 192 only
# tiles at 64, so _fit_block has to shrink the 128s.  head_dim 16 has a
# power-of-two scale (folded into q in bf16 too), head_dim 32 has not.  The
# last three work a tile through in chunks of rows: square tiles, where a
# chunk on the diagonal stops at its own last key; 64 x 128, where the
# diagonal crosses a tile at an offset; and the whole sequence as one tile,
# the form the cell's shape takes.  Two heads throughout: ungrouped they
# are one program's two heads (the whole 32- or 64-lane row its block),
# grouped they fold into the batch (the transposed route).
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
    plain reference; the call itself, on [B, L, H*D] operands, where the
    case names its chunks."""
    from horovod_tpu.ops.pallas_kernels import (_fit_block, _flash_fwd_core,
                                                _flash_local_call,
                                                _heads_layout,
                                                _heads_per_program,
                                                _rows_layout)

    q, k, v = _rand_qkv(7, l=seq, h=2, hkv=2 // group, d=d, dtype=dtype)
    bq = _fit_block(seq, block_q, dtype)
    bk = _fit_block(seq, block_k, dtype)
    if rows is None:
        assert seq // bq > 1 and seq // bk > 1      # several tiles each way
        out, lse = _flash_fwd_core(q, k, v, causal, d ** -0.5, bq, bk)
    else:
        fold = _heads_per_program(2, 2 // group, d) is None
        assert fold == (group == 2)
        out, lse = _flash_local_call(
            *(_rows_layout(x, fold) for x in (q, k, v)),
            heads=1 if fold else 2, causal=causal, scale=d ** -0.5,
            block_q=bq, block_k=bk, rows=rows)
        assert out.shape == ((4, seq, d) if fold else (2, seq, 2 * d))
        out, lse = _heads_layout(out, q.shape, fold), lse.reshape(2, 2, seq)
    _assert_matches_reference(out, lse, q, k, v, causal)


def _assert_matches_reference(out, lse, q, k, v, causal):
    """Head by head, each against its own reference: a lane select that
    swapped or mixed two heads of a program fails in both."""
    b, seq, h, _ = q.shape
    dtype = q.dtype
    assert out.shape == q.shape and out.dtype == dtype
    assert lse.shape == (b, h, seq) and lse.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    ref_out, ref_lse = attention_reference(q, k, v, causal=causal,
                                           with_lse=True)
    for head in range(h):
        np.testing.assert_allclose(
            np.asarray(out[:, :, head], np.float32),
            np.asarray(ref_out[:, :, head], np.float32), rtol=tol, atol=tol,
            err_msg=f"out of head {head}")
        np.testing.assert_allclose(
            np.asarray(lse[:, head]), np.asarray(ref_lse[:, head]),
            rtol=2e-5, atol=2e-5 if dtype == jnp.float32 else 2e-3,
            err_msg=f"lse of head {head}")


# (q heads, kv heads, head_dim, heads a program takes or None for the
# transposed route, seq, block_q, block_k, rows per chunk).  head_dim 64
# is the benchmark's: a pair of heads a program, one pair (the row is the
# block) and two (the pair is a block index), over several tiles and in
# chunks of one tile.  head_dim 128 is one head a program, grouped queries
# through the index map.  H odd at head_dim 64 has no 128-lane block and
# folds its heads into the batch.
HEAD_LAYOUTS = [(2, 2, 64, 2, 256, 128, 128, 32),
                (4, 4, 64, 2, 256, 64, 128, None),
                (4, 4, 64, 2, 256, 256, 256, 64),
                (2, 1, 128, 1, 256, 128, 64, None),
                (3, 3, 64, None, 256, 128, 128, None)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "h, hkv, d, per, seq, block_q, block_k, rows", HEAD_LAYOUTS,
    ids=["h2_d64_pair_rows32", "h4_d64_pairs_tiles",
         "h4_d64_pairs_one_tile_rows64", "h2_d128_gqa", "h3_d64_folded"])
def test_local_forward_head_layouts(h, hkv, d, per, seq, block_q, block_k,
                                    rows, dtype):
    """The causal forward at each way a program takes its heads from the
    [B, L, H*D] rows, every head against its own reference."""
    from horovod_tpu.ops.pallas_kernels import (_flash_local_call,
                                                _heads_layout,
                                                _heads_per_program,
                                                _rows_layout)

    assert _heads_per_program(h, hkv, d) == per
    fold = per is None
    q, k, v = _rand_qkv(9, l=seq, h=h, hkv=hkv, d=d, dtype=dtype)
    out, lse = _flash_local_call(
        *(_rows_layout(x, fold) for x in (q, k, v)), heads=1 if fold else h,
        causal=True, scale=d ** -0.5, block_q=block_q, block_k=block_k,
        rows=rows)
    assert out.shape == ((2 * h, seq, d) if fold else (2, seq, h * d))
    _assert_matches_reference(_heads_layout(out, q.shape, fold),
                              lse.reshape(2, h, seq), q, k, v, True)
