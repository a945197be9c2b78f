"""The self-contained local flash forward (_flash_fwd_core ->
_flash_local_call) against the plain reference, over its tile logic: 48
cases, each a program of its own to trace and compile in interpret mode,
which is all of their time.  Split from tests/test_pallas.py so that
neither file is a worker's whole share of the run under --dist loadfile."""

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

    q, k, v = _rand_qkv(7, l=seq, h=2, hkv=2 // group, d=d, dtype=dtype)
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
    assert lse.shape == (2, 2, seq) and lse.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    ref_out, ref_lse = attention_reference(q, k, v, causal=causal,
                                           with_lse=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5 if dtype == jnp.float32
                               else 2e-3)
