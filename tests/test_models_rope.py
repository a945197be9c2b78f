"""RoPE as the model applies it (``models/transformer.py`` ``_rope_tables``
and ``ops.pallas_kernels.rope`` on the [B, L, H * D] rows a projection
wrote) against the [B, L, H, D] half-slicing formula it replaced, kept
here as the oracle, over the rotary settings of the benchmark's three LM
configurations."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.models import transformer as tfm  # noqa: E402
from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402


def _rope_of(x, pos, rope):
    """``x`` [B, L, H, D] rotated as ``_qkv_gate`` rotates the rows a
    projection wrote: the tables, then ``rope`` on [B, L, H * D]."""
    b, l, h, d = x.shape
    return pk.rope(x.reshape(b, l, h * d),
                   *tfm._rope_tables(pos, rope, d)).reshape(x.shape)


def _half_slicing(x, pos, rope):
    """The oracle: rotate-half by slicing each head of x [B, L, H, D] in
    its halves, as the model did before it worked on rows."""
    d = x.shape[-1]
    dim = rope.dim or d
    ang = (pos[..., None].astype(jnp.float32)
           * jnp.asarray(tfm._rope_frequencies(rope, d)))
    cos = (jnp.cos(ang) * rope.attention_factor)[..., None, :]
    sin = (jnp.sin(ang) * rope.attention_factor)[..., None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., dim:]],
        -1).astype(x.dtype)


# (head_dim, heads, kv heads, rotary settings) of the three configurations:
# lm24x1024 (plain), laguna_xs2's full layers (64 of 128, YaRN, the
# attention factor) and its windowed ones (plain 128), qwen3_next_80b (64
# of 256).
ROTARY = {
    "lm24x1024": (64, 16, 16, tfm.Rope()),
    "laguna_full": (128, 48, 8, tfm.Rope(
        theta=500000.0, dim=64, yarn_factor=64.0, yarn_original_max=4096,
        attention_factor=0.1 * float(np.log(64.0)) + 1.0)),
    "laguna_window": (128, 64, 8, tfm.Rope()),
    "qwen3_next": (256, 16, 2, tfm.Rope(theta=1e7, dim=64)),
}


@pytest.mark.parametrize("seq", [1, 7, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(ROTARY))
def test_rope_on_the_rows_is_the_half_slicing_formula(name, dtype, seq):
    """``rope`` on the [B, L, H * D] rows against the [B, L, H, D]
    half-slicing formula, for q's and k's head counts, at per-row
    positions that differ across the batch: to the last bit in float32 and
    to one bf16 rounding in bf16, and ``jax.grad`` through it against the
    oracle's gradient (whose two cotangents a lane are rounded each before
    they are summed, in the activation dtype)."""
    d, heads, kv_heads, rope = ROTARY[name]
    pos = jnp.stack([jnp.arange(seq), 4000 - jnp.arange(seq)])
    ulp = float(jnp.finfo(dtype).eps)

    def both(f):                        # q's and k's rows, and their pulls
        def run(xs, ws):
            out = []
            for x, w in zip(xs, ws):
                y, pull = jax.vjp(lambda x: f(x, pos, rope), x)
                out.append((y, pull(w)[0]))
            return out
        return jax.jit(run)

    xs, ws = ([jax.random.normal(jax.random.PRNGKey(key + i),
                                 (2, seq, h, d), dtype)
               for i, h in enumerate((heads, kv_heads))] for key in (0, 2))
    for (y, g), (y_want, g_want), w in zip(
            both(_rope_of)(xs, ws), both(_half_slicing)(xs, ws), ws):
        assert y.dtype == g.dtype == dtype
        if dtype == jnp.float32:
            np.testing.assert_array_equal(y, y_want)
        else:
            np.testing.assert_allclose(
                np.asarray(y, np.float32), np.asarray(y_want, np.float32),
                rtol=ulp, atol=0)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(g_want, np.float32),
            rtol=2 * ulp, atol=2 * ulp * float(jnp.abs(w).max()))
