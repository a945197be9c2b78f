"""The double-gated short convolution (LFM2's ``conv`` mixer, transformers'
``Lfm2ShortConv``): a layer's sequence mixing by a causal depthwise
convolution of a few taps between two elementwise gates, all three read
from one input projection.

    [B | C | X] = h W_in            three blocks of d columns, in that order
    u = B * X
    v_t = sum_i w[i] u[t - (taps - 1) + i] (+ bias)    no activation
    out = (C * v) W_out

It carries no state beyond the last ``taps - 1`` tokens of ``u``: no scan,
no chunk, no kernel of its own.  The gates and the taps are one pass over
``[B | C | X]`` (``gated_delta.causal_conv`` with both gates), bound by
HBM: 3 d read and d written a token.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax

from .gated_delta import causal_conv

__all__ = ["gated_short_conv"]


def gated_short_conv(x: jax.Array, p: Dict[str, jax.Array], *,
                     proj: Callable[[jax.Array, jax.Array], jax.Array]
                     ) -> jax.Array:
    """The mixer on x [B, L, d] (already normed).

    ``p``: ``w_in`` [d, 3 d] with the columns [B | C | X], ``conv`` [taps,
    d] (tap ``taps - 1`` weighs the token itself) and, where the
    convolution has a bias, ``conv_bias`` [d]; ``w_out`` [d, d].  ``proj``
    is the model's dense projection (``x @ w`` in the compute dtype).  The
    gates and the taps are float32 inside one fusion; the result is in
    x's dtype."""
    d = x.shape[-1]
    with jax.named_scope("hvdt.sconv.in"):
        # Products on the matrix's column blocks, so that no [B | C | X]
        # row exists: each gate's cotangent would be padded to its width.
        b, c, xs = (proj(x, p["w_in"][:, i * d:(i + 1) * d])
                    for i in range(3))
    with jax.named_scope("hvdt.sconv.conv"):
        y = causal_conv(xs, p["conv"], p.get("conv_bias"), times=b, gate=c)
    with jax.named_scope("hvdt.sconv.out"):
        return proj(y, p["w_out"])
