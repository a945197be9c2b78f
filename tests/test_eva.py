"""EVA attention (``ops/eva.py``) at a small size on the CPU: both forms of
the aggregation (the blocked XLA form; the kernels through the interpreter:
the causal flash calls on the aligned windows with their logsumexp as an
output, the summaries' three calls, the merge) against the dense
definition PAIR BY PAIR (which (i, j) and (i, c) are visible: both masks,
the first window has no summary, a chunk of the row's own window is never
a summary), the output and the gradients to q, k, v, phi, mu against the
plain reference's pieces (``benchmark/reference/evabyte.py``) in float32,
the summaries' kernels over several tiles of a prefix, and the pair
count."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import evabyte as reference  # noqa: E402
from horovod_tpu.ops import eva  # noqa: E402
from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402

WINDOW, CHUNK, HEADS, DIM = 8, 2, 3, 16
FORMS = {"xla": eva._eva_xla, "kernels": eva._eva_kernels}
# three windows; a sequence that is one window
LENGTHS = [3 * WINDOW, WINDOW]


def operands(length, batch=2, seed=0, scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, length, HEADS, DIM))
               for key in ks[:3])
    phi, mu = (scale * jax.random.normal(key, (HEADS, DIM))
               for key in ks[3:])
    return q, k, v, phi, mu


def dense(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK):
    """The definition from the plain reference's pieces, a sequence at a
    time: its pooling, its two masks, one softmax over [keys ;
    summaries]."""
    def sequence(q, k, v):
        length, dh = q.shape[0], q.shape[-1]
        ks, vs = reference.summaries(k, v, phi, mu, chunk)
        s = jnp.einsum("qhd,khd->hqk", q, jnp.concatenate([k, ks])) \
            / np.sqrt(dh)
        seen = reference.visible(jnp.arange(length), length, window, chunk)
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                          jnp.concatenate([v, vs]))
    return jax.vmap(sequence)(q, k, v)


@pytest.mark.parametrize("length", LENGTHS)
def test_the_two_masks_pair_by_pair(length):
    seen = np.asarray(reference.visible(jnp.arange(length), length, WINDOW,
                                        CHUNK))
    exact, summary = seen[:, :length], seen[:, length:]
    for i in range(length):
        for j in range(length):
            assert exact[i, j] == (j // WINDOW == i // WINDOW and j <= i)
        for c in range(length // CHUNK):
            assert summary[i, c] == ((c * CHUNK) // WINDOW < i // WINDOW)
    # the first window has no summary; a chunk of a row's own window is
    # never one; every chunk of every earlier window is
    assert not summary[:WINDOW].any()
    per = WINDOW // CHUNK
    for i in range(length):
        w = i // WINDOW
        assert not summary[i, w * per:].any() and summary[i, :w * per].all()
    assert (int(exact.sum()), int(summary.sum())) == \
        eva.eva_visible_pairs(length, WINDOW, CHUNK)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_row_reads_the_keys_and_summaries_its_masks_show_and_no_other(
        form):
    """Which v_j and which v~_c move o_i, read off the Jacobian: the
    aggregation's own masks, pair by pair."""
    length = 3 * WINDOW
    q, k, v, phi, mu = operands(length, batch=1)
    ks, vs = eva.eva_summaries(k, v, phi, mu, CHUNK)

    def rows(v, vs):                    # one number a row: head 0's sum
        return FORMS[form](q, k, v, ks, vs, WINDOW, CHUNK)[0, :, 0].sum(-1)

    by_key, by_summary = jax.jit(jax.jacobian(rows, argnums=(0, 1)))(v, vs)
    moved = np.abs(np.asarray(by_key)[:, 0, :, 0]).sum(-1) > 0
    moved_s = np.abs(np.asarray(by_summary)[:, 0, :, 0]).sum(-1) > 0
    seen = np.asarray(reference.visible(jnp.arange(length), length, WINDOW,
                                        CHUNK))
    np.testing.assert_array_equal(moved, seen[:, :length])
    np.testing.assert_array_equal(moved_s, seen[:, length:])


@pytest.mark.parametrize("length", LENGTHS, ids=["3windows", "1window"])
@pytest.mark.parametrize("path", ["off", "on"], ids=["xla", "kernels"])
def test_output_and_gradients_match_the_definition(monkeypatch, path,
                                                   length):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    args = operands(length)
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)
                     ).reshape(args[0].shape)

    def ours(*a):
        return eva.eva_attention(*a, window=WINDOW, chunk=CHUNK)

    np.testing.assert_allclose(jax.jit(ours)(*args), dense(*args),
                               atol=2e-6)
    got = jax.jit(jax.grad(lambda *a: (ours(*a) * weight).sum(),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.grad(lambda *a: (dense(*a) * weight).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v phi mu".split(), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    if length == WINDOW:                # no summary is read
        assert not np.asarray(got[3]).any() and not np.asarray(got[4]).any()
    else:
        assert np.abs(np.asarray(got[3])).max() > 1e-3
        assert np.abs(np.asarray(got[4])).max() > 1e-3


@pytest.mark.parametrize("forward, backward", [(4, 8), (8, 4), (16, 16)])
def test_the_summaries_kernels_over_several_tiles_of_a_prefix(
        monkeypatch, forward, backward):
    """4 windows, 4 summaries a window: a row sees a prefix of 0, 4, 8 or
    12 of the 16.  In tiles of 4 every tile is whole or skipped, in tiles
    of 8 the prefixes of 4 and 12 end inside one (masked by columns), in
    one tile of 16 all do; the forward and the backward's two calls take
    their own tile.  Output, logsumexp and the three cotangents against
    the XLA form of the same, with a cotangent on the logsumexp as the
    merge sends one."""
    monkeypatch.setattr(pk, "_EVA_FWD_TILE", forward)
    monkeypatch.setattr(pk, "_EVA_BWD_TILE", backward)
    length, per = 4 * WINDOW, WINDOW // CHUNK
    q, k, v, phi, mu = operands(length, seed=3)
    ks, vs = eva.eva_summaries(k, v, phi, mu, CHUNK)
    assert pk.eva_summary_tiles(length, WINDOW, per, DIM, q.dtype)

    def scalar(fn):
        def f(q, ks, vs):
            out, lse = fn(q, ks, vs)
            later = lse[:, :, WINDOW:]          # the first window's: -1e30
            return (out * jnp.sin(out + 1.0)).sum() + (later ** 2).sum()
        return f

    kernels = lambda *a: pk.eva_summary_attention(  # noqa: E731
        *a, window=WINDOW, per=per)
    plain = lambda *a: eva._summary_attention_xla(  # noqa: E731
        *a, WINDOW, per)
    out, lse = jax.jit(kernels)(q, ks, vs)
    want_out, want_lse = plain(q, ks, vs)
    np.testing.assert_allclose(out, want_out, atol=2e-6)
    np.testing.assert_allclose(lse[:, :, WINDOW:], want_lse[:, :, WINDOW:],
                               atol=2e-6)
    assert (np.asarray(lse[:, :, :WINDOW]) < -1e29).all()
    assert not np.asarray(out[:, :WINDOW]).any()
    got = jax.jit(jax.grad(scalar(kernels), argnums=(0, 1, 2)))(q, ks, vs)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2))(q, ks, vs)
    for name, a, b in zip(("q", "ks", "vs"), got, want):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


def test_flash_attention_stats_gives_the_logsumexp_and_takes_its_cotangent():
    q, k, v, _, _ = operands(32, seed=5)

    def scalar(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return (out * jnp.cos(out)).sum() + (lse ** 2).sum()
        return f

    plain = lambda *a: pk.attention_reference(*a, with_lse=True)  # noqa: E731
    out, lse = jax.jit(pk.flash_attention_stats)(q, k, v)
    want_out, want_lse = plain(q, k, v)
    np.testing.assert_allclose(out, want_out, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    got = jax.jit(jax.grad(scalar(pk.flash_attention_stats),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-5)


@pytest.mark.parametrize("seq, window, chunk", [
    (24, 8, 2), (8, 8, 2), (64, 16, 4), (32768, 2048, 16)])
def test_the_pair_count(seq, window, chunk):
    exact, summary = eva.eva_visible_pairs(seq, window, chunk)
    if seq <= 64:
        seen = np.asarray(reference.visible(jnp.arange(seq), seq, window,
                                            chunk))
        assert (exact, summary) == (int(seen[:, :seq].sum()),
                                    int(seen[:, seq:].sum()))
    else:
        # the issue's count at the cell's length: 48% on summaries
        assert (exact, summary) == (33_570_816, 31_457_280)
        assert exact + summary == 65_028_096


def test_what_the_mixer_refuses():
    q, k, v, phi, mu = operands(20)
    with pytest.raises(ValueError, match="whole windows"):
        eva.eva_attention(q, k, v, phi, mu, window=8, chunk=2)
    with pytest.raises(ValueError, match="whole windows"):
        eva.eva_attention(*operands(24), window=8, chunk=3)
    with pytest.raises(ValueError, match="a key head a query head"):
        eva.eva_attention(q, k[:, :, :1], v[:, :, :1], phi, mu, window=4,
                          chunk=2)
