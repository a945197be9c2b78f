"""``ops.pallas_kernels.moe_sum_rows`` (PR 40): the experts' rows back to
their tokens as one Mosaic call, in the interpreter on the CPU, against
XLA's gather and sum (``parallel.moe._sum_held_rows_xla``), which stays
the path of the shapes the kernel has no blocks for; the expert layer's
gradients through both custom VJPs with the kernel on; and the lowered
layer, which no longer gathers from the sorted buffer."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel import moe

T, D = 1024, 128


def routed(t, k, experts, first, held, seed=1):
    """(inverse [T * k], held [T, k], segment [T * k]) as
    ``moe_held_experts`` makes them: top-k of ``experts``, the ``held``
    from ``first`` sorted to the front by expert, the others behind."""
    _, picked = jax.lax.top_k(
        jax.random.uniform(jax.random.PRNGKey(seed), (t, experts)), k)
    local = picked.reshape(t * k) - first
    landed = (local >= 0) & (local < held)
    segment = jnp.where(landed, local, held).astype(jnp.int32)
    inverse = jnp.argsort(jnp.argsort(segment, stable=True))
    return inverse.astype(jnp.int32), landed.reshape(t, k), segment


def buffer(t, k, d, dtype, landed):
    """Rows of the sorted buffer; behind the ``landed`` first, NaN: what
    the grouped products leave there may be anything."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (t * k, d)).astype(dtype)
    return jnp.where((jnp.arange(t * k) < landed)[:, None], rows, jnp.nan)


def runs_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


KINDS = {"all": (8, 0, 8), "none": (16, 8, 8), "one_in_eight": (64, 0, 8)}


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("k", [1, 8, 10])
def test_the_kernel_sums_what_xlas_gather_and_sum_do(k, dtype, kind):
    """Every pick held (a deployment's full buffer), none, one in eight
    (the benchmark's cells): the float32 sum of a token's held rows, one
    rounding; nothing of the rows behind those that landed."""
    experts, first, held_experts = KINDS[kind]
    if kind == "all" and k > experts:
        experts = held_experts = 16
    inverse, held, segment = routed(T, k, experts, first, held_experts)
    rows = buffer(T, k, D, dtype, int(held.sum()))
    fn = jax.jit(lambda r, i, h, s: moe._sum_held_rows(
        r, i, h, s, held_experts + 1))
    assert runs_kernel(fn, rows, inverse, held, segment)
    got = np.asarray(fn(rows, inverse, held, segment), np.float64)
    picks = np.where(np.asarray(held)[:, :, None], np.asarray(
        rows, np.float64)[np.asarray(inverse)].reshape(T, k, D), 0.0)
    want = picks.sum(1)
    assert np.isfinite(got).all()
    # a float32 sum of k rows, and for bf16 rows one rounding of it (half
    # an ulp of 2 ** -7)
    room = k * 2.0 ** -23 * np.abs(picks).sum(1)
    if dtype == jnp.bfloat16:
        room = room + 2.0 ** -8 * np.abs(want)
        np.testing.assert_array_equal(got, np.asarray(
            moe._sum_held_rows_xla(rows, inverse, held), np.float64))
    assert (np.abs(got - want) <= room).all()


@pytest.mark.parametrize("t, k, d, experts", [
    (1000, 2, 128, 4),          # tokens that are not whole programs
    (1024, 2, 96, 4),           # rows that are not whole lanes
    (1024, 1, 128, 128)])       # more run ends than rows
def test_a_shape_without_blocks_keeps_xlas_form_and_the_same_numbers(
        t, k, d, experts):
    assert pk._moe_sum_rows_blocks(t, k, d, experts + 1,
                                   jnp.bfloat16) is None
    inverse, held, segment = routed(t, k, experts, 0, experts)
    rows = buffer(t, k, d, jnp.bfloat16, t * k)
    fn = lambda r, i, h, s: moe._sum_held_rows(r, i, h, s, experts + 1)
    assert not runs_kernel(fn, rows, inverse, held, segment)
    np.testing.assert_array_equal(
        np.asarray(fn(rows, inverse, held, segment), np.float32),
        np.asarray(moe._sum_held_rows_xla(rows, inverse, held), np.float32))


def test_the_blocks_are_read_from_the_shapes_alone():
    """The three sparse cells' layers have blocks (16,384 tokens, rows of
    2,048, 33 / 33 / 17 runs); what a tile stages is the picks plus the
    runs' ends in whole lanes, and fits VMEM."""
    for k, segments, staged in ((8, 33, 1536), (10, 33, 1792), (8, 17, 1280)):
        groups, vmem = pk._moe_sum_rows_blocks(16384, k, 2048, segments,
                                               jnp.bfloat16)
        assert groups * 8 == staged and vmem < 64 << 20
    assert pk._moe_sum_rows_blocks(16384, 8, 1 << 15, 33, jnp.float32) is None


def _layer_inputs():
    import test_moe_held as held_tests

    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    e, f = 16, 8
    w = dict(x=jax.random.normal(ks[0], (T, D)),
             w_router=jax.random.normal(ks[1], (D, e)),
             w_up=jax.random.normal(ks[2], (e, D, f)) * 0.3,
             w_gate=jax.random.normal(ks[3], (e, D, f)) * 0.3,
             w_down=jax.random.normal(ks[4], (e, f, D)) * 0.3)
    return held_tests, w


@pytest.mark.parametrize("first, held", [(0, 4), (8, 8), (0, 16)])
def test_the_layer_and_its_gradients_with_the_kernel_on(first, held):
    """Both custom VJPs with the kernel in them (the forward of
    ``_tokens_of_rows``, the backward of ``_rows_of_tokens``), against
    every expert applied to every token and masked by the picks."""
    held_tests, w = _layer_inputs()
    route = dict(top_k=2, score="softmax")
    layer = lambda w: held_tests._layer(w, first, held, **route)[0]
    plain = lambda w: held_tests._every_expert_on_every_token(
        w, first, held, **route)
    loss = lambda fn: (lambda w: jnp.sum(fn(w) ** 2))
    text = str(jax.make_jaxpr(jax.grad(loss(layer)))(w))
    assert text.count("pallas_call") == 2
    np.testing.assert_allclose(jax.jit(layer)(w), plain(w), rtol=1e-5,
                               atol=1e-4)
    got, want = jax.jit(jax.grad(loss(layer)))(w), jax.grad(loss(plain))(w)
    for name in w:      # float32 at widths of 128: against the leaf's scale
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_the_lowered_layer_gathers_nothing_from_the_sorted_buffer(
        monkeypatch, request):
    """The layer's gradient lowered for the TPU (the Pallas -> Mosaic
    lowering is Python and needs no chip): ``moe_sum_rows`` is called under
    ``hvdt.moe.dispatch.tokens`` and under ``.rows`` (one jitted function
    for both, the Mosaic call under ``hvdt.kernel.moe_sum_rows``), and
    every gather that is left reads the tokens' ``[T, D]`` rows (the other
    move and its transpose), none the ``[T * k, D]`` buffer: the gathered
    copy cannot come back unnoticed.  A shape without blocks still gathers
    from the buffer."""
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    held_tests, w = _layer_inputs()
    # ``moe_sum_rows`` is jitted: a trace of these shapes from another test
    # holds the interpreter's call, and this one's must not outlive it.
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)

    def lowered(tokens):
        ws = dict(w, x=w["x"][:tokens])
        text = jax.jit(jax.grad(lambda w: jnp.sum(held_tests._layer(
            w, 0, 4, top_k=2, score="softmax")[0] ** 2))).trace(ws).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
        locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

        def where(line):
            at = re.search(r"loc\((#loc\d+)\)$", line)
            return locs.get(at.group(1), "") if at else ""

        lines = text.splitlines()
        calls = sorted(
            re.search(r"hvdt\.moe\.dispatch\.(\w+)", where(line)).group(1)
            for line in lines if "call @moe_sum_rows" in line)
        kernels = [where(line) for line in lines
                   if "@tpu_custom_call" in line
                   and "hvdt.kernel.moe_sum_rows" in where(line)]
        gathered = [re.search(r": \((tensor<[^>]*>)", line).group(1)
                    for line in lines if "stablehlo.gather" in line
                    and "tensor<" in line and "x128xf32>" in line]
        return calls, kernels, gathered

    calls, kernels, gathered = lowered(T)
    assert calls == ["rows", "tokens"] and 1 <= len(kernels) <= 2
    assert gathered and set(gathered) == {f"tensor<{T}x{D}xf32>"}
    calls, kernels, gathered = lowered(T - 24)
    assert not calls and not kernels
    assert f"tensor<{(T - 24) * 2}x{D}xf32>" in gathered
