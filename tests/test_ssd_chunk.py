"""The chunk passes of the Mamba-2 scan under their own differentiation
rules (``ops/ssd._chunk_state`` / ``_chunk_out``): the hand-written rules
on XLA's schedule against ``jax.grad`` of the recurrence a token; the
Mosaic kernels in interpret mode against XLA's schedule of the same rules,
forward and every cotangent; a state that lives across chunks through the
kernels; which schedule is chosen.  CPU only."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu import models  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402
from horovod_tpu.ops import ssd  # noqa: E402
from test_gated_delta_inverse import choose, jit  # noqa: E402,F401
from test_ssd import operands, recurrence  # noqa: E402

ARGS = "x delta a b c".split()


def value_and_grads(fn, args):
    """y and the five gradients of a fixed, uneven reading of it."""
    def read(*a):
        y = fn(*a)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=jnp.float32)
                                   .reshape(y.shape))), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        read, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return y, grads


def assert_close(got, want, rtol, atol, what=""):
    """``atol`` is a share of the gradient's largest entry."""
    for name, a, b in zip(ARGS, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol * float(np.abs(b).max()),
            err_msg=f"{what} d{name}")


# chunk, length, groups, as tests/test_ssd.py: whole chunks, two groups, a
# ragged tail; and a chunk that is the whole sequence.
@pytest.mark.parametrize("chunk,length,groups", [
    (64, 256, 1), (64, 192, 2), (64, 200, 1), (128, 300, 2), (32, 32, 1)])
def test_the_rules_are_the_recurrences_gradient(chunk, length, groups):
    """In float32, tight: the hand-written rules on XLA's schedule (no
    pallas_call in the jaxpr) give the value and the five gradients of the
    scan run a token at a time."""
    args = operands(length, groups=groups, seed=length)
    scan = functools.partial(ssd.ssd_scan, chunk=chunk)
    assert "pallas_call" not in str(jax.make_jaxpr(scan)(*args))
    y, grads = value_and_grads(scan, args)
    y_ref, grads_ref = value_and_grads(recurrence, args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    assert_close(grads, grads_ref, 2e-4, 2e-5, "token by token")


def rule_io(args, chunk, seed=0):
    """The operands of the two rules from the scan's, whole chunks: the
    rows, delta, the running log-decay, entering states and ``D`` a
    lane."""
    x, delta, a, b, c = args
    bsz, length, h, p = x.shape
    dims = (h, p) + b.shape[2:]
    xbc = jnp.concatenate([t.reshape(bsz, length, -1) for t in (x, b, c)],
                          -1)
    l = jnp.cumsum((delta * a).reshape(bsz, -1, chunk, h), 2).reshape(
        delta.shape)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    s_in = jax.random.normal(
        ks[0], (length // chunk, bsz, h * p, dims[3])).astype(x.dtype)
    return (xbc, delta, l, s_in, jax.random.normal(ks[1], (1, h * p))), dims


def test_every_cotangent_of_the_rules_on_xlas_schedule():
    """``_chunk_state`` and ``_chunk_out`` themselves against JAX's
    transpose of XLA's schedule of their forwards (the same operations,
    undifferentiated by hand): the cotangents of the rows, delta, the
    running log-decay, the entering states and ``D``."""
    chunk = 64
    (xbc, delta, l, s_in, skip), dims = rule_io(
        operands(192, groups=2, seed=5), chunk)
    wave = lambda t: jnp.sin(jnp.arange(t.size, dtype=jnp.float32)  # noqa
                             ).reshape(t.shape)
    for rule, plain, ins in (
            (ssd._chunk_state, ssd._state_fwd_jax, (xbc, delta, l)),
            (ssd._chunk_out, ssd._out_fwd_jax,
             (xbc, delta, l, s_in, skip))):
        def both(*a):
            want, vjp = jax.vjp(lambda *a: plain(*a, dims, chunk), *a)
            got, by_hand = jax.vjp(
                lambda *a: rule(*a, dims, chunk, False), *a)
            return got, want, by_hand(wave(want)), vjp(wave(want))

        got, want, ours, jaxs = jax.jit(both)(*ins)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        for name, g, w in zip(("xbc", "delta", "l", "s_in", "skip"), ours,
                              jaxs):
            np.testing.assert_allclose(
                g, w, rtol=1e-4, atol=2e-5 * float(jnp.abs(w).max()),
                err_msg=f"{plain.__name__} -> d{name}")


# ---------------------------------------------------------------------------
# The Mosaic schedule of the same rules, in the interpreter.
# ---------------------------------------------------------------------------

TILED = dict(heads=8, head_dim=64, state=128, chunk=128)


def tiled_operands(length, seed=0, dtype=jnp.float32, batch=1, slow=False,
                   **sizes):
    """Operands at a shape the kernels tile.  ``slow``: time steps as the
    model draws them (``_init_state_space_mixer``: log-uniform in [1e-3,
    1e-1] behind the softplus), so that a head's state outlives a
    chunk."""
    sizes = dict(TILED, **sizes)
    h, p, s = sizes["heads"], sizes["head_dim"], sizes["state"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.random.normal(ks[1], (batch, length, h))
    if slow:
        m = models.StateSpaceMixer(heads=h, head_dim=p, state=s,
                                   chunk=sizes["chunk"])
        cfg = models.TransformerConfig(d_model=16, layers=1, period=(
            models.LayerKind(heads=0, kv_heads=0, d_ff=16, ssm=m),))
        bias = transformer._init_state_space_mixer(
            iter(jax.random.split(ks[5], 8)), cfg, m)["dt_bias"]
        dt = 0.1 * dt + bias.astype(jnp.float32)
    return (jax.random.normal(ks[0], (batch, length, h, p)).astype(dtype),
            jax.nn.softplus(dt),
            -jnp.exp(jax.random.normal(ks[2], (h,)) - 1.0),
            jax.random.normal(ks[3], (batch, length, 1, s)).astype(dtype),
            jax.random.normal(ks[4], (batch, length, 1, s)).astype(dtype))


def runs_kernels(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim", [64, 128], ids=["two_heads_a_tile",
                                                     "a_head_a_tile"])
def test_the_kernels_are_the_rules(dtype, tol, head_dim):
    """Two chunks of 128, 8 heads of 64 (two a lane tile) or of 128 over a
    state of 128: the four Mosaic calls against XLA's schedule of the same
    rules, each output and every cotangent (the rows' with x, B and C in
    it, delta, the running log-decay whose transpose reaches ``a``, the
    entering states, ``D``), within ``tol`` of its largest entry."""
    chunk = TILED["chunk"]
    (xbc, delta, l, s_in, skip), dims = rule_io(
        tiled_operands(256, seed=23, dtype=dtype, head_dim=head_dim), chunk)
    assert pk.ssd_chunk_tiles(xbc.shape[1], dims, chunk)

    def close(name, got, want):
        for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            assert g.shape == w.shape and g.dtype == w.dtype, (name, i)
            g, w = (np.asarray(t, np.float32) for t in (g, w))
            np.testing.assert_allclose(
                g, w, rtol=tol, atol=tol * float(np.abs(w).max()),
                err_msg=f"{name}[{i}]")

    def both(kernel, plain, *ins):
        got = jax.jit(lambda *a: kernel(*a, dims, chunk))(*ins)
        close(kernel.__name__, got,
              jax.jit(lambda *a: plain(*a, dims, chunk))(*ins))
        return got

    own = both(pk.ssd_chunk_state, ssd._state_fwd_jax, xbc, delta, l)
    d_own = jax.random.normal(jax.random.PRNGKey(1), own.shape)
    both(pk.ssd_chunk_state_bwd, ssd._state_bwd_jax, xbc, delta, l, d_own)
    y = both(pk.ssd_chunk_out, ssd._out_fwd_jax, xbc, delta, l, s_in, skip)
    assert y.dtype == jnp.float32 and y.shape == xbc.shape[:2] + (
        dims[0] * dims[1],)
    d_y = jax.random.normal(jax.random.PRNGKey(2), y.shape)
    both(pk.ssd_chunk_out_bwd, ssd._out_bwd_jax, xbc, delta, l, s_in, skip,
         d_y)


@pytest.mark.parametrize("schedule", ["xla", "mosaic"])
def test_a_chunks_shares_of_the_decays_cotangent_cancel(schedule):
    """The pairs hold ratios ``exp(l_i - l_j)`` alone, so what reaches l
    through them sums to nothing over a chunk: in bfloat16 too, to
    float32's rounding and not to bfloat16's (with no entering state and
    no ``D``; a form that summed a row's terms on one rounding of the
    operands and a column's on another read a hundredth of the sum of
    magnitudes here, and cosines of 0.54 on the chip)."""
    chunk = TILED["chunk"]
    (xbc, delta, l, s_in, skip), dims = rule_io(
        tiled_operands(256, seed=3, dtype=jnp.bfloat16, slow=True), chunk)
    d_y = jax.random.normal(jax.random.PRNGKey(2),
                            xbc.shape[:2] + (dims[0] * dims[1],))
    bwd = ssd._out_bwd_jax if schedule == "xla" else pk.ssd_chunk_out_bwd
    d_l = jax.jit(lambda *a: bwd(*a, dims, chunk))(
        xbc, delta, l, jnp.zeros_like(s_in), jnp.zeros_like(skip), d_y)[2]
    d_l = d_l.reshape(1, -1, chunk, dims[0])
    assert float(jnp.abs(d_l.sum(2)).max()) < 1e-4 * float(
        jnp.abs(d_l).sum(2).min())


def test_the_scan_through_the_kernels_is_the_recurrence(choose):
    """The whole scan with the kernels chosen, three chunks: value and the
    five gradients (``a``'s through the cumulative sum's transpose) of
    the scan run a token at a time."""
    args = tiled_operands(384, seed=29)
    choose(True)
    scan = functools.partial(ssd.ssd_scan, chunk=TILED["chunk"])
    assert runs_kernels(scan, *args)
    y, grads = value_and_grads(scan, args)
    y_ref, grads_ref = value_and_grads(recurrence, args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(y_ref).max()))
    assert_close(grads, grads_ref, 2e-4, 2e-5, "kernels")


def test_a_state_lives_across_chunks_through_the_kernels(choose):
    """Time steps drawn as the model draws them: what the first chunk
    wrote is read in the third at the recurrence's value, and the
    gradients of the first chunk's x and B through the third chunk's y
    are the recurrence's."""
    chunk = TILED["chunk"]
    x, delta, a, b, c = tiled_operands(3 * chunk, seed=31, slow=True)
    choose(True)

    def late(fn):                       # the third chunk's y alone
        return lambda x, b: fn(x, delta, a, b, c)[:, 2 * chunk:].sum()

    scan = functools.partial(ssd.ssd_scan, chunk=chunk)
    assert runs_kernels(scan, x, delta, a, b, c)
    got = jax.jit(jax.grad(late(scan), argnums=(0, 1)))(x, b)
    want = jax.jit(jax.grad(late(recurrence), argnums=(0, 1)))(x, b)
    for g, w in zip(got, want):
        early = np.abs(np.asarray(w[:, :chunk]))
        assert early.max() > 0.1 * np.abs(np.asarray(w)).max()
        np.testing.assert_allclose(g[:, :chunk], w[:, :chunk], rtol=2e-4,
                                   atol=2e-5 * float(early.max()))


def mixer_leaves(seed=0, d=32, groups=1, **sizes):
    sizes = dict(TILED, **sizes)
    h, p, s = sizes["heads"], sizes["head_dim"], sizes["state"]
    inner, bc = h * p, groups * s
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 10))
    return {"w_in": jax.random.normal(next(ks), (d, 2 * inner + 2 * bc + h))
            * d ** -0.5,
            "conv": jax.random.uniform(next(ks), (4, inner + 2 * bc),
                                       minval=-0.5, maxval=0.5),
            "conv_bias": jax.random.uniform(next(ks), (inner + 2 * bc,),
                                            minval=-0.5, maxval=0.5),
            "a_log": jnp.log(jnp.arange(1.0, h + 1)),
            "d_skip": jax.random.normal(next(ks), (h,)),
            "dt_bias": jax.random.normal(next(ks), (h,)) - 2.0,
            "ssd_norm": jax.random.normal(next(ks), (inner,)),
            "w_out": jax.random.normal(next(ks), (inner, d)) * inner ** -0.5}


def mixer(x, p, groups=1, **sizes):
    sizes = dict(TILED, **sizes)
    return ssd.mamba2_mixer(
        x, p, heads=sizes["heads"], head_dim=sizes["head_dim"],
        state=sizes["state"], groups=groups, chunk=sizes["chunk"], eps=1e-5,
        proj=lambda a, w: a @ w)


def test_the_mixer_on_the_kernels_is_the_mixer_on_xla(choose):
    """``mamba2_mixer`` with Mosaic's schedule (x, B, C on the rows'
    column blocks, ``D x`` inside the call, y token-major) against XLA's:
    value and the gradients of every leaf, ``d_skip``, ``a_log`` and
    ``dt_bias`` among them."""
    p = mixer_leaves(seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 32))
    read = lambda x, p: (mixer(x, p) ** 2).sum()         # noqa: E731
    want = jax.jit(jax.value_and_grad(read, argnums=(0, 1)))(x, p)
    choose(True)
    assert runs_kernels(mixer, x, p)
    got = jit(jax.value_and_grad(read, argnums=(0, 1)))(x, p)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            g, w, rtol=1e-3, atol=1e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_kernels_inside_a_shard_map(devices, choose):
    """The benchmark's step is a shard_map over dp with every axis manual:
    the kernels take rows that vary over it beside a parameter that does
    not, forward and backward."""
    mesh = Mesh(np.asarray(devices[:2]), ("dp",))
    args = tiled_operands(128, seed=37, batch=2)
    want = value_and_grads(
        functools.partial(ssd.ssd_scan, chunk=TILED["chunk"]), args)
    choose(True)
    specs = (P("dp"), P("dp"), P(), P("dp"), P("dp"))
    sharded = jax.shard_map(
        functools.partial(ssd.ssd_scan, chunk=TILED["chunk"]), mesh=mesh,
        in_specs=specs, out_specs=P("dp"))
    assert runs_kernels(sharded, *args)
    y, grads = value_and_grads(sharded, args)
    np.testing.assert_allclose(y, want[0], rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want[0]).max()))
    assert_close(grads, want[1], 2e-4, 2e-5, "shard_map")


@pytest.mark.parametrize("on_tpu, length, sizes, calls", [
    (False, 256, {}, (0, 0)),                   # off the TPU
    (True, 256, {}, (2, 4)),                    # state, out; both backwards
    (True, 200, {}, (0, 0)),                    # a padded tail
    (True, 256, dict(groups=2), (0, 0)),        # two groups
    (True, 256, dict(chunk=64), (0, 0)),        # a chunk of half a lane tile
    (True, 256, dict(heads=4), (0, 0)),         # half a block of heads
    (True, 256, dict(state=64), (0, 0)),        # a state of half a lane tile
], ids=["cpu", "tpu", "padded_tail", "two_groups", "chunk_64", "four_heads",
        "state_64"])
def test_the_schedule_is_read_from_platform_and_shapes(choose, on_tpu,
                                                       length, sizes, calls):
    """Mosaic calls in the mixer's jaxpr, undifferentiated and under
    ``jax.grad`` (the rules' forwards again, then their backwards); XLA's
    form wherever the platform or a shape says so, with no knob."""
    groups = sizes.pop("groups", 1)
    p = mixer_leaves(groups=groups, **sizes)
    x = jnp.ones((1, length, 32))
    choose(on_tpu)
    run = functools.partial(mixer, groups=groups, **sizes)
    grad = jax.grad(lambda x, p: run(x, p).sum(), argnums=(0, 1))
    assert tuple(str(jax.make_jaxpr(fn)(x, p)).count("pallas_call")
                 for fn in (run, grad)) == calls


def test_the_chooser_answers_xla_here():
    """Unforced, on the CPU: no test of the model pays the interpreter."""
    dims = (TILED["heads"], TILED["head_dim"], 1, TILED["state"])
    assert pk.ssd_chunk_tiles(256, dims, TILED["chunk"])
    assert not ssd._scan_on_kernels(256, dims, TILED["chunk"])
    # granite-4.0-h-micro's own sizes tile
    assert pk.ssd_chunk_tiles(8192, (64, 64, 1, 128), 256)


def test_no_pairs_leave_the_kernels(monkeypatch):
    """The mixer's gradient at the cell's heads, state and chunk (64 x 64,
    128, 256; four chunks), lowered for the TPU (the Pallas -> Mosaic
    lowering is Python and needs no chip): four Mosaic call sites (the two
    rules' forwards and backwards, each a jitted entry that the forward
    and ``jax.checkpoint``'s recompute share), x, B and C go in as the
    convolution's own bf16 rows, y comes out once as float32 token-major
    rows, and no [256, 256] array exists anywhere in the program: the
    pairs live and die inside the calls."""
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    jax.clear_caches()
    sizes = dict(heads=64, head_dim=64, state=128, chunk=256)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                     mixer_leaves(d=256, **sizes))
    x = jnp.ones((1, 1024, 256), jnp.bfloat16)
    run = jax.checkpoint(functools.partial(mixer, **sizes))
    try:
        text = jax.jit(jax.grad(
            lambda x, p: run(x, p).astype(jnp.float32).sum(),
            argnums=(0, 1))).trace(x, p).lower(
                lowering_platforms=("tpu",)).as_text()
    finally:
        jax.clear_caches()
    calls = [line.rsplit(" : ", 1)[1] for line in text.splitlines()
             if "@tpu_custom_call" in line]
    rows, y = "tensor<1x1024x4352xbf16>", "tensor<1x1024x4096xf32>"
    outs = [c.split(" -> ")[1] for c in calls if c.startswith(f"({rows}")]
    assert len(calls) == len(outs) == 4
    assert sum(o.startswith(y) for o in outs) == 1
    assert "256x256x" not in text and "x256x256>" not in text
