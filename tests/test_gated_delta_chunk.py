"""The chunk-local passes of the Gated DeltaNet scan under their own
differentiation rule (``ops/gated_delta._chunk_passes``): outputs and all
five input gradients against ``jax.vjp`` of the passes written op by op
(the body ``gated_delta_rule`` had before the rule, kept here as the
reference) and against the delta rule run token by token; the Mosaic
kernels in interpret mode against XLA's schedule of the same rule; which
schedule is chosen; equal neighbouring keys and fast decays; the carries'
dtype.  CPU only."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.ops import gated_delta as gd  # noqa: E402
from test_gated_delta import operands, token_by_token  # noqa: E402
from test_gated_delta_inverse import choose, jit  # noqa: E402,F401

B, HK, HV, DK, DV = 2, 2, 4, 16, 8
ARGS = "q k v g beta".split()


def op_by_op(q, k, v, g, beta, *, chunk=gd.CHUNK, carry_dtype=jnp.float32):
    """``gated_delta_rule`` as it was before the chunk-local passes had a
    rule of their own: every pass a jnp operation, differentiated by JAX
    one by one (the inverse by its own two products)."""
    b, l, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    dt = v.dtype
    f32 = jnp.float32
    pad = (-l) % chunk
    n = (l + pad) // chunk

    def chunks(x, *tail):
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((b, n, chunk) + tail)

    def pairs(x, y):
        return jnp.einsum("bnihd,bnjhd->bnhij", x, y,
                          preferred_element_type=f32)

    def rows(m, x):
        return jnp.einsum("bnhrij,bnjhrd->bnhrid", m, x,
                          preferred_element_type=f32)

    def step(s, xs):
        w_n, u_n, k_n, g_n = xs
        u = (u_n - jnp.einsum("bhrid,bhrde->bhrie", w_n, s.astype(dt),
                              preferred_element_type=f32)).astype(dt)
        s_next = (g_n[..., None, None] * s.astype(f32)
                  + jnp.einsum("bihrd,bhrie->bhrde", k_n, u,
                               preferred_element_type=f32))
        return s_next.astype(carry_dtype), (s.astype(dt), u)

    qn = chunks((gd._l2norm(q) * dk ** -0.5).astype(dt), hk, dk)
    kn = chunks(gd._l2norm(k).astype(dt), hk, dk)
    v = chunks(v, hk, r, dv)
    beta = chunks(beta.astype(f32), hk, r)
    gc = jnp.cumsum(chunks(g.astype(f32), hk, r).astype(carry_dtype),
                    axis=2).astype(f32)
    gamma = jnp.exp(gc)
    gc_rows = jnp.moveaxis(gc, 2, -1)
    diff = gc_rows[..., :, None] - gc_rows[..., None, :]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    ratio = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    beta_rows = jnp.moveaxis(beta, 2, -1)[..., None]
    a = jnp.where(jnp.tril(lower, -1),
                  beta_rows * ratio * pairs(kn, kn)[:, :, :, None], 0.0)
    t = gd._unit_lower_inverse(a).astype(dt)
    attn = (ratio * pairs(qn, kn)[:, :, :, None]).astype(dt)
    kv = kn[:, :, :, :, None]
    u_own = rows(t, (beta[..., None] * v).astype(dt))
    w = rows(t, ((beta * gamma)[..., None] * kv).astype(dt)).astype(dt)
    k_out = (jnp.exp(gc[:, :, -1:] - gc)[..., None] * kv).astype(dt)
    gamma_end = gamma[:, :, -1]
    first = lambda x: jnp.moveaxis(x, 1, 0)             # noqa: E731
    xs = (first(w), first(u_own), first(k_out), first(gamma_end))
    s0 = jnp.zeros((b, hk, r, dk, dv), carry_dtype)
    _, (s_in, u) = lax.scan(step, s0, xs)
    s_in, u = jnp.moveaxis(s_in, 0, 1), jnp.moveaxis(u, 0, 1)
    q_in = (gamma[..., None] * qn[:, :, :, :, None]).astype(dt)
    o = (jnp.einsum("bnihrd,bnhrde->bnihre", q_in, s_in).astype(f32)
         + jnp.einsum("bnhrij,bnhrje->bnihre", attn, u,
                      preferred_element_type=f32))
    return o.reshape(b, n * chunk, hv, dv)[:, :l]


def value_and_grads(fn, args):
    """o and the five gradients of a fixed, uneven reading of it."""
    def read(*a):
        o = fn(*a)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                                   .reshape(o.shape))), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        read, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return o, grads


def assert_close(got, want, rtol, atol, what="", least=0.0):
    """``atol`` is a share of the gradient's largest entry (of ``least``
    where the whole gradient is rounding: g's under a decay that has
    underflowed)."""
    for name, a, b in zip(ARGS, got, want):
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol * max(float(jnp.abs(b).max()), least),
            err_msg=f"{what} d{name}")


@pytest.mark.parametrize("length, chunk", [(192, 64), (100, 64), (96, 16)],
                         ids=["three_chunks", "ragged_tail", "chunks_of_16"])
def test_the_rule_is_jax_vjp_of_the_passes_one_by_one(length, chunk):
    """In float32, at the tolerances of tests/test_gated_delta.py: the
    hand-written rule is the transpose JAX derives, pass by pass."""
    args = operands(length, seed=length)
    o, grads = value_and_grads(
        functools.partial(gd.gated_delta_rule, chunk=chunk), args)
    o_ref, grads_ref = value_and_grads(
        functools.partial(op_by_op, chunk=chunk), args)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-6)
    assert_close(grads, grads_ref, 1e-4, 2e-5, "against jax.vjp")


def test_the_rule_is_the_recurrences_gradient():
    args = operands(128, seed=11)
    o, grads = value_and_grads(gd.gated_delta_rule, args)
    o_ref, grads_ref = value_and_grads(token_by_token, args)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-6)
    assert_close(grads, grads_ref, 1e-4, 2e-5, "token by token")


def chunk_io(args, chunk=gd.CHUNK):
    """The operands of ``_chunk_passes`` from the rule's, whole chunks."""
    q, k, v, g, beta = args
    b, l = q.shape[:2]
    dims = (q.shape[2], v.shape[2], q.shape[3], v.shape[3])
    qkv = jnp.concatenate([x.reshape(b, l, -1) for x in (q, k, v)], -1)
    n = l // chunk
    gc = gd._log_decay(g, n, chunk, dims, jnp.float32)
    beta = beta.reshape(b, n, chunk, dims[0], dims[1] // dims[0])
    return (qkv, gc, beta), dims


def test_every_output_and_cotangent_of_the_passes():
    """``_chunk_passes`` itself, output by output: each of the five with
    a cotangent of its own against JAX's transpose of XLA's schedule of
    the forward (the same operations, undifferentiated by hand)."""
    (qkv, gc, beta), dims = chunk_io(operands(128, seed=13))
    plain = lambda *a: gd._chunk_fwd_jax(*a, dims, gd.CHUNK)[0]  # noqa: E731
    out, vjp = jax.vjp(plain, qkv, gc, beta)
    got, rule = jax.vjp(lambda *a: gd._chunk_passes(*a, dims, gd.CHUNK),
                        qkv, gc, beta)
    names = "qn w u_own k_out attn".split()
    for i, name in enumerate(names):
        np.testing.assert_allclose(got[i], out[i], rtol=1e-6, atol=1e-6,
                                   err_msg=name)
        ct = [jnp.zeros_like(x) for x in out]
        ct[i] = jnp.sin(jnp.arange(out[i].size, dtype=jnp.float32)
                        ).reshape(out[i].shape)
        for a, b, arg in zip(rule(tuple(ct)), vjp(tuple(ct)),
                             ("qkv", "gc", "beta")):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=2e-5 * max(float(jnp.abs(b).max()),
                                                 1e-3),
                err_msg=f"{name} -> d{arg}")


def test_equal_neighbouring_keys_and_fast_decays():
    """Equal unit keys with beta 1 (A is the strict lower triangle of ones,
    where a Neumann series fails) and g of -20 a token (gamma underflows
    inside the chunk): the rule's gradients are the recurrence's, and
    nothing is inf or nan."""
    q, k, v, g, beta = operands(128, seed=17)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    for g_case, beta_case in ((g * 0.0, jnp.ones_like(beta)),
                              (jnp.full_like(g, -20.0), beta)):
        args = (q, k, v, g_case, beta_case)
        o, grads = value_and_grads(gd.gated_delta_rule, args)
        assert all(bool(jnp.isfinite(x).all()) for x in grads)
        o_ref, grads_ref = value_and_grads(token_by_token, args)
        np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4)
        assert_close(grads, grads_ref, 1e-3, 1e-4, "edge", least=0.1)


def test_the_carries_dtype_still_changes_the_result():
    """The benchmark's control: ``carry_dtype=bfloat16`` rounds the
    cumulative log-decay and the state, value and gradients."""
    args = operands(256, seed=7)
    o, grads = value_and_grads(gd.gated_delta_rule, args)
    o16, grads16 = value_and_grads(functools.partial(
        gd.gated_delta_rule, carry_dtype=jnp.bfloat16), args)
    assert float(jnp.abs(o16 - o).max()) > 1e-3 * float(jnp.abs(o).max())
    assert all(bool(jnp.isfinite(x).all()) for x in grads16)
    assert float(jnp.abs(grads16[3] - grads[3]).max()) > 1e-3 * float(
        jnp.abs(grads[3]).max())


def test_bfloat16_operands_differentiate():
    """bf16 rows: the rule's products take bf16 operands and sum in
    float32; its gradients stay near the float32 ones."""
    args = operands(128, seed=19)
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    q, k, v, g, beta = args
    _, want = value_and_grads(gd.gated_delta_rule, args)
    _, got = value_and_grads(gd.gated_delta_rule,
                             (bf(q), bf(k), bf(v), g, beta))
    for name, a, b in zip(ARGS, got, want):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16)
        err = float(jnp.abs(a.astype(jnp.float32) - b).max())
        assert err < 0.06 * float(jnp.abs(b).max()), name


# ---------------------------------------------------------------------------
# The Mosaic schedule of the same rule, in the interpreter.
# ---------------------------------------------------------------------------

TILED = dict(b=1, hk=2, hv=4, d=128)    # heads of whole lane tiles


def tiled_operands(length, seed=0, dtype=jnp.float32, b=1):
    hk, hv, d = TILED["hk"], TILED["hv"], TILED["d"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, length, hk, d), dtype),
            jax.random.normal(ks[1], (b, length, hk, d), dtype),
            jax.random.normal(ks[2], (b, length, hv, d), dtype),
            -jnp.exp(jax.random.normal(ks[3], (b, length, hv))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, hv))))


def runs_kernels(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_the_kernels_are_the_rule(choose):
    """Chunks of 64, 2 key and 4 value heads of 128, 4 chunks: the three
    Mosaic calls and the solve's between them against XLA's schedule of
    the same rule, every output and the three cotangents."""
    (qkv, gc, beta), dims = chunk_io(tiled_operands(256, seed=23))
    assert gd.pallas_kernels.gdn_chunk_tiles(qkv.shape[1], dims, gd.CHUNK)
    out, t = jax.jit(lambda *a: gd._chunk_fwd_jax(*a, dims, gd.CHUNK))(
        qkv, gc, beta)
    cts = tuple(jnp.sin(jnp.arange(x.size, dtype=jnp.float32)
                        ).reshape(x.shape) for x in out)
    grads = jax.jit(lambda *a: gd._chunk_bwd_jax(*a, dims, gd.CHUNK))(
        qkv, gc, beta, t, cts)
    choose(True)
    passes = lambda *a: gd._chunk_passes(*a, dims, gd.CHUNK)  # noqa: E731
    assert runs_kernels(passes, qkv, gc, beta)
    got, rule = jax.vjp(jit(passes), qkv, gc, beta)
    for name, a, b in zip("qn w u_own k_out attn".split(), got, out):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    for name, a, b in zip(("qkv", "gc", "beta"), rule(cts), grads):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=f"d{name}")


@pytest.mark.parametrize("length", [128, 100], ids=["whole", "ragged_tail"])
def test_the_rule_through_the_kernels_is_the_recurrence(choose, length):
    """The whole rule with the kernels chosen, a ragged length padded in
    front of them: value and gradients of the token-by-token rule."""
    args = tiled_operands(length, seed=29)
    choose(True)
    assert runs_kernels(gd.gated_delta_rule, *args)
    o, grads = value_and_grads(gd.gated_delta_rule, args)
    o_ref, grads_ref = value_and_grads(token_by_token, args)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-6)
    assert_close(grads, grads_ref, 1e-4, 2e-5, "kernels")


def test_the_kernels_on_equal_keys_and_fast_decays(choose):
    q, k, v, g, beta = tiled_operands(128, seed=31)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    choose(True)
    for g_case, beta_case in ((g * 0.0, jnp.ones_like(beta)),
                              (jnp.full_like(g, -20.0), beta)):
        args = (q, k, v, g_case, beta_case)
        o, grads = value_and_grads(gd.gated_delta_rule, args)
        assert all(bool(jnp.isfinite(x).all()) for x in grads)
        o_ref, grads_ref = value_and_grads(token_by_token, args)
        np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4)
        assert_close(grads, grads_ref, 1e-3, 1e-4, "edge", least=0.1)


def test_the_kernels_inside_a_shard_map(devices, choose):
    """The benchmark's step is a shard_map over dp with every axis manual:
    the kernels take operands that vary over it, forward and backward."""
    mesh = Mesh(np.asarray(devices[:2]), ("dp",))
    args = tiled_operands(64, seed=37, b=2)
    want = value_and_grads(gd.gated_delta_rule, args)
    choose(True)
    sharded = jax.shard_map(gd.gated_delta_rule, mesh=mesh,
                            in_specs=(P("dp"),) * 5, out_specs=P("dp"))
    assert runs_kernels(sharded, *args)
    o, grads = value_and_grads(sharded, args)
    np.testing.assert_allclose(o, want[0], rtol=2e-5, atol=2e-6)
    assert_close(grads, want[1], 1e-4, 2e-5, "shard_map")


@pytest.mark.parametrize("on_tpu, length, chunk, tiled, calls", [
    (False, 128, 64, True, (0, 0)),     # off the TPU
    (True, 128, 64, True, (3, 4)),      # before, the solve, after; backward
    (True, 100, 64, True, (3, 4)),      # a ragged tail is padded first
    (True, 128, 16, True, (1, 1)),      # a chunk the kernels do not tile:
    (True, 128, 64, False, (1, 1)),     # heads of 16 and 8: the solve alone
], ids=["cpu", "tpu", "ragged", "chunk_16", "narrow_heads"])
def test_the_schedule_is_read_from_platform_and_shapes(choose, on_tpu,
                                                       length, chunk, tiled,
                                                       calls):
    """Mosaic calls in the rule's jaxpr, undifferentiated and under
    ``jax.grad`` (whose forward is the rule's own: the solve is not run
    again for the backward)."""
    args = tiled_operands(length) if tiled else operands(length)
    choose(on_tpu)
    rule = functools.partial(gd.gated_delta_rule, chunk=chunk)
    grad = jax.grad(lambda *a: rule(*a).sum(), argnums=(0, 1, 2, 3, 4))
    assert tuple(str(jax.make_jaxpr(fn)(*args)).count("pallas_call")
                 for fn in (rule, grad)) == calls


def test_the_chooser_answers_xla_here():
    """Unforced, on the CPU: no test of the model pays the interpreter."""
    (qkv, _, _), dims = chunk_io(tiled_operands(64))
    assert not gd._chunk_on_kernels(qkv, dims, gd.CHUNK)
