"""ops/attention.py: which code runs behind ``attention(q, k, v)``, and
that either of them is attention.  CPU; the kernel in interpret mode."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu.ops.attention as att
import horovod_tpu.ops.pallas_kernels as pk

KERNEL, XLA = True, False

# (mode, platform, batch, heads, seq) -> kernel or XLA; batch and heads
# are the LOCAL sizes.  `auto` on a TPU is a sequence length
# (att._CROSSOVER_SEQ, 512: PERF.md section 6, PR 32).
POLICY = [
    # the LM cells of BENCHMARK.json, per chip
    ("auto", "tpu", 128, 16, 512, KERNEL),    # lm24x1024_s512_b128
    ("auto", "tpu", 8, 16, 4096, KERNEL),     # lm24x1024_s4096_b8
    ("auto", "tpu", 32, 16, 512, KERNEL),     # lm24x1024_s512_dp4
    ("auto", "tpu", 2, 48, 8192, KERNEL),     # laguna_xs2_s8192
    ("auto", "cpu", 8, 16, 4096, XLA),        # auto never off the TPU
    ("auto", "cpu", 128, 16, 512, XLA),
    # the edge is a length, whatever the batch (384 tiles; 512 x 16 x
    # 384 x 384 f32 scores are 4.5 GiB)
    ("auto", "tpu", 1, 16, 512, KERNEL),      # inside
    ("auto", "tpu", 512, 16, 384, XLA),       # outside
    ("auto", "tpu", 256, 16, 256, XLA),       # under the crossover
    ("on", "cpu", 2, 4, 128, KERNEL),         # forced wherever shapes tile
    ("on", "tpu", 128, 16, 512, KERNEL),
    ("on", "tpu", 2, 4, 130, XLA),            # 130 % 128 != 0
    ("on", "tpu", 2, 4, 4, XLA),              # under 8 rows
    ("off", "tpu", 8, 16, 4096, XLA),         # the master switch
]


@pytest.mark.parametrize(
    "mode, platform, batch, heads, seq, expected", POLICY,
    ids=[f"{m}-{p}-b{b}h{h}L{l}" for m, p, b, h, l, _ in POLICY])
def test_policy_picks_from_mode_platform_and_local_shape(
        monkeypatch, mode, platform, batch, heads, seq, expected):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", mode)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(platform=platform)])
    assert att.kernel_enabled(seq, batch=batch, heads=heads) is expected
    # No mesh: the plan is the policy's answer and nothing else.
    assert att.kernel_plan(batch, seq, heads, heads) == (
        "direct" if expected else None)


def _inputs(kv_heads, dtype, b=2, l=128, h=4, d=32):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, l, h, d), dtype)
    k = jax.random.normal(ks[1], (b, l, kv_heads, d), dtype)
    v = jax.random.normal(ks[2], (b, l, kv_heads, d), dtype)
    do = jax.random.normal(ks[3], (b, l, h, d), dtype)
    return q, k, v, do


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("mode", ["off", "on"], ids=["xla", "kernel"])
def test_attention_matches_reference_on_either_path(
        monkeypatch, mode, group, dtype):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", mode)
    kernel_calls = []
    flash = pk.flash_attention
    monkeypatch.setattr(
        pk, "flash_attention",
        lambda *a, **kw: kernel_calls.append(1) or flash(*a, **kw))
    q, k, v, do = _inputs(4 // group, dtype)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out.astype(jnp.float32)
                    * do.astype(jnp.float32)).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    got = run(att.attention)
    want = run(pk.attention_reference)
    assert bool(kernel_calls) == (mode == "on")
    # f32: the order of the reductions; bf16: the probabilities and the
    # operands of the backward's products are rounded to 8 bits.
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol, err_msg=f"{name} [{mode}]")
