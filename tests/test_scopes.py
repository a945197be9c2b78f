"""The names the program puts on its own step (PR 24): every ``hvdt.*``
scope is in the lowered text of the path that should carry it, and every
``pallas_call`` site lowers under its ``hvdt.kernel.<kernel>`` name.  The
benchmark's phase split (``benchmark/phase_split.py``) and an operator's
XProf trace (docs/observability.md) read these names; a refactor that
drops one fails here, on the CPU.  Names are metadata: nothing here runs
a kernel for its result."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import models


def lowered_text(fn, *args) -> str:
    """The lowered module with the name stack of every operation (inside
    a called function the stack is relative to the call)."""
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def compiled_text(fn, *args) -> str:
    """The compiled module: every ``op_name`` is the whole path, as the
    benchmark's ``Context.hlo_text`` has it."""
    return jax.jit(fn).lower(*args).compile().as_text()


def lm_config(loss_chunk):
    return models.TransformerConfig(
        vocab=256, d_model=64, layers=2, heads=4, kv_heads=4, d_ff=128,
        max_seq=32, remat=True, loss_chunk=loss_chunk)


@pytest.fixture(scope="module", params=[128, 0],
                ids=["chunked_loss", "dense_loss"])
def lm_grad_text(request):
    cfg = lm_config(request.param)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return compiled_text(jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg)), params, tokens)


# What JAX itself wraps around a scope under value_and_grad of a scanned,
# checkpointed block: these strings are what phase_split.phase() matches.
@pytest.mark.parametrize("path", [
    "jvp()/while/body/closed_call/hvdt.attention/",
    "jvp()/while/body/closed_call/hvdt.mlp/",
    "transpose(jvp())/while/body/closed_call/checkpoint/hvdt.attention/",
    "transpose(jvp())/while/body/closed_call/checkpoint/hvdt.mlp/",
    "checkpoint/rematted_computation/hvdt.attention/",
    "checkpoint/rematted_computation/hvdt.mlp/",
    "jvp(hvdt.loss)/",
    "transpose(jvp(hvdt.loss))/",
])
def test_lm_loss_and_grad_carry_the_model_scopes(lm_grad_text, path):
    assert path in lm_grad_text


def test_the_tied_heads_matmul_is_inside_the_loss_scope(lm_grad_text):
    # Outside the block scan the only matmul of the forward is the head's.
    outside = [line for line in lm_grad_text.splitlines()
               if "dot_general" in line and "jvp(" in line
               and "hvdt.attention" not in line and "hvdt.mlp" not in line
               and "transpose(" not in line]
    assert outside and all("jvp(hvdt.loss)" in line for line in outside)


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.asarray(jax.devices()[:4], dtype=object), ("dp",))


def update_text(hvd, mesh4, **kwargs):
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3), **kwargs)
    params = {"w": jnp.ones((64, 128)), "b": jnp.ones((128,))}
    state = opt.init(params)

    def local(params, state, grads):
        grads = hvd.optimizer.pvary_tree(grads, "dp")
        return opt.update(grads, state, params)

    step = jax.shard_map(local, mesh=mesh4, in_specs=(P(), P(), P()),
                         out_specs=(P(), P()))
    return compiled_text(step, params, state, params)


@pytest.mark.parametrize("passes", [1, 2], ids=["chain", "multisteps"])
@pytest.mark.parametrize("path", [
    "shard_map/hvdt.exchange/",
    "hvdt.exchange/hvdt.fused_allreduce.b0/",
    "hvdt.optimizer/",
])
def test_distributed_optimizer_update_carries_its_scopes(hvd, mesh4, path,
                                                         passes):
    text = update_text(hvd, mesh4, backward_passes_per_step=passes)
    assert path in text
    # The exchange is not under the optimizer's name, nor the other way.
    assert "hvdt.optimizer/hvdt.exchange" not in text
    assert "hvdt.exchange/hvdt.optimizer" not in text


def test_the_optimizer_scope_leaves_init_and_the_state_as_they_were(hvd):
    inner = optax.adamw(1e-3)
    opt = hvd.DistributedOptimizer(inner)
    params = {"w": jnp.ones((8, 128))}
    state = opt.init(params)
    want = (optax.EmptyState(), inner.init(params))
    assert jax.tree.structure(state) == jax.tree.structure(want)
    assert type(state[1]) is type(want[1])
    # Extra arguments still reach a transformation that takes them.
    seen = {}

    def update(updates, state, params=None, *, value):
        seen["value"] = value
        return updates, state

    takes_extra = optax.GradientTransformationExtraArgs(
        lambda p: optax.EmptyState(), update)
    opt = hvd.DistributedOptimizer(takes_extra, axis=())
    opt.update(params, opt.init(params), params, value=3.0)
    assert seen == {"value": 3.0}


# ---------------------------------------------------------------------------
# Every pallas_call site, lowered in interpret mode.
# ---------------------------------------------------------------------------


def _flash(kernel):
    from horovod_tpu.ops import pallas_kernels as pk

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    if kernel == "flash_fwd":                   # the local forward
        return lambda: pk.flash_attention(q, q, q, block_q=128,
                                          block_k=128)
    if kernel == "flash_fwd.ring":              # the ring step's call
        stat = jnp.zeros((1, 2, 128), jnp.float32)
        return lambda: pk.flash_block_update(
            q, q, q, q, stat, stat, q_offset=0, k_offset=0, causal=True,
            scale=0.125, block_q=128, block_k=128)
    if kernel == "flash_bwd":                   # the local backward
        return lambda: jax.grad(lambda q: pk.flash_attention(
            q, q, q, block_q=128, block_k=128).sum())(q)
    if kernel == "flash_win_fwd":               # the same two, windowed
        return lambda: pk.flash_attention(q, q, q, window=32)
    if kernel == "flash_win_bwd":
        return lambda: jax.grad(lambda q: pk.flash_attention(
            q, q, q, window=32).sum())(q)
    if kernel == "flash_bd_fwd":                # and under the block mask
        return lambda: pk.flash_attention(q, q, q, block_diffusion=4)
    if kernel == "flash_bd_bwd":
        return lambda: jax.grad(lambda q: pk.flash_attention(
            q, q, q, block_diffusion=4).sum())(q)
    lse = jnp.zeros((1, 2, 128), jnp.float32)   # the ring step's two
    return lambda: pk.flash_grad_block(q, q, q, q, q, lse,
                                       block_q=128, block_k=128)


def _conv(kernel):
    from horovod_tpu.ops import conv_fused as cf

    a, w = jnp.ones((64, 128), jnp.float32), jnp.ones((128, 128))
    if kernel == "conv1x1_bn":
        return lambda: cf.matmul_bn_relu(a, w, jnp.ones(128),
                                         jnp.zeros(128))
    return lambda: cf.matmul_batch_stats(a, w, block_m=64)


def _optim(kernel):
    from horovod_tpu.ops import optim_kernels as ok

    p = jnp.ones((8, 128), jnp.float32)
    scalars = jnp.ones((3,), jnp.float32)
    if kernel == "fused_adam":
        return lambda: ok.adam_leaf_update(p, p, p, p, scalars)
    return lambda: ok.sgd_leaf_update(p, p, scalars[:1], momentum=0.9,
                                      nesterov=False)


def _quant(kernel):
    from horovod_tpu.quant import kernels as qk

    flat = jnp.linspace(-1.0, 1.0, 32 * 256)
    if kernel == "quantize":
        return lambda: qk.quantize_flat(flat, 256, use_kernels=True)
    if kernel == "dequantize":
        return lambda: qk.dequantize_flat(
            *qk.quantize_flat(flat, 256, use_kernels=False), 256,
            use_kernels=True)
    if kernel == "quantize4":
        return lambda: qk.quantize_flat_int4(flat, 256, use_kernels=True)
    return lambda: qk.dequantize_flat_int4(
        *qk.quantize_flat_int4(flat, 256, use_kernels=False), 256,
        use_kernels=True)


def _eva(kernel):
    """EVA's five: the causal calls on the aligned windows with the
    logsumexp as an output, and the summaries' three."""
    from horovod_tpu.ops import pallas_kernels as pk

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    if kernel.startswith("eva_win"):
        fn = pk.flash_attention_stats
        args = (q, q, q)
    else:
        fn = functools.partial(pk.eva_summary_attention, window=64, per=4)
        args = (q, q[:, :8], q[:, :8])
    if kernel.endswith("fwd"):
        return lambda: fn(*args)
    return lambda: jax.grad(lambda *a: fn(*a)[0].sum(), argnums=(0, 1, 2))(
        *args)


def _gdn(kernel):
    """The solve's call on its slabs, and the three calls of the
    chunk-local passes around it (one chunk of one key head)."""
    from horovod_tpu.ops import pallas_kernels as pk

    if kernel == "gdn_inverse":
        cols = jnp.zeros((16, 16, 128), jnp.float32)
        return lambda: pk.unit_lower_inverse_slabs(cols)
    dims, chunk = (1, 2, 128, 128), 64
    qkv = jnp.ones((1, chunk, 512), jnp.float32)
    small = jnp.zeros((1, 1, chunk, 1, 2), jnp.float32)
    forward = functools.partial(pk.gdn_chunk_forward, dims=dims, chunk=chunk)
    if kernel != "gdn_chunk_bwd":
        return lambda: forward(qkv, small, small)
    out, t = jax.eval_shape(forward, qkv, small, small)
    zeros = lambda x: jnp.zeros(x.shape, x.dtype)       # noqa: E731
    return lambda: pk.gdn_chunk_backward(
        qkv, small, small, zeros(t), tuple(map(zeros, out)), dims, chunk)


def _ssd(kernel):
    """The four calls of the Mamba-2 scan's chunk passes (one chunk of 128
    tokens, 8 heads of 16 over a state of 128)."""
    from horovod_tpu.ops import pallas_kernels as pk

    dims, chunk = (8, 16, 1, 128), 128
    xbc = jnp.ones((1, chunk, 128 + 256), jnp.float32)
    small = jnp.zeros((1, chunk, 8), jnp.float32)
    states, skip = jnp.ones((1, 1, 128, 128)), jnp.ones((1, 128))
    args = {"ssd_chunk_state": (xbc, small, small),
            "ssd_chunk_state_bwd": (xbc, small, small, states),
            "ssd_chunk_out": (xbc, small, small, states, skip),
            "ssd_chunk_out_bwd": (xbc, small, small, states, skip,
                                  jnp.ones((1, chunk, 128)))}[kernel]
    return lambda: getattr(pk, kernel)(*args, dims, chunk)


def _rope(kernel):
    from horovod_tpu.ops import pallas_kernels as pk

    x, table = jnp.ones((1, 16, 128)), jnp.ones((1, 16, 64))
    return lambda: pk._rope_call(x, table, table, half=32, conj=False,
                                 block=(1, 16, 128))


def _moe_rows(kernel):
    from horovod_tpu.ops import pallas_kernels as pk

    rows = jnp.ones((2048, 128), jnp.bfloat16)
    picks = jnp.arange(2048, dtype=jnp.int32)
    return lambda: pk.moe_sum_rows(
        rows, picks, jnp.ones((1024, 2), bool), picks // 1024, segments=3)


KERNEL_SITES = (
    [(_flash, k) for k in ("flash_fwd", "flash_win_fwd", "flash_bd_fwd",
                           "flash_fwd.ring", "flash_bwd", "flash_win_bwd",
                           "flash_bd_bwd", "flash_dq", "flash_dkv")]
    + [(_eva, k) for k in ("eva_win_fwd", "eva_win_bwd", "eva_sum_fwd",
                           "eva_sum_dq", "eva_sum_dkv")]
    + [(_conv, k) for k in ("conv1x1_bn", "conv1x1_bn_stats")]
    + [(_gdn, k) for k in ("gdn_inverse", "gdn_chunk_before",
                           "gdn_chunk_after", "gdn_chunk_bwd")]
    + [(_ssd, k) for k in ("ssd_chunk_state", "ssd_chunk_out",
                           "ssd_chunk_state_bwd", "ssd_chunk_out_bwd")]
    + [(_rope, "rope")]
    + [(_moe_rows, "moe_sum_rows")]
    + [(_optim, k) for k in ("fused_adam", "fused_sgd")]
    + [(_quant, k) for k in ("quantize", "dequantize", "quantize4",
                             "dequantize4")])


@pytest.mark.parametrize("build, kernel", KERNEL_SITES,
                         ids=[k for _, k in KERNEL_SITES])
def test_every_pallas_call_site_lowers_under_its_name(build, kernel):
    text = lowered_text(build(kernel))
    scope = kernel.split(".")[0]        # two sites share flash_fwd's name
    # A whole segment of the path, bare or inside jvp(..)/transpose(..),
    # or the first of a jitted function's own (moe_sum_rows).
    assert re.search(rf"[\"/(]hvdt\.kernel\.{scope}[/)]", text)


def test_no_pallas_call_site_is_left_without_a_name():
    """A new ``pl.pallas_call`` arrives with a ``hvdt.kernel.`` scope in
    the ``with`` statement above it, entered through ``kernel_scope``
    (``telemetry/compile_ledger.py``, PR 51: the scope, and a count of the
    site's traces) (a site that lowers under one of
    several names, as the local flash calls do under a window, under the
    block mask, on EVA's windows and under none, names them all there), and
    with a case in
    KERNEL_SITES for each name."""
    import inspect

    from horovod_tpu.ops import conv_fused, optim_kernels, pallas_kernels
    from horovod_tpu.quant import kernels as quant_kernels

    named = []
    for mod in (pallas_kernels, conv_fused, optim_kernels, quant_kernels):
        lines = inspect.getsource(mod).splitlines()
        for i, line in enumerate(lines):
            if re.search(r"(=|return) pl\.pallas_call\($", line):
                start = i - 1
                while not lines[start].lstrip().startswith("with "):
                    start -= 1
                statement = " ".join(lines[start:i])
                assert "kernel_scope(" in statement and i - start <= 4
                # every quoted word but what ``part`` is compared with
                names = re.findall(r'(?<!== )"(\w+)"', statement)
                assert names, f"{mod.__name__}:{i + 1} has no kernel scope"
                named.extend(names)
    assert sorted(named) == sorted(k.split(".")[0] for _, k in KERNEL_SITES)


def test_no_kernel_scope_is_entered_but_through_the_ledger():
    """Every ``hvdt.kernel.*`` site of the package goes through
    ``kernel_scope``: no literal is left beside it."""
    import inspect

    from horovod_tpu.ops import conv_fused, optim_kernels, pallas_kernels
    from horovod_tpu.quant import kernels as quant_kernels

    for mod in (pallas_kernels, conv_fused, optim_kernels, quant_kernels):
        source = inspect.getsource(mod)
        assert not re.search(r'named_scope\(\(?"hvdt\.kernel\.', source)
        assert "kernel_scope(" in source


def test_kernel_scope_counts_a_trace_a_shape_and_none_on_a_second_call():
    """``kernel_scope`` is ``jax.named_scope("hvdt.kernel.<name>")`` (the
    lowered name is the one above) and counts one trace of the site a
    distinct shape; a second call of the same jitted entry traces
    nothing, so it counts nothing."""
    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.telemetry import compile_ledger, default_registry

    site = compile_ledger.get_ledger().kernels.setdefault(
        "rope", compile_ledger.KernelSite())
    counter = default_registry().counter("hvdt_kernel_traces_total")
    before = site.traces, site.seconds, counter.value(kernel="rope")
    entry = jax.jit(lambda x, table: pk._rope_call(
        x, table, table, half=32, conj=False, block=(1, 16, 128)))

    def args(seq):
        return jnp.ones((1, seq, 128)), jnp.ones((1, seq, 64))

    assert "hvdt.kernel.rope" in entry.lower(*args(16)).as_text(
        debug_info=True)
    assert site.traces == before[0] + 1 and site.seconds > before[1]
    entry(*args(16))                    # the shape the lowering traced
    assert site.traces == before[0] + 1
    entry(*args(32))
    assert site.traces == before[0] + 2
    entry(*args(32))
    entry(*args(16))
    assert site.traces == before[0] + 2
    assert counter.value(kernel="rope") - before[2] == site.traces - before[0]


def test_lm_gradient_runs_flash_attention_as_three_bare_calls(monkeypatch):
    """A one-layer LM gradient at a shape that selects the flash kernel,
    lowered for the TPU (the Pallas -> Mosaic lowering is Python and needs
    no chip): exactly three Mosaic calls of flash attention per layer, fed
    by RoPE's six (PR 36: q and k on the projections' rows, the rows and
    two float32 tables of a block's 128 lanes in, the rows out, under
    ``hvdt.attention.rope/hvdt.kernel.rope``).  Two are the forward
    and its recompute, each q, k, v -> (out in the activation dtype, lse
    as a row).  One is the backward, q, k, v, dO and the two f32 row
    statistics (lse, delta) -> dq, dk, dv in the activation dtype.  The
    activations are the projections' own [B, L, H*D] rows (PR 29): no
    [B, H, L, D] array, no running (acc, m, l), no ``[..., 1]`` column and
    no f32 array of the sequence's size goes in or comes out of any of
    them, and nothing in
    the attention backward is a loop or a ``dynamic_update_slice``: the
    blockwise XLA backward is not there.  The benchmark's ``flash_fwd_ms``
    / ``flash_fwd_roofline`` and ``flash_bwd_ms`` / ``flash_bwd_roofline``
    pick their Mosaic events by the two names checked here (PERF.md
    section 3; since PR 30)."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    cfg = models.TransformerConfig(
        vocab=256, d_model=128, layers=1, heads=2, kv_heads=2, d_ff=256,
        max_seq=256, remat=True, loss_chunk=0)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg))).trace(
            params, tokens).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)

    def location(line):
        loc = re.search(r"loc\((#loc\d+)\)$", line).group(1)
        return re.search(rf"^{loc} = loc\((.*)$", text, re.M).group(1)

    act, row = "tensor<2x256x128xbf16>", "tensor<2x2x1x256xf32>"
    table = "tensor<2x256x128xf32>"
    types = {
        "rope": rf"\({act}, {table}, {table}\) -> {act}.*",
        "flash_fwd": rf"\(({act}, ){{2}}{act}\) -> \({act}, {row}\).*",
        "flash_bwd": rf"\(({act}, ){{4}}{row}, {row}\) -> "
                     rf"\(({act}, ){{2}}{act}\).*"}
    calls = {"rope": 0, "flash_fwd": 0, "flash_bwd": 0}
    for line in text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        child, kernel = re.search(
            r"hvdt\.attention/hvdt\.attention\.(\w+)/hvdt\.kernel\.(\w+)/",
            location(line)).groups()
        # RoPE's call under .rope (attn_rope_ms reads it, attn_surround_ms,
        # the events under .core that are no Mosaic calls, does not), the
        # flash calls under .core.
        assert child == ("rope" if kernel == "rope" else "core")
        calls[kernel] += 1
        signature = line.rsplit(" : ", 1)[1]
        assert re.fullmatch(types[kernel], signature), line
        if kernel != "rope":
            assert not re.search(r"x1x(f32|bf16)>|x256x\d+xf32>|x256x64x",
                                 signature)
    # q and k through RoPE in the forward, its recompute and the backward
    assert calls == {"rope": 6 * cfg.layers, "flash_fwd": 2 * cfg.layers,
                     "flash_bwd": cfg.layers}
    # Every operation's name stack is a location of the text: none under
    # the attention scope is a loop, inside one, or a dynamic_update_slice.
    assert not re.search(
        r'loc\("[^"]*hvdt\.attention/[^"]*(while|dynamic_update_slice)', text)



def _primitives_outside_kernels(jaxpr):
    """Names of the primitives of a jaxpr and of everything it calls, the
    bodies of its ``pallas_call``s left out (a kernel transposes tiles in
    VMEM; the question here is what XLA is asked to move in HBM)."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives_outside_kernels(sub)
    return names


@pytest.mark.parametrize("heads, kv_heads, head_dim, transposes", [
    (4, 4, 64, False), (2, 1, 128, False), (3, 3, 64, True)],
    ids=["h4_d64_pairs", "h2_d128_gqa", "h3_d64_folded"])
def test_flash_attention_moves_no_layout_around_its_calls(
        heads, kv_heads, head_dim, transposes):
    """The counter that the [B, L, H*D] kernel layout engages (PR 29): at
    b2 L256 the jaxpr of flash_attention and of its gradient is one
    forward call and one backward call with reshapes (bitcasts) around
    them and no ``transpose``, for head_dim 64 in pairs and head_dim 128
    alike.  A shape with no 128-lane block (H odd at head_dim 64) keeps
    the transposed route, heads folded into the batch, and shows them."""
    from horovod_tpu.ops import pallas_kernels as pk

    q = jnp.ones((2, 256, heads, head_dim), jnp.bfloat16)
    kv = jnp.ones((2, 256, kv_heads, head_dim), jnp.bfloat16)
    assert (pk._heads_per_program(heads, kv_heads, head_dim) is None
            ) == transposes
    names = _primitives_outside_kernels(jax.make_jaxpr(
        lambda q, k, v, do: jax.vjp(pk.flash_attention, q, k, v)[1](do))(
            q, kv, kv, q).jaxpr)
    assert names.count("pallas_call") == 2
    assert ("transpose" in names) == transposes


# ---------------------------------------------------------------------------
# A layer-pattern model: the sparse feed-forward's scopes under hvdt.mlp,
# and the windowed kernels' names beside the full-causal ones.
# ---------------------------------------------------------------------------


def pattern_config(seq):
    full = models.LayerKind(heads=2, kv_heads=1, d_ff=256)
    sliding = models.LayerKind(heads=2, kv_heads=1, window=128, sparse=True)
    return models.TransformerConfig(
        vocab=256, d_model=128, head_dim=128, layers=3, leading=(full,),
        period=(sliding, sliding), max_seq=seq, remat=True, loss_chunk=0,
        out_gate=True, tie_head=False,
        moe=models.Experts(held=2, d_ff=128, routed=4, per_token=2,
                           shared_d_ff=128))


@pytest.fixture(scope="module")
def pattern_grad_text():
    cfg = pattern_config(32)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return compiled_text(jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg)), params, tokens)


@pytest.mark.parametrize("scope", [
    "hvdt.moe/hvdt.moe.route/", "hvdt.moe/hvdt.moe.dispatch/",
    "hvdt.moe/hvdt.moe.experts/", "hvdt.moe/hvdt.moe.shared/"])
@pytest.mark.parametrize("wrapper", [
    "jvp()/while/body/closed_call/while/body/closed_call/hvdt.mlp/",
    "checkpoint/rematted_computation/hvdt.mlp/",
    "closed_call/checkpoint/hvdt.mlp/"])
def test_the_sparse_feed_forward_carries_its_scopes_under_mlp(
        pattern_grad_text, wrapper, scope):
    """``hvdt.moe`` around the whole sparse feed-forward, under
    ``hvdt.mlp`` (so the phase split's mlp remainder still holds it), and
    inside it the four parts the benchmark's moe readers sum; forward,
    recompute and backward, in the scan of the period's run of layers."""
    assert wrapper + scope in pattern_grad_text
    # the leading dense layer has an mlp and no moe
    assert re.search(r"hvdt\.mlp/(?!hvdt\.moe)", pattern_grad_text)


def test_a_pattern_lm_names_windowed_and_full_kernels_apart(monkeypatch):
    """Lowered for the TPU: the leading full-causal layer's three Mosaic
    calls under ``flash_fwd`` / ``flash_bwd``, the scanned sliding
    layers' three under ``flash_win_fwd`` / ``flash_win_bwd`` (one call
    site each for the run of two layers), grouped queries at head_dim 128
    by the index map (k, v enter at their own width, nothing repeated)."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    cfg = pattern_config(256)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg))).trace(
            params, tokens).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)

    def location(line):
        loc = re.search(r"loc\((#loc\d+)\)$", line).group(1)
        return re.search(rf"^{loc} = loc\((.*)$", text, re.M).group(1)

    calls = {}
    for line in text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        child, kernel = re.search(
            r"hvdt\.attention\)?/hvdt\.attention\.(\w+)\)?/"
            r"hvdt\.kernel\.(\w+)/", location(line)).groups()
        assert child == ("rope" if kernel == "rope" else "core")
        calls[kernel] = calls.get(kernel, 0) + 1
        if kernel.endswith("fwd"):      # q [2,256,256], k, v [2,256,128]
            assert "(tensor<2x256x256xbf16>, tensor<2x256x128xbf16>, " \
                "tensor<2x256x128xbf16>)" in line
    # RoPE: q and k, forward, recompute and backward, of the leading layer
    # and of the run's one call site
    assert calls == {"flash_fwd": 2, "flash_bwd": 1, "flash_win_fwd": 2,
                     "flash_win_bwd": 1, "rope": 12}
    # The grouped products are ragged dots, all under the experts' scope.
    ragged = [location(line) for line in text.splitlines()
              if "chlo.ragged_dot" in line]
    assert len(ragged) >= 9             # 3 forward, 3 recompute, 6 backward
    assert all("hvdt.moe/hvdt.moe.experts/" in loc for loc in ragged)


def test_a_block_diffusion_lm_names_its_kernels_loss_and_lookup(monkeypatch):
    """Lowered for the TPU, the second objective's gradient: the block-mask
    kernels under ``hvdt.attention.core`` as ``flash_bd_fwd`` (forward and
    ``rematted_computation``) and ``flash_bd_bwd`` (``transpose(``), no
    causal or windowed call; the noisy half's slice and the weighted loss
    under ``hvdt.loss``; the two streams' rows and their lookup under
    ``hvdt.embed``."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    cfg = models.TransformerConfig(
        vocab=128, layers=2, d_model=256, heads=2, kv_heads=2, head_dim=128,
        d_ff=256, max_seq=256, remat=True, loss_chunk=64, diffusion_block=4)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    batch = (jax.ShapeDtypeStruct((2, 128), jnp.int32),
             jax.ShapeDtypeStruct((2, 32), jnp.float32),
             jax.ShapeDtypeStruct((2, 128), jnp.bool_))
    text = jax.jit(jax.value_and_grad(
        lambda p, *b: models.transformer_block_diffusion_loss(
            p, *b, cfg))).trace(params, *batch).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    flash = set(re.findall(r'loc\("([^"]*hvdt\.kernel\.flash[^"]*)"', text))
    assert flash == {
        "hvdt.attention/hvdt.attention.core/hvdt.kernel.flash_bd_fwd/"
        "pallas_call",
        "checkpoint/rematted_computation/hvdt.attention/"
        "hvdt.attention.core/hvdt.kernel.flash_bd_fwd/pallas_call",
        "checkpoint/hvdt.attention/hvdt.attention.core/"
        "hvdt.kernel.flash_bd_bwd/pallas_call"}
    # the rows [x_t ; x_0] and their lookup; its backward the scatter-add
    for name in ("jvp(hvdt.embed)/select_n", "jvp(hvdt.embed)/concatenate",
                 "jvp(hvdt.embed)/gather",
                 "transpose(jvp(hvdt.embed))/scatter-add",
                 # the noisy half, a row's weight masked / t, the weighted sum
                 "jvp(hvdt.loss)/slice", "jvp(hvdt.loss)/div",
                 "jvp(hvdt.loss)/mul", "transpose(jvp(hvdt.loss))/pad"):
        assert f'/{name}"' in text, name


# ---------------------------------------------------------------------------
# The linear mixer: hvdt.gdn beside hvdt.attention, and its four parts.
# ---------------------------------------------------------------------------


def hybrid_config():
    linear = models.LayerKind(
        heads=0, kv_heads=0, sparse=True, linear=models.LinearMixer(
            key_heads=1, value_heads=2, key_dim=16, value_dim=16))
    full = models.LayerKind(heads=2, kv_heads=1, sparse=True)
    cfg = models.TransformerConfig(
        vocab=256, d_model=32, head_dim=16, layers=3,
        period=(linear, linear, full), max_seq=64, remat=True,
        out_gate="elementwise", qk_norm=True, zero_centered_norm=True,
        tie_head=False, moe=models.Experts(
            held=2, d_ff=16, routed=4, per_token=2, score="softmax",
            shared_d_ff=16, shared_gate=True))
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg)), params, tokens


@pytest.fixture(scope="module")
def hybrid_grad_text():
    return compiled_text(*hybrid_config())


@pytest.mark.parametrize("scope", [
    "hvdt.gdn/hvdt.gdn.proj/", "hvdt.gdn/hvdt.gdn.conv/",
    "hvdt.gdn/hvdt.gdn.scan/", "hvdt.gdn/hvdt.gdn.norm/"])
@pytest.mark.parametrize("wrapper", [
    "jvp()/while/body/closed_call/while/body/closed_call/",
    "checkpoint/rematted_computation/",
    "closed_call/checkpoint/"])
def test_the_linear_mixer_carries_its_scopes(hybrid_grad_text, wrapper,
                                             scope):
    """``hvdt.gdn`` around the whole Gated DeltaNet sublayer and inside it
    the four parts the benchmark's ``gdn_*`` readers sum; forward,
    recompute and backward, in the scan of the period's run of layers."""
    assert wrapper + scope in hybrid_grad_text


def test_the_linear_mixer_is_a_sibling_of_attention(hybrid_grad_text):
    """Nothing of the linear mixer is under ``hvdt.attention`` (so
    ``attention_ms`` keeps meaning softmax attention), the full layer of the
    same period keeps ``hvdt.attention``, the state's loop is a ``while``
    under ``hvdt.gdn.scan``, and the shared expert's gate is inside
    ``hvdt.moe.shared``."""
    assert "hvdt.attention/hvdt.gdn" not in hybrid_grad_text
    assert "hvdt.gdn/hvdt.attention" not in hybrid_grad_text
    assert re.search(r"while/body/closed_call/hvdt\.attention/",
                     hybrid_grad_text)
    assert ("hvdt.gdn/hvdt.gdn.scan/hvdt.gdn.scan.state/while/body/"
            in hybrid_grad_text)
    # the gate's sigmoid, beside the shared expert's own silu
    assert re.search(r"hvdt\.moe/hvdt\.moe\.shared/(exp|logistic)\b",
                     hybrid_grad_text)


def test_the_inverses_kernel_is_in_the_forward_and_the_recompute(
        monkeypatch, hybrid_grad_text):
    """With the kernels lowered through Mosaic as on a TPU (the lowering is
    Python; the inverse's chooser reads the same switch): the run of two linear layers
    holds the Mosaic call of ``(I + A)^-1`` twice, in the forward and in
    ``rematted_computation``, each under ``hvdt.gdn/hvdt.gdn.scan/
    hvdt.gdn.scan.chunk/hvdt.kernel.gdn_inverse`` (what ``gdn_scan_ms``
    and ``gdn_chunk_ms`` sum and what a later
    ``gdn_inverse_ms`` picks by ``phase_split.scope_calls(ctx,
    "hvdt.kernel.gdn_inverse", trace_reduce.is_mosaic)``), float32
    ``[C, 1, C, matrices]`` slabs in and out, and never under ``transpose(``:
    the backward is the inverse's own two-product cotangent rule.  The
    four-stage loop of XLA's schedule is gone from that text and is what
    the CPU compiles (no kernel scope there)."""
    from horovod_tpu.ops import pallas_kernels as pk

    assert "hvdt.kernel.gdn_inverse" not in hybrid_grad_text
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    fn, params, tokens = hybrid_config()
    text = jax.jit(fn).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)

    def location(line):
        loc = re.search(r"loc\((#loc\d+)\)$", line).group(1)
        return re.search(rf"^{loc} = loc\((.*)$", text, re.M).group(1)

    calls = [(location(line), line) for line in text.splitlines()
             if "@tpu_custom_call" in line]
    inverse = [(loc, line) for loc, line in calls
               if "hvdt.kernel.gdn_inverse" in loc]
    assert len(inverse) == 2
    for loc, line in inverse:
        assert re.search(r"hvdt\.gdn\)?/hvdt\.gdn\.scan\)?/"
                         r"hvdt\.gdn\.scan\.chunk\)?/"
                         r"hvdt\.kernel\.gdn_inverse/", loc)
        assert "hvdt.attention" not in loc and "transpose(" not in loc
        # 2 sequences x 1 chunk x 2 value heads, padded to a block; one
        # group of slabs (the chunk kernels give a group a value head)
        assert ("(tensor<64x1x64x128xf32>) -> tensor<64x1x64x128xf32>"
                in line)
    # inside the scanned body a name stack is relative to the call: the
    # forward's is bare, the recompute's starts at its checkpoint
    assert sorted(loc.split("hvdt.gdn/")[0].strip('"')
                  for loc, _ in inverse) == [
                      "", "checkpoint/rematted_computation/"]


# ---------------------------------------------------------------------------
# The second level (PR 35): the children of hvdt.attention, hvdt.gdn.scan
# and hvdt.moe.dispatch, and hvdt.embed.  One reader of the benchmark reads
# each (PERF.md section 3).
# ---------------------------------------------------------------------------

FLAT = ("jvp()/while/body/closed_call/", "checkpoint/rematted_computation/",
        "transpose(jvp())/while/body/closed_call/checkpoint/")
NESTED = ("jvp()/while/body/closed_call/while/body/closed_call/",
          "checkpoint/rematted_computation/", "closed_call/checkpoint/")
ROWS = "hvdt.mlp/hvdt.moe/hvdt.moe.dispatch/hvdt.moe.dispatch.rows/"
TOKENS = "hvdt.mlp/hvdt.moe/hvdt.moe.dispatch/hvdt.moe.dispatch.tokens/"


def _under(wrappers, parent, children):
    """Each wrapper, the path ``parent``, each child of its last scope."""
    scope = parent.split("/")[-1]
    return [f"{w}{parent}/{scope}{c}/" for c in children for w in wrappers]


UNIFORM_PATHS = _under(FLAT, "hvdt.attention",
                       (".qkv", ".rope", ".core", ".out")) + [
    "jvp(hvdt.embed)/", "transpose(jvp(hvdt.embed))/"]
CHILD_PATHS = (
    # forward, recompute and backward of every child, in each fixture
    # whose configuration has it
    [("pattern_grad_text", path) for path in _under(
        NESTED, "hvdt.attention", (".qkv", ".rope", ".core", ".gate",
                                    ".out"))]
    + [("pattern_grad_text", w + ROWS) for w in NESTED]
    # the rows' way back is not needed again for the backward: no recompute
    + [("pattern_grad_text", w + TOKENS) for w in NESTED[::2]]
    + [("hybrid_grad_text", path) for path in _under(
        NESTED, "hvdt.gdn/hvdt.gdn.scan", (".chunk", ".state", ".out"))]
    + [("hybrid_grad_text", path) for path in _under(
        NESTED, "hvdt.attention", (".rope", ".gate"))]
    + [("hybrid_grad_text", w + ROWS) for w in NESTED]
    + [("hybrid_grad_text", w + TOKENS) for w in NESTED[::2]]
    + [("hybrid_grad_text", "jvp(hvdt.embed)/")])


@pytest.mark.parametrize("path", UNIFORM_PATHS)
def test_the_uniform_lm_carries_the_child_scopes(lm_grad_text, path):
    assert path in lm_grad_text


@pytest.mark.parametrize("fixture, path", CHILD_PATHS)
def test_a_child_scope_is_on_the_path_under_its_parent(request, fixture,
                                                       path):
    assert path in request.getfixturevalue(fixture)


def _children_are_siblings(text):
    """No child is under another (a reader's time would count twice), and
    a child is nowhere but under its parent.  (XLA joins the names of two
    operations it merges with a semicolon: one path ends there.)"""
    for parent, children in [
            ("hvdt.attention", ("qkv", "rope", "core", "gate", "out")),
            ("hvdt.gdn.scan", ("chunk", "state", "out")),
            ("hvdt.moe.dispatch", ("rows", "tokens"))]:
        family = "|".join(children)
        dotted = re.escape(parent)
        assert not re.search(
            rf"{dotted}\.({family})/[^\";]*{dotted}\.({family})/", text)
        # bare, or closing a wrapper: jvp(hvdt.attention)/hvdt.attention.qkv/
        for found in re.finditer(rf"([\w.]*)\)*/{dotted}\.({family})/", text):
            assert found.group(1) == parent, found.group(0)
    assert not re.search(r"hvdt\.\w+/[^\"]*hvdt\.embed", text)
    return True


def test_the_uniform_lms_children_are_siblings(lm_grad_text):
    assert _children_are_siblings(lm_grad_text)


@pytest.mark.parametrize("fixture", ["pattern_grad_text",
                                     "hybrid_grad_text"])
def test_the_children_of_a_scope_are_siblings(request, fixture):
    assert _children_are_siblings(request.getfixturevalue(fixture))


def test_the_states_loop_is_under_state_and_nothing_else_of_the_scan_is(
        hybrid_grad_text):
    """The two products of a trip of the state's loop (their einsums name
    them) are in a ``while`` body under ``hvdt.gdn.scan.state``, forward,
    recompute and backward; no product of the chunks or of ``O`` is."""
    in_loop = ("bhrid,bhrde->bhrie", "bihrd,bhrie->bhrde")
    names = re.findall(r'op_name="([^"]*hvdt\.gdn\.scan[^"]*)"',
                       hybrid_grad_text)
    trips = [n for n in names if any(e in n for e in in_loop)]
    assert trips and all(
        "hvdt.gdn.scan/hvdt.gdn.scan.state/while/body/" in n
        or "hvdt.gdn.scan/hvdt.gdn.scan.state/closed_call/while/body/" in n
        for n in trips)
    others = [n for n in names if "hvdt.gdn.scan.state/" in n]
    assert not [n for n in others
                if "bnihd,bnjhd->bnhij" in n or "bnhrij,bnhrje->bnihre" in n]
    assert any("transpose(" in n for n in others)
    assert any("rematted_computation" in n for n in others)


def _name_stacks(jaxpr, outer=()):
    """(primitive, whole name stack) of every equation of ``jaxpr`` and of
    the jaxprs its equations call (a kernel's body is the kernel's)."""
    from jax._src import core

    for eqn in jaxpr.eqns:
        stack = outer + (str(eqn.source_info.name_stack),)
        inner = [] if eqn.primitive.name == "pallas_call" else list(
            core.jaxprs_in_params(eqn.params))
        for sub in inner:
            yield from _name_stacks(sub, stack)
        if not inner:
            yield eqn.primitive.name, "/".join(s for s in stack if s)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["xla", "mosaic"])
def test_the_chunk_rule_is_under_chunk_forward_and_backward(monkeypatch,
                                                            on_tpu):
    """Every equation of ``gated_delta_rule`` under ``jax.grad`` is under
    one of the scan's three children, and those of the chunk-local
    passes' hand-written rule (everything that is not the state's loop or
    ``O``), the backward's among them, under ``hvdt.gdn.scan.chunk``: what
    ``gdn_chunk_ms`` sums, so that no time of the rule slides to
    ``unscoped_ms``.  In both schedules; in Mosaic's the four calls are
    the forward's three and the backward's one."""
    from horovod_tpu.ops import gated_delta as gd

    monkeypatch.setattr(gd, "_on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        x = jnp.ones((1, 128, 1, 128), jnp.float32)
        v, small = jnp.ones((1, 128, 2, 128)), jnp.ones((1, 128, 2)) * 0.5
        grad = jax.grad(lambda *a: gd.gated_delta_rule(*a).sum(),
                        argnums=(0, 1, 2, 3, 4))
        stacks = [(p, s) for p, s in _name_stacks(
            jax.make_jaxpr(grad)(x, x, v, -small, small).jaxpr)
            if "hvdt." in s or p == "pallas_call"]
    finally:
        jax.clear_caches()
    children = [re.findall(r"hvdt\.gdn\.scan\.(\w+)", s) for _, s in stacks]
    assert all(c and set(c) <= {"chunk", "state", "out"} and len(set(c)) == 1
               for c in children)
    chunk = [(p, s) for (p, s), c in zip(stacks, children) if "chunk" in c]
    backward = [s for _, s in chunk if s.startswith("transpose(")]
    assert len(backward) > 10 and len(chunk) - len(backward) > 10
    calls = [s.split("hvdt.kernel.")[1] for p, s in chunk
             if p == "pallas_call"]
    assert calls == (["gdn_chunk_before", "gdn_inverse", "gdn_chunk_after",
                      "gdn_chunk_bwd"] if on_tpu else [])
    assert not [p for (p, _), c in zip(stacks, children)
                if p == "pallas_call" and "chunk" not in c]


# ---------------------------------------------------------------------------
# The state-space mixer (PR 45): hvdt.ssd beside hvdt.attention and
# hvdt.gdn, its four parts, and the three children of its scan.
# ---------------------------------------------------------------------------


def state_space_config():
    mamba = models.LayerKind(
        heads=0, kv_heads=0, d_ff=32, ssm=models.StateSpaceMixer(
            heads=4, head_dim=8, state=16, chunk=16))
    full = models.LayerKind(heads=2, kv_heads=1, d_ff=32, rope=None)
    cfg = models.TransformerConfig(
        vocab=256, d_model=32, head_dim=16, layers=3,
        period=(mamba, mamba, full), max_seq=64, remat=True, loss_chunk=128,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=8.0)
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg)), params, tokens


@pytest.fixture(scope="module")
def state_space_grad_text():
    return compiled_text(*state_space_config())


@pytest.mark.parametrize("path", _under(
    NESTED, "hvdt.ssd", (".proj", ".conv", ".scan", ".norm")) + _under(
    NESTED, "hvdt.ssd/hvdt.ssd.scan", (".chunk", ".state", ".out")))
def test_the_state_space_mixer_carries_its_scopes(state_space_grad_text,
                                                  path):
    """``hvdt.ssd`` around the whole Mamba-2 sublayer, inside it the four
    parts and under its scan the three children the benchmark's ``ssd_*``
    readers sum; forward, recompute and backward, in the scan of the
    period's run of layers."""
    assert path in state_space_grad_text


def test_the_state_space_mixer_is_a_sibling_of_attention(
        state_space_grad_text):
    """Nothing of the mixer is under ``hvdt.attention`` or ``hvdt.gdn``
    (``attention_ms`` keeps meaning softmax attention), the attention layer
    of the same period keeps ``hvdt.attention``, and the state's loop is a
    ``while`` under ``hvdt.ssd.scan.state``."""
    text = state_space_grad_text
    assert "hvdt.attention/hvdt.ssd" not in text
    assert "hvdt.ssd/hvdt.attention" not in text and "hvdt.gdn" not in text
    assert re.search(r"while/body/closed_call/hvdt\.attention/", text)
    assert "hvdt.ssd/hvdt.ssd.scan/hvdt.ssd.scan.state/while/body/" in text


@pytest.mark.parametrize("on_tpu", [False, True], ids=["xla", "mosaic"])
def test_the_scans_children_account_for_all_of_it(monkeypatch, on_tpu):
    """Every equation of the mixer under ``jax.grad`` that is under
    ``hvdt.ssd.scan`` is under exactly one of ``.chunk``, ``.state`` and
    ``.out`` (so ``ssd_chunk_ms + ssd_state_ms + ssd_out_ms`` is
    ``ssd_scan_ms``), the backwards of the two hand-written rules among
    them (they carry the forwards' scopes), and the rest of it under
    ``.proj``, ``.conv`` or ``.norm``.  In both schedules; in Mosaic's the
    calls are the chunk's own state under ``.chunk`` and y under ``.out``,
    forward and backward, each under its ``hvdt.kernel.ssd_*`` name."""
    from horovod_tpu.ops import gated_delta as gd
    from horovod_tpu.ops.ssd import mamba2_mixer

    # sizes the kernels tile where the platform is answered as a TPU
    sizes = dict(heads=8, head_dim=16, state=128, chunk=128) if on_tpu \
        else dict(heads=4, head_dim=8, state=16, chunk=16)
    cfg = models.TransformerConfig(layers=1, d_model=32, period=(
        models.LayerKind(heads=0, kv_heads=0, d_ff=32,
                         ssm=models.StateSpaceMixer(**sizes)),))
    p = jax.eval_shape(lambda k: models.transformer_init(k, cfg),
                       jax.random.PRNGKey(0))["period"]["0"]
    p = jax.tree.map(lambda a: jnp.ones(a.shape[2:], a.dtype), p)
    grad = jax.grad(lambda x, p: mamba2_mixer(
        x, p, proj=lambda a, w: a @ w, eps=1e-5,
        **cfg.period[0].ssm.sizes).sum(), argnums=(0, 1))
    monkeypatch.setattr(gd, "_on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        found = list(_name_stacks(jax.make_jaxpr(grad)(
            jnp.ones((1, 4 * sizes["chunk"], 32)), p).jaxpr))
    finally:
        jax.clear_caches()
    stacks = [s for _, s in found]
    scan = [re.findall(r"hvdt\.ssd\.scan\.(\w+)", s) for s in stacks
            if "hvdt.ssd.scan" in s]
    assert len(scan) > 50 and all(
        len(set(c)) == 1 and set(c) <= {"chunk", "state", "out"}
        for c in scan)
    assert {c[0] for c in scan} == {"chunk", "state", "out"}
    rest = [s for s in stacks if "hvdt.ssd.scan" not in s
            and "hvdt.ssd" in s]
    assert rest and all(re.search(r"hvdt\.ssd\.(proj|conv|norm)", s)
                        for s in rest)
    calls = [(re.search(r"hvdt\.ssd\.scan\.(\w+)", s).group(1),
              s.split("hvdt.kernel.")[1].strip("/)"),
              s.startswith("transpose(")) for prim, s in found
             if prim == "pallas_call"]
    assert sorted(calls) == (sorted([
        ("chunk", "ssd_chunk_state", False), ("out", "ssd_chunk_out", False),
        ("chunk", "ssd_chunk_state_bwd", True),
        ("out", "ssd_chunk_out_bwd", True)]) if on_tpu else [])


# ---------------------------------------------------------------------------
# The double-gated short convolution (PR 48): hvdt.sconv beside
# hvdt.attention, hvdt.gdn and hvdt.ssd, its three parts, and the route
# that picks by score plus bias.
# ---------------------------------------------------------------------------


def short_conv_config():
    conv = models.LayerKind(heads=0, kv_heads=0, sparse=True,
                            conv=models.ShortConv(taps=3))
    full = models.LayerKind(heads=2, kv_heads=1, sparse=True)
    cfg = models.TransformerConfig(
        vocab=256, d_model=32, head_dim=16, layers=4,
        leading=(models.LayerKind(heads=0, kv_heads=0, d_ff=48,
                                  conv=models.ShortConv(taps=3)),),
        period=(full, conv, conv), max_seq=64, remat=True, loss_chunk=128,
        qk_norm=True, moe=models.Experts(
            held=2, d_ff=16, routed=8, per_token=2, select_bias=True,
            normalize_eps=1e-6))
    params = jax.eval_shape(
        lambda k: models.transformer_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return jax.value_and_grad(
        lambda p, t: models.transformer_loss(p, t, cfg)), params, tokens


@pytest.fixture(scope="module")
def short_conv_grad_text():
    return compiled_text(*short_conv_config())


@pytest.mark.parametrize("path", _under(
    NESTED, "hvdt.sconv", (".in", ".conv", ".out")))
def test_the_short_convolution_carries_its_scopes(short_conv_grad_text,
                                                  path):
    """``hvdt.sconv`` around the whole sublayer, inside it the three parts
    the benchmark's ``sconv_*`` readers read; forward, recompute and
    backward, in the scan of the period's run of layers."""
    assert path in short_conv_grad_text


def test_the_short_convolution_is_a_sibling_of_attention(
        short_conv_grad_text):
    """Nothing of the mixer is under ``hvdt.attention`` (``attention_ms``
    keeps meaning softmax attention), the leading layer carries the same
    names outside the scan, the attention layer of the same period keeps
    ``hvdt.attention``, and a route that takes a selection bias says so
    under ``hvdt.moe.route`` in the forward alone."""
    text = short_conv_grad_text
    assert "hvdt.attention/hvdt.sconv" not in text
    assert "hvdt.sconv/hvdt.attention" not in text
    assert "hvdt.gdn" not in text and "hvdt.ssd" not in text
    assert re.search(r"while/body/closed_call/hvdt\.attention/", text)
    assert [line for line in text.splitlines()
            if "hvdt.sconv/hvdt.sconv.conv/" in line
            and "while/body" not in line]
    select = "hvdt.moe/hvdt.moe.route/hvdt.moe.route.select_bias/"
    assert select in text
    assert not [line for line in text.splitlines()
                if select in line and "rematted_computation" in line]


def test_the_three_parts_account_for_all_of_the_short_convolution():
    """Every equation of the mixer under ``jax.grad`` is under exactly one
    of ``.in``, ``.conv`` and ``.out``."""
    from horovod_tpu.ops.short_conv import gated_short_conv

    p = {"w_in": jnp.ones((32, 96)), "conv": jnp.ones((3, 32)),
         "w_out": jnp.ones((32, 32))}
    grad = jax.grad(lambda x, p: gated_short_conv(
        x, p, proj=lambda a, w: a @ w).sum(), argnums=(0, 1))
    stacks = [s for _, s in _name_stacks(jax.make_jaxpr(grad)(
        jnp.ones((1, 64, 32)), p).jaxpr)]
    parts = [re.findall(r"hvdt\.sconv\.(\w+)", s) for s in stacks
             if "jvp()" != s and "transpose(jvp())" != s]   # the test's sum
    assert len(parts) > 20 and all(len(set(c)) == 1 for c in parts)
    assert {c[0] for c in parts} == {"in", "conv", "out"}
