"""chip_smoke.py — the quickest proof that the training main path still
starts on the chip.

One process, no children.  It drives the path a user of this framework
runs — ``hvd.init()`` -> the default ``dp`` mesh over every local chip ->
``hvd.DistributedOptimizer`` fed per-rank gradients (``pvary_tree``, so
the framework's own fused, bucketed exchange is in the compiled program)
-> ``hvd.donated_step`` -> a few steps — at the full width of the two
models the repo has on-chip history for, and checks what comes out:

* the ``bert-large`` LM (seq 512) and ResNet-50 (bs 128 per chip): one
  compile, one warm-up, three steps, loss finite and falling;
* the same LM at seq 4096, where ``auto`` must pick the Pallas attention
  kernels with no knob set (it does from seq 512 up, so the seq-512 LM
  above runs them too), against XLA attention on the forward;
* every ``pallas_call`` the package ships, compiled through Mosaic once
  and compared with the ``jnp`` reference beside it;
* on more than one chip: the work and the memory are spread over all of
  them, the compiled step holds all-reduces over every replica that carry
  the whole gradient pytree, and ``dp=n`` training matches one device.

The step times printed are smoke timings of a handful of steps, not a
benchmark.  Without a TPU the script exits non-zero having run nothing;
its ``__main__`` has no CPU route.  The phase functions take their sizes
as arguments so tests/test_chip_smoke.py can call them at toy size on the
CPU simulator.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# examples/jax_transformer_lm.py PRESETS["bert-large"]
BERT_LARGE = dict(layers=24, d_model=1024, heads=16, d_ff=4096, vocab=30528,
                  loss_chunk=8192)
# Sequences per chip for the seq-512 LM: the recorded configuration, which
# fits 16 GB on this path too.
LM_PER_CHIP_BATCH = 128


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def env_knob(name: str, value: str):
    """Set one env knob for the duration of a trace (the model reads its
    knobs at trace time)."""
    before = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if before is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = before


def fetch_losses(run_step, steps: int):
    """Warm-up plus ``steps`` timed calls of ``run_step() -> loss``, each
    ended by a host fetch of the loss.  Returns (losses, seconds) with the
    warm-up first."""
    losses, secs = [], []
    for _ in range(steps + 1):
        t0 = time.perf_counter()
        losses.append(float(run_step()))
        secs.append(time.perf_counter() - t0)
    return losses, secs


def check_losses(name: str, losses) -> None:
    import math

    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{name}: loss did not fall on the fixed batch: {losses}")


# ---------------------------------------------------------------------------
# The main path: DistributedOptimizer over per-rank gradients inside
# shard_map over dp, jitted by donated_step.
# ---------------------------------------------------------------------------


def build_dp_step(mesh, loss_fn, optimizer, n_batch_args: int):
    """``step(params, opt_state, *batch) -> (params, opt_state, loss)``
    over ``mesh``'s ``dp`` axis, plus the DistributedOptimizer it uses.
    ``loss_fn(params, *local_batch)`` sees one rank's shard."""
    import jax
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    opt = hvd.DistributedOptimizer(optimizer)

    def local_step(params, opt_state, *batch):
        # Per-rank gradients: with unvarying params AD would psum the
        # cotangents itself and the exchange layer would be bypassed.
        diff = hvd.optimizer.pvary_tree(params, "dp")
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, *batch))(diff)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, "dp"))

    step = hvd.donated_step(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P()) + (P("dp"),) * n_batch_args,
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))
    return step, opt


def place(mesh, tree, spec):
    import jax
    from jax.sharding import NamedSharding

    return jax.device_put(tree, NamedSharding(mesh, spec))


def compile_step(name: str, step, *args):
    """AOT-compile ``step`` once; the returned executable is what the
    phase then runs, so compile time and step time stay apart."""
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    say(f"[{name}] compile {time.perf_counter() - t0:.1f} s; per device: "
        f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
        f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    return compiled


def lm_config(seq: int, *, dtype=None, **model):
    """``model`` is BERT_LARGE or a toy of the same keys."""
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        kv_heads=model["heads"], max_seq=seq, dtype=dtype or jnp.bfloat16,
        remat=True, **model)


def lm_setup(mesh, cfg, global_batch: int, seed: int = 0):
    """(compiled-step inputs) for the LM on ``mesh``: params and AdamW
    state replicated, one fixed token batch sharded over dp."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import transformer_init, transformer_loss

    step, opt = build_dp_step(
        mesh, lambda p, t: transformer_loss(p, t, cfg), optax.adamw(3e-4),
        n_batch_args=1)
    params = place(mesh, transformer_init(jax.random.PRNGKey(seed), cfg),
                   P())
    opt_state = place(mesh, opt.init(params), P())
    tokens = place(mesh, np.random.default_rng(seed).integers(
        0, cfg.vocab, (global_batch, cfg.max_seq)).astype(np.int32),
        P("dp"))
    return step, params, opt_state, tokens


def run_steps(name: str, compiled, params, opt_state, batch, steps: int):
    """Warm-up plus ``steps`` steps of ``compiled`` on one fixed batch,
    losses checked and timings printed; returns (losses, params)."""
    state = [params, opt_state]

    def one():
        state[0], state[1], loss = compiled(state[0], state[1], *batch)
        return loss

    losses, secs = fetch_losses(one, steps)
    check_losses(name, losses)
    say(f"[{name}] warm-up {secs[0]:.2f} s; smoke step times "
        + " ".join(f"{s:.3f}" for s in secs[1:]) + " s; loss "
        + " -> ".join(f"{v:.4f}" for v in losses))
    return losses, state[0]


def run_lm(name: str, mesh, cfg, global_batch: int, steps: int,
           inspect=None):
    """Compile once, warm up, take ``steps`` steps of the LM; returns
    (losses, params, tokens).  ``inspect(compiled, params, tokens)`` runs
    between compile and the first step."""
    step, params, opt_state, tokens = lm_setup(mesh, cfg, global_batch)
    compiled = compile_step(name, step, params, opt_state, tokens)
    if inspect is not None:
        inspect(compiled, params, tokens)
    losses, params = run_steps(name, compiled, params, opt_state,
                               (tokens,), steps)
    return losses, params, tokens


def phase_lm(mesh, *, seq: int, per_chip_batch: int, steps: int = 3,
             model=BERT_LARGE, inspect=None):
    """The LM at ``seq`` on the whole mesh."""
    n = mesh.devices.size
    cfg = lm_config(seq, **model)
    return run_lm(f"lm seq{seq} b{per_chip_batch}x{n}", mesh, cfg,
                  per_chip_batch * n, steps, inspect)[0]


def phase_resnet(mesh, *, per_chip_batch: int, image_size: int = 224,
                 steps: int = 3, depth: int = 50, num_classes: int = 1000):
    """ResNet, bf16, SGD-momentum — the recipe of
    examples/jax_synthetic_benchmark.py on the main path."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import ResNetConfig, resnet50_init, resnet_loss

    n = mesh.devices.size
    name = f"resnet{depth} b{per_chip_batch}x{n}"
    cfg = ResNetConfig(num_classes=num_classes, dtype=jnp.bfloat16,
                       depth=depth)
    params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)
    step, opt = build_dp_step(
        mesh, lambda p, x, y: resnet_loss(p, stats, x, y, cfg)[0],
        optax.sgd(0.01, momentum=0.9), n_batch_args=2)
    params = place(mesh, params, P())
    opt_state = place(mesh, opt.init(params), P())
    batch = per_chip_batch * n
    images = place(mesh, jax.random.normal(
        jax.random.PRNGKey(1), (batch, image_size, image_size, 3),
        jnp.bfloat16), P("dp"))
    labels = place(mesh, jax.random.randint(
        jax.random.PRNGKey(2), (batch,), 0, num_classes), P("dp"))
    compiled = compile_step(name, step, params, opt_state, images, labels)
    return run_steps(name, compiled, params, opt_state, (images, labels),
                     steps)[0]


def phase_long_seq(mesh, *, seq: int, per_chip_batch: int, steps: int = 2,
                   model=BERT_LARGE, compare_sequences: int = 2,
                   tol: float = 2e-2):
    """The LM at a length where ``ops.attention.kernel_enabled`` selects
    the flash kernel for the per-chip shapes, then the forward loss of the
    trained weights on the batch's first ``compare_sequences`` sequences:
    Pallas kernel against XLA attention (forward only — XLA attention with
    its backward does not fit at 4096)."""
    import jax
    import numpy as np

    from horovod_tpu.models import transformer_loss
    from horovod_tpu.ops.attention import kernel_enabled
    from horovod_tpu.ops.pallas_kernels import _use_interpret

    n = mesh.devices.size
    cfg = lm_config(seq, **model)
    name = f"lm seq{seq} b{per_chip_batch}x{n}"
    if not kernel_enabled(seq, batch=per_chip_batch, heads=cfg.heads):
        raise AssertionError(
            f"{name}: the flash kernel was not selected "
            f"(HVDT_FLASH_ATTENTION="
            f"{os.environ.get('HVDT_FLASH_ATTENTION', '<unset>')!r})")

    def inspect(compiled, params, tokens):
        if not _use_interpret() and "tpu_custom_call" not in \
                compiled.as_text():
            raise AssertionError(
                f"{name}: no Mosaic custom call in the compiled step")

    _, params, tokens = run_lm(name, mesh, cfg, per_chip_batch * n, steps,
                               inspect)

    # Both attention paths on ONE device: a Mosaic kernel outside
    # shard_map cannot be partitioned over the mesh the weights sit on.
    params = jax.device_put(params, mesh.devices.flat[0])
    toks = np.asarray(tokens[:compare_sequences])
    fwd = {}
    for mode in ("on", "off"):
        with env_knob("HVDT_FLASH_ATTENTION", mode):
            fwd[mode] = float(jax.jit(
                lambda p, t: transformer_loss(p, t, cfg))(params, toks))
    rel = abs(fwd["on"] - fwd["off"]) / abs(fwd["off"])
    say(f"[{name}] forward loss on {compare_sequences} sequences: "
        f"pallas {fwd['on']:.5f} vs xla {fwd['off']:.5f} (rel {rel:.2e})")
    if not rel < tol:
        raise AssertionError(
            f"{name}: flash and XLA attention disagree: {fwd}")
    return fwd


# ---------------------------------------------------------------------------
# More than one chip: who did the work, whose exchange, is it right.
# ---------------------------------------------------------------------------


def assert_sharded_over(array, n: int) -> None:
    devs = {s.device for s in array.addressable_shards}
    if len(devs) != n:
        raise AssertionError(
            f"batch sits on {len(devs)} device(s), expected {n}")


def assert_even_peak_memory(devices, tolerance: float = 0.25):
    """Every device's lifetime peak within ``tolerance`` of device 0's."""
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    say("peak_bytes_in_use per device: "
        + " ".join(f"{p / 2**30:.2f}GiB" for p in peaks)
        + f" (limit {devices[0].memory_stats()['bytes_limit'] / 2**30:.2f}"
        "GiB)")
    for d, p in zip(devices, peaks):
        if abs(p - peaks[0]) > tolerance * peaks[0]:
            raise AssertionError(
                f"{d} peaked at {p} bytes, device 0 at {peaks[0]}")
    return peaks


_SHAPE_RE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_ALLREDUCE_RE = re.compile(
    r"=\s+(?P<shape>.*?)\s+all-reduce(?:-start)?\((?P<rest>.*)$")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}


def hlo_allreduces(hlo_text: str):
    """(operand bytes, replica-group size) of every all-reduce (or
    all-reduce-start) in an HLO module's text."""
    out = []
    for line in hlo_text.splitlines():
        m = _ALLREDUCE_RE.search(line)
        if not m:
            continue
        nbytes = 0
        for dtype, dims in _SHAPE_RE.findall(m.group("shape")):
            count = 1
            for d in filter(None, dims.split(",")):
                count *= int(d)
            nbytes += count * _DTYPE_BYTES[dtype]
        rest = m.group("rest")
        g = re.search(r"replica_groups=\{\{([\d,]*)\}", rest)
        if g:
            group = len(g.group(1).split(","))
        else:
            g = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
            group = int(g.group(2)) if g else 0
        out.append((nbytes, group))
    return out


def check_exchange(name: str, compiled, params, n: int) -> None:
    """The compiled step's all-reduces span all ``n`` replicas and carry
    the gradient pytree's bytes.  XLA's combiner may merge buckets, so the
    count is printed beside the bucket plan's, not compared."""
    import jax

    from horovod_tpu.ops.device import fused_allreduce_buckets

    leaves = jax.tree.leaves(params)
    grad_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    plan = fused_allreduce_buckets(leaves, None)
    found = hlo_allreduces(compiled.as_text())
    over_all = [b for b, g in found if g == n]
    say(f"[{name}] HLO all-reduces: {len(found)} ({len(over_all)} over "
        f"{n} replicas, {sum(over_all)} bytes); bucket plan: {len(plan)} "
        f"buckets, gradient pytree {grad_bytes} bytes")
    # The scalar loss pmean rides along, hence the small allowance.
    if not grad_bytes <= sum(over_all) <= grad_bytes * 1.001 + 4096:
        raise AssertionError(
            f"{name}: all-reduces over {n} replicas carry "
            f"{sum(over_all)} bytes, the gradient pytree is {grad_bytes}")


def phase_dp_matches_single(devices, *, seq: int, global_batch: int,
                            steps: int = 3, model=BERT_LARGE, dtype=None):
    """The LM from one seed on the dp=n mesh and on a one-device mesh:
    first-step loss equal to 1e-3 relative, last to 2e-2.  (ResNet's local
    batch-norm statistics make it unfit for this check.)"""
    import numpy as np
    from jax.sharding import Mesh

    cfg = lm_config(seq, dtype=dtype, **model)
    runs = {}
    for label, devs in (("dp", devices), ("one", devices[:1])):
        mesh = Mesh(np.asarray(devs, dtype=object), ("dp",))
        runs[label] = run_lm(
            f"lm seq{seq} gb{global_batch} on {len(devs)}", mesh, cfg,
            global_batch, steps)[0]
    for idx, tol in ((0, 1e-3), (-1, 2e-2)):
        a, b = runs["dp"][idx], runs["one"][idx]
        if abs(a - b) > tol * abs(b):
            raise AssertionError(
                f"dp={len(devices)} loss {a} vs one-device {b} at step "
                f"{idx} (tolerance {tol}): {runs}")
    say(f"dp={len(devices)} matches one device: {runs}")
    return runs


# ---------------------------------------------------------------------------
# Every pallas_call the package ships, once through the compiler, against
# the jnp reference beside it.
# ---------------------------------------------------------------------------


def _close(name, got, want, *, rtol, atol):
    """allclose on the device (the outputs are up to 100 MB each): only
    the worst excess over ``atol + rtol * |want|`` comes to the host."""
    import jax
    import jax.numpy as jnp

    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        excess = float(jnp.max(jnp.abs(g - w) - (atol + rtol * jnp.abs(w))))
        if not excess <= 0:         # also catches NaN
            raise AssertionError(
                f"{name}: off the reference by {excess} beyond "
                f"rtol={rtol}, atol={atol}")


def _qkv(b, l, h, d, seed=0):
    """bf16 q, k, v and an upstream gradient, [B, L, H, D]."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (b, l, h, d), jnp.bfloat16)
                 for k in ks)


def kernel_flash_forward(*, batch=8, seq=4096, heads=16, head_dim=64):
    """The local forward (pallas_kernels._flash_local_call) at the blocks
    flash_attention itself chooses, ``out`` and the logsumexp.  The default
    shape is the benchmark cell's (lm24x1024_s4096_b8): a block choice that
    Mosaic cannot lower fails here and not in the benchmark.  The reference
    takes one sequence at a time (its f32 score square is 1 GiB each)."""
    import jax

    from horovod_tpu.ops.pallas_kernels import (_flash_fwd_core,
                                                _forward_blocks,
                                                attention_reference)

    q, k, v, _ = _qkv(batch, seq, heads, head_dim)
    blocks = _forward_blocks(seq, seq, head_dim, q.dtype)
    got = jax.jit(lambda q, k, v: _flash_fwd_core(
        q, k, v, True, head_dim ** -0.5, *blocks))(q, k, v)
    want = jax.jit(lambda q, k, v: jax.lax.map(
        lambda qkv: jax.tree.map(
            lambda x: x[0],
            attention_reference(*(x[None] for x in qkv), with_lse=True)),
        (q, k, v)))(q, k, v)
    _close("flash_attention", got[0], want[0], rtol=2e-2, atol=2e-2)
    # f32 statistic of bf16 products accumulated in f32: 5e-5 off on the
    # v5e at this shape (PR 25).
    _close("flash_attention logsumexp", got[1], want[1], rtol=1e-3,
           atol=1e-3)


def kernel_flash_ring_step(*, batch=1, seq=2048, heads=16, head_dim=64):
    """The ring block kernel (HVDT_RING_PALLAS): flash_block_update with
    traced global offsets, one fully visible and one diagonal block."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_kernels import flash_block_update
    from horovod_tpu.parallel.ring_attention import _block_update

    q, k, v, _ = _qkv(batch, seq, heads, head_dim)
    scale = head_dim ** -0.5
    acc = jnp.zeros((batch, seq, heads, head_dim), jnp.float32)
    m = jnp.full((batch, heads, seq), -1e30, jnp.float32)
    s = jnp.zeros((batch, heads, seq), jnp.float32)

    def normalized(update):
        # acc is the unnormalized sum over up to ``seq`` keys: compare what
        # attention returns (acc / row_sum) and the softmax statistics.
        acc_, m_, s_ = update
        return (acc_ / jnp.maximum(s_, 1e-30).transpose(0, 2, 1)[..., None],
                m_, jnp.log(jnp.maximum(s_, 1e-30)))

    @jax.jit
    def kern(q_off, k_off):
        return normalized(flash_block_update(
            q, k, v, acc, m, s, q_offset=q_off, k_offset=k_off,
            causal=True, scale=scale))

    @jax.jit
    def ref(q_off, k_off):
        mask = ((q_off + jnp.arange(seq))[:, None]
                >= (k_off + jnp.arange(seq))[None, :])[None, None]
        return normalized(_block_update(q, k, v, acc, m, s, mask, scale))

    for q_off, k_off in ((seq, 0), (seq, seq)):
        _close("flash_block_update", kern(q_off, k_off), ref(q_off, k_off),
               rtol=2e-2, atol=2e-2)


def _attention_grads(fn, q, k, v, do):
    import jax
    import jax.numpy as jnp

    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                * do.astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)


def _reference_grads_by_head(q, k, v, do, window=None, with_out=False):
    """dq, dk, dv of sum(attention_reference * do), one (sequence, head)
    at a time: a head's f32 score square is 64 MiB at seq 4096 and its
    gradient holds a handful of them.  ``with_out`` puts the reference's
    output before them."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_kernels import attention_reference

    b, l, h, d = q.shape

    def one(qkvdo):
        q1, k1, v1, do1 = (x[None, :, None, :] for x in qkvdo)
        ref = functools.partial(attention_reference, window=window)
        grads = jax.grad(
            lambda q, k, v: jnp.sum(
                ref(q, k, v).astype(jnp.float32)
                * do1.astype(jnp.float32)), argnums=(0, 1, 2))(q1, k1, v1)
        if with_out:
            grads = (ref(q1, k1, v1),) + grads
        return tuple(g[0, :, 0, :] for g in grads)

    by_head = jax.jit(lambda *xs: jax.lax.map(one, tuple(
        x.transpose(0, 2, 1, 3).reshape(b * h, l, d) for x in xs)))
    return tuple(g.reshape(b, h, l, d).transpose(0, 2, 1, 3)
                 for g in by_head(q, k, v, do))


def kernel_flash_backward(*, batch=8, seq=4096, heads=16, head_dim=64):
    """The local backward (pallas_kernels._flash_local_bwd_call) as
    flash_attention's gradient runs it, at the blocks _backward_blocks
    chooses.  The default shape is the benchmark cell's
    (lm24x1024_s4096_b8), like kernel_flash_forward's."""
    from horovod_tpu.ops.pallas_kernels import flash_attention

    q, k, v, do = _qkv(batch, seq, heads, head_dim)
    _close("flash_attention backward",
           _attention_grads(flash_attention, q, k, v, do),
           _reference_grads_by_head(q, k, v, do), rtol=5e-2, atol=5e-2)


def _flash_grouped_case(name, *, batch, seq, heads, kv_heads, head_dim,
                        window):
    """flash_attention forward and backward with grouped queries (the kv
    head picked in the index map, dk / dv per query head summed by the
    caller) and an optional window, against attention_reference head by
    head with each head's own copy of its group's k and v."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_kernels import flash_attention

    q, _, _, do = _qkv(batch, seq, heads, head_dim)
    _, k, v, _ = _qkv(batch, seq, kv_heads, head_dim, seed=1)
    group = heads // kv_heads
    fn = functools.partial(flash_attention, window=window)
    out = jax.jit(fn)(q, k, v)
    grads = _attention_grads(fn, q, k, v, do)
    want_out, dq, dk, dv = _reference_grads_by_head(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), do,
        window, with_out=True)
    dk, dv = (x.reshape(batch, seq, kv_heads, group, head_dim).sum(3)
              for x in (dk.astype(jnp.float32), dv.astype(jnp.float32)))
    _close(name, out, want_out, rtol=2e-2, atol=2e-2)
    # dk / dv are sums over a group of bf16 per-head results.
    _close(f"{name} backward", grads, (dq, dk, dv), rtol=5e-2,
           atol=5e-2 * group ** 0.5)


def kernel_flash_gqa128(*, batch=2, seq=8192, heads=48, kv_heads=8,
                        head_dim=128):
    """The local kernels full-causal at head_dim 128 with grouped queries:
    the full-attention layers' calls of laguna_xs2_s8192 (blocks 4096
    forward, 2048 backward)."""
    _flash_grouped_case("flash_attention gqa128", batch=batch, seq=seq,
                        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                        window=None)


def kernel_flash_gqa256(*, batch=1, seq=16384, heads=16, kv_heads=2,
                        head_dim=256):
    """The local kernels full-causal at head_dim 256, groups of 8: the
    full-attention layer's calls of qwen3_next_s16384 (a block is one head
    of 256 lanes; blocks of 2048 forward and backward at this length)."""
    _flash_grouped_case("flash_attention gqa256", batch=batch, seq=seq,
                        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                        window=None)


def kernel_flash_window(*, batch=2, seq=8192, heads=64, kv_heads=8,
                        head_dim=128, window=512):
    """The windowed local kernels (hvdt.kernel.flash_win_fwd / _bwd): the
    sliding-window layers' calls of laguna_xs2_s8192, blocks of 512 from
    the window."""
    _flash_grouped_case("flash_attention window", batch=batch, seq=seq,
                        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                        window=window)


def kernel_flash_block_diffusion(*, batch=1, seq=8192, heads=32, kv_heads=4,
                                 head_dim=128, block=4):
    """The local kernels under the block mask (hvdt.kernel.flash_bd_fwd /
    _bwd): the calls of sdar_30b_s8192, on the 2 x seq rows [noisy ;
    clean] of a sequence, against attention_reference under the same mask
    head by head."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_kernels import (attention_reference,
                                                flash_attention)

    q, _, _, do = _qkv(batch, 2 * seq, heads, head_dim)
    _, k, v, _ = _qkv(batch, 2 * seq, kv_heads, head_dim, seed=1)
    group = heads // kv_heads
    fn = functools.partial(flash_attention, block_diffusion=block)
    out = jax.jit(fn)(q, k, v)
    grads = _attention_grads(fn, q, k, v, do)

    @jax.jit
    def one_head(q, k, v, do):          # [B, 2L, 1, D] each
        ref = functools.partial(attention_reference, block_diffusion=block)
        out, vjp = jax.vjp(ref, *(x.astype(jnp.float32)
                                  for x in (q, k, v)))
        return (out,) + vjp(do.astype(jnp.float32))

    want = [one_head(q[:, :, h:h + 1], k[:, :, h // group:h // group + 1],
                     v[:, :, h // group:h // group + 1], do[:, :, h:h + 1])
            for h in range(heads)]
    want_out, dq, dk, dv = (jnp.concatenate(xs, axis=2)
                            for xs in zip(*want))
    dk, dv = (x.reshape(batch, 2 * seq, kv_heads, group, head_dim).sum(3)
              for x in (dk, dv))
    _close("flash_attention block diffusion", out, want_out, rtol=2e-2,
           atol=2e-2)
    _close("flash_attention block diffusion backward", grads, (dq, dk, dv),
           rtol=5e-2, atol=5e-2 * group ** 0.5)


def kernel_flash_grad_block(*, batch=1, seq=2048, heads=16, head_dim=64):
    """flash_grad_block's dq and dk/dv pallas_calls: the ring step's
    backward (parallel/ring_attention.py), here over one whole sequence
    from the local forward's out and logsumexp."""
    import jax

    from horovod_tpu.ops.pallas_kernels import (_flash_fwd_core,
                                                _forward_blocks,
                                                attention_reference,
                                                flash_grad_block)

    q, k, v, do = _qkv(batch, seq, heads, head_dim)
    blocks = _forward_blocks(seq, seq, head_dim, q.dtype)

    @jax.jit
    def got(q, k, v, do):
        out, lse = _flash_fwd_core(q, k, v, True, head_dim ** -0.5, *blocks)
        return flash_grad_block(q, k, v, do, out, lse, causal=True)

    _close("flash_grad_block", got(q, k, v, do),
           _attention_grads(attention_reference, q, k, v, do),
           rtol=5e-2, atol=5e-2)


def _conv_inputs(batch, hw, cin, cout):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (batch, hw, hw, cin), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (cin, cout)) * cin ** -0.5).astype(
        jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (cout,))
    bias = 0.1 * jax.random.normal(ks[3], (cout,))
    return x, w, scale, bias


def kernel_conv_bn_relu(*, batch=128, hw=14, cin=256, cout=1024):
    """ops/conv_fused.matmul_bn_relu at a ResNet-50 stage-3 1x1 conv."""
    import jax

    from horovod_tpu.ops import conv_fused as cf

    args = _conv_inputs(batch, hw, cin, cout)
    _close("conv1x1_bn_relu", jax.jit(cf.conv1x1_bn_relu)(*args),
           jax.jit(cf.conv1x1_bn_relu_reference)(*args),
           rtol=2e-2, atol=2e-2)


def kernel_conv_bn_train(*, batch=128, hw=14, cin=256, cout=1024):
    """ops/conv_fused.matmul_batch_stats (train-form BN), same shape."""
    import jax

    from horovod_tpu.ops import conv_fused as cf

    args = _conv_inputs(batch, hw, cin, cout)
    _close("conv1x1_bn_train", jax.jit(cf.conv1x1_bn_train)(*args),
           jax.jit(cf.conv1x1_bn_train_reference)(*args),
           rtol=2e-2, atol=2e-2)


def kernel_fused_adam(*, shape=(24, 1024, 1024)):
    """ops/optim_kernels fused_adam on a bert-large leaf, against the XLA
    lowering of the same update."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import optim_kernels as ok

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p, g, m = (jax.random.normal(k, shape) for k in ks[:3])
    v = jnp.square(jax.random.normal(ks[3], shape))
    sc = jnp.asarray([3e-4, 1.0 / (1 - 0.9 ** 3), 1.0 / (1 - 0.999 ** 3)],
                     jnp.float32)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, eps_root=0.0, wd=1e-4)
    if not ok.fused_update_eligible(g, p.dtype, m.dtype, v.dtype):
        raise AssertionError(f"adam leaf {shape} not kernel-eligible")
    want = jax.jit(lambda *a: ok._adam_leaf_xla(*a, **kw))(p, g, m, v, sc)
    _close("fused_adam",
           jax.jit(lambda *a: ok._adam_leaf_fused(*a, **kw))(p, g, m, v,
                                                             sc),
           want, rtol=1e-5, atol=1e-7)


def kernel_fused_sgd(*, shape=(3, 3, 512, 512)):
    """ops/optim_kernels fused_sgd on a ResNet-50 leaf."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import optim_kernels as ok

    g, m = (jax.random.normal(k, shape)
            for k in jax.random.split(jax.random.PRNGKey(0)))
    sc = jnp.asarray([0.01], jnp.float32)
    kw = dict(momentum=0.9, nesterov=False)
    if not ok.fused_update_eligible(g, m.dtype):
        raise AssertionError(f"sgd leaf {shape} not kernel-eligible")
    want = jax.jit(lambda *a: ok._sgd_leaf_xla(*a, **kw))(g, m, sc)
    _close("fused_sgd",
           jax.jit(lambda *a: ok._sgd_leaf_fused(*a, **kw))(g, m, sc),
           want, rtol=1e-5, atol=1e-7)


def kernel_gdn_inverse(*, matrices=8192, chunk=64):
    """ops/pallas_kernels unit_lower_inverse_slabs against XLA's loop of the
    same substitution, on the [C, C, matrices] slabs of one call of
    qwen3_next_s16384's scan (256 chunks x 32 value heads)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_delta as gd
    from horovod_tpu.ops import pallas_kernels as pk

    if not pk.unit_lower_inverse_tiles(chunk):
        raise AssertionError(f"chunk {chunk} does not tile the kernel")
    lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)[:, :, None]
    cols = jnp.where(lower, 0.3 * jax.random.normal(
        jax.random.PRNGKey(0), (chunk, chunk, matrices)), 0.0)
    _close("gdn_inverse", jax.jit(pk.unit_lower_inverse_slabs)(cols),
           jax.jit(gd._inverse_slabs_loop)(cols), rtol=1e-4, atol=1e-4)


def kernel_gdn_chunk(*, seq=16384, key_heads=16, value_heads=32,
                     head_dim=128):
    """ops/pallas_kernels gdn_chunk_forward / gdn_chunk_backward (the
    Mosaic schedule of the scan's chunk-local passes and of their rule)
    against XLA's schedule of the same rule (ops/gated_delta
    _chunk_fwd_jax / _chunk_bwd_jax) on the rows of one linear layer of
    qwen3_next_s16384: the five outputs, the inverses and the three
    cotangents, each within a hundredth of its largest entry (bf16
    outputs of float32 sums in two orders)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_delta as gd
    from horovod_tpu.ops import pallas_kernels as pk

    chunk = gd.CHUNK
    dims = (key_heads, value_heads, head_dim, head_dim)
    if not pk.gdn_chunk_tiles(seq, dims, chunk):
        raise AssertionError(f"rows {seq} x {dims} do not tile the kernels")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    width = (2 * key_heads + value_heads) * head_dim
    qkv = jax.nn.silu(jax.random.normal(ks[0], (1, seq, width))
                      ).astype(jnp.bfloat16)
    g = -0.1 * jnp.exp(jax.random.normal(ks[1], (1, seq, value_heads)))
    gc = gd._log_decay(g, seq // chunk, chunk, dims, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], gc.shape))

    def close(name, got, want):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise AssertionError(f"{name}[{i}]: {a.shape} {a.dtype} "
                                     f"against {b.shape} {b.dtype}")
            top = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
            _close(f"{name}[{i}]", a, b, rtol=1e-2, atol=1e-2 * top)

    out, t = jax.jit(lambda *a: gd._chunk_fwd_jax(*a, dims, chunk))(
        qkv, gc, beta)
    # the kernels leave a key head's R inverses side by side, [C, R C]
    packed = jnp.moveaxis(t, 3, 4).reshape(t.shape[:3] + (chunk, -1))
    close("gdn_chunk_fwd", jax.jit(
        lambda *a: pk.gdn_chunk_forward(*a, dims, chunk))(qkv, gc, beta),
        (out, packed))
    cts = tuple((0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
                for k, x in zip(ks[3:], out))
    close("gdn_chunk_bwd", jax.jit(
        lambda *a: pk.gdn_chunk_backward(*a, dims, chunk))(
            qkv, gc, beta, packed, cts),
        jax.jit(lambda *a: gd._chunk_bwd_jax(*a, dims, chunk))(
            qkv, gc, beta, t, cts))


def kernel_ssd_chunk(*, seq=8192, heads=64, head_dim=64, state=128,
                     chunk=256):
    """ops/pallas_kernels ssd_chunk_state / ssd_chunk_out and their
    backwards (the Mosaic schedule of the Mamba-2 scan's chunk passes and
    of their rules) against XLA's schedule of the same rules (ops/ssd
    _state_fwd_jax / _out_fwd_jax / _state_bwd_jax / _out_bwd_jax) on the
    rows of one state-space layer of granite_h_micro_s8192: each output and
    every cotangent within a hundredth of its largest entry (bf16 operands
    of float32 sums in two orders)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.ops import ssd

    dims = (heads, head_dim, 1, state)
    if not pk.ssd_chunk_tiles(seq, dims, chunk):
        raise AssertionError(f"rows {seq} x {dims} do not tile the kernels")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    inner = heads * head_dim
    xbc = jax.nn.silu(jax.random.normal(ks[0], (1, seq, inner + 2 * state))
                      ).astype(jnp.bfloat16)
    # time steps log-uniform in [1e-3, 1e-1], as the model draws them
    delta = jnp.exp(jax.random.uniform(
        ks[1], (1, seq, heads), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    a = -jnp.arange(1.0, heads + 1)
    l = jnp.cumsum((delta * a).reshape(1, -1, chunk, heads), 2).reshape(
        delta.shape)
    s_in = jax.random.normal(
        ks[2], (seq // chunk, 1, inner, state)).astype(jnp.bfloat16)
    skip = jax.random.normal(ks[3], (1, inner))

    def both(kernel, plain, *ins):
        got = jax.jit(lambda *t: kernel(*t, dims, chunk))(*ins)
        want = jax.jit(lambda *t: plain(*t, dims, chunk))(*ins)
        for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            if g.shape != w.shape or g.dtype != w.dtype:
                raise AssertionError(
                    f"{kernel.__name__}[{i}]: {g.shape} {g.dtype} "
                    f"against {w.shape} {w.dtype}")
            top = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
            _close(f"{kernel.__name__}[{i}]", g, w, rtol=1e-2,
                   atol=1e-2 * top)
        return got

    own = both(pk.ssd_chunk_state, ssd._state_fwd_jax, xbc, delta, l)
    both(pk.ssd_chunk_state_bwd, ssd._state_bwd_jax, xbc, delta, l,
         0.1 * jax.random.normal(ks[4], own.shape))
    y = both(pk.ssd_chunk_out, ssd._out_fwd_jax, xbc, delta, l, s_in, skip)
    both(pk.ssd_chunk_out_bwd, ssd._out_bwd_jax, xbc, delta, l, s_in, skip,
         0.1 * jax.random.normal(ks[5], y.shape))


def kernel_moe_sum_rows(*, tokens=16384, picks=10, width=2048, routed=512,
                        held=32):
    """ops/pallas_kernels moe_sum_rows against XLA's gather and sum
    (parallel/moe._sum_held_rows_xla) on the sorted buffer of one sparse
    layer of qwen3_next_s16384 (163,840 rows of 2,048, one pick in sixteen
    held), the rows behind those that landed NaN: nothing of them may
    reach a token."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.parallel import moe

    _, experts = jax.lax.top_k(jax.random.uniform(
        jax.random.PRNGKey(0), (tokens, routed)), picks)
    local = experts.reshape(-1)
    landed = local < held
    segment = jnp.where(landed, local, held)
    inverse = jnp.argsort(jnp.argsort(segment, stable=True))
    rows = jax.random.normal(jax.random.PRNGKey(1), (tokens * picks, width),
                             jnp.bfloat16)
    rows = jnp.where((jnp.arange(tokens * picks) < landed.sum())[:, None],
                     rows, jnp.nan)
    landed = landed.reshape(tokens, picks)
    if pk._moe_sum_rows_blocks(tokens, picks, width, held + 1,
                               rows.dtype) is None:
        raise AssertionError("the kernel has no blocks for this shape")
    got = jax.jit(lambda r, i, h, s: moe._sum_held_rows(
        r, i, h, s, held + 1))(rows, inverse, landed, segment)
    _close("moe_sum_rows", got,
           jax.jit(moe._sum_held_rows_xla)(rows, inverse, landed),
           rtol=1e-2, atol=1e-2)


def kernel_rope(*, batch=8, seq=4096, heads=16, head_dim=64):
    """ops/pallas_kernels rope on the [B, L, H*D] rows of
    lm24x1024_s4096_b8's q: the Mosaic call and its backward (the same
    call at the negated angle) against the function on heads [B, L, H, D],
    XLA's half-slicing form with JAX's own transpose."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import pallas_kernels as pk

    q, _, _, do = _qkv(batch, seq, heads, head_dim)
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    cos, sin, half = tfm._rope_tables(positions, tfm.Rope(), head_dim)

    def rows(x):
        return x.reshape(batch, seq, heads * head_dim)

    # off the TPU (the toy rehearsal) the chooser has no block: one here
    block = pk._rope_block(rows(q), head_dim) or (
        1, seq, max(head_dim, 128))

    def both(f, x, g):
        y, pull = jax.vjp(f, x)
        return y, pull(g)[0]

    got = jax.jit(lambda x, g: both(
        lambda x: pk._rope_rows(x, cos, sin, half, block), x, g))(
            rows(q), rows(do))
    want = jax.jit(lambda x, g: both(
        lambda x: pk.rope(x, cos, sin, half), x, g))(q, do)
    _close("rope", got, tuple(rows(w) for w in want), rtol=2 ** -7,
           atol=2 ** -6)


def _quant_roundtrip(name, quant, dequant, eligible, size, block):
    """One quantize/dequantize pair on a flat gradient bucket, Pallas
    against the XLA lowering.  A code may differ by one where the two
    lowerings round a scale differently, so values are compared to
    within one quantization step."""
    import jax
    import jax.numpy as jnp

    if not eligible(size, block):
        raise AssertionError(f"{name}: {size}/{block} not kernel-eligible")
    x = jax.random.normal(jax.random.PRNGKey(0), (size,), jnp.float32)

    def roundtrip(use_kernels):
        @jax.jit
        def f(x):
            q, s = quant(x, block, use_kernels)
            return dequant(q, s, block, use_kernels), s
        return f(x)

    got, got_s = roundtrip(True)
    want, want_s = roundtrip(False)
    _close(f"quant {name} scales", got_s, want_s, rtol=1e-6, atol=0)
    step = float(jnp.max(want_s))
    err = float(jnp.max(jnp.abs(got - want)))
    if not err <= step * 1.001:
        raise AssertionError(
            f"quant {name}: kernel and XLA differ by {err}, one "
            f"quantization step is {step}")
    if not float(jnp.max(jnp.abs(want - x))) <= step * 0.5001:
        raise AssertionError(f"quant {name}: round trip off")


def kernel_quant_int8(*, size=1 << 24, block=256):
    """quant/kernels int8 pair on one 64 MiB gradient bucket."""
    from horovod_tpu.quant import kernels as qk

    _quant_roundtrip("int8", qk.quantize_flat, qk.dequantize_flat,
                     qk.quant_kernel_eligible, size, block)


def kernel_quant_int4(*, size=1 << 24, block=256):
    """quant/kernels int4 pair, same bucket."""
    from horovod_tpu.quant import kernels as qk

    _quant_roundtrip("int4", qk.quantize_flat_int4, qk.dequantize_flat_int4,
                     qk.quant_kernel_eligible_int4, size, block)


KERNELS = (kernel_flash_forward, kernel_flash_ring_step,
           kernel_flash_backward, kernel_flash_gqa128, kernel_flash_gqa256,
           kernel_flash_window, kernel_flash_block_diffusion,
           kernel_flash_grad_block,
           kernel_conv_bn_relu, kernel_conv_bn_train, kernel_gdn_inverse,
           kernel_gdn_chunk, kernel_ssd_chunk,
           kernel_rope, kernel_moe_sum_rows,
           kernel_fused_adam, kernel_fused_sgd, kernel_quant_int8,
           kernel_quant_int4)


def phase_kernels(kernels=KERNELS, **sizes):
    """Run each kernel check; ``sizes`` maps a check's name to its
    keyword arguments (toy sizes on the CPU)."""
    for fn in kernels:
        t0 = time.perf_counter()
        fn(**sizes.get(fn.__name__, {}))
        say(f"[kernels] {fn.__name__} ok "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")


# ---------------------------------------------------------------------------
# Start-up checks and the run itself.
# ---------------------------------------------------------------------------


def main() -> int:
    from horovod_tpu.ops import overlap
    from horovod_tpu.step_pipeline import enable_compilation_cache
    from horovod_tpu.telemetry import compile_ledger
    from horovod_tpu.telemetry.step_stats import peak_flops_for

    import jax

    if overlap._jax_backend_initialized():
        raise AssertionError("a JAX backend is up before hvd.init()")
    # Floor at 0.5 s, not the knob's 1 s: a compile that takes 0.9 s in
    # one run and 1.2 s in the next would otherwise never be found again.
    cache_dir = enable_compilation_cache(
        default=os.path.join(ROOT, ".xla_cache"), min_compile_secs=0.5)
    # What the persistent cache did in this process: the library's own
    # account (jax.monitoring), installed by the call above.
    compiles = compile_ledger.get_ledger()

    import horovod_tpu as hvd

    hvd.init()
    dev = jax.devices()[0]
    n = len(jax.devices())
    say(f"jax {jax.__version__}; platform: {dev.platform}; device_kind: "
        f"{dev.device_kind}; devices: {n}; compile cache: {cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); nothing "
              "was run", file=sys.stderr)
        return 1
    if peak_flops_for(dev.device_kind) == (None, None):
        print(f"chip_smoke: device kind {dev.device_kind!r} is not in "
              "telemetry/step_stats.PEAK_BY_DEVICE_KIND; nothing was run",
              file=sys.stderr)
        return 1
    if not cache_dir:
        raise AssertionError("the compilation cache did not engage")
    # hvd.init() put the latency-hiding flags into LIBTPU_INIT_ARGS while
    # no backend was up (checked above); libtpu aborts on a flag it does
    # not accept, so reaching this line means it took all of them.
    init_args = os.environ.get("LIBTPU_INIT_ARGS", "")
    for flag in overlap._ASYNC_COLLECTIVE_FLAGS:
        if flag not in init_args:
            raise AssertionError(f"{flag} not in LIBTPU_INIT_ARGS")
    say(f"LIBTPU_INIT_ARGS: {init_args}")
    if os.environ.get("HVDT_FLASH_ATTENTION"):
        raise AssertionError("HVDT_FLASH_ATTENTION is set; the smoke "
                             "checks what auto selects")

    mesh = hvd.mesh()
    devices = list(mesh.devices.flat)
    if mesh.axis_names != ("dp",) or len(devices) != n:
        raise AssertionError(f"default mesh is {mesh}")

    def inspect_lm(compiled, params, tokens):
        assert_sharded_over(tokens, n)
        if n > 1:
            check_exchange("lm seq512", compiled, params, n)

    phase_lm(mesh, seq=512, per_chip_batch=LM_PER_CHIP_BATCH,
             inspect=inspect_lm)
    assert_even_peak_memory(devices)
    phase_resnet(mesh, per_chip_batch=128)
    phase_long_seq(mesh, seq=4096, per_chip_batch=8)
    if n > 1:
        phase_dp_matches_single(devices, seq=512, global_batch=32)
    phase_kernels()

    cached = os.listdir(cache_dir)
    if not cached:
        raise AssertionError(f"compile cache {cache_dir} is empty")
    slow = {p.name: round(p.compile_s, 1)
            for p in compiles.programs.values() if p.compile_s >= 1.0}
    say(f"compile cache: {compiles.requests} requests, "
        f"{compiles.builds(hit=True)} hits, {len(slow)} programs with "
        f">= 1 s of cache-miss compiles {slow}; "
        f"{len(cached)} entries in {cache_dir}")
    if "horovod_tpu.native" in sys.modules:
        raise AssertionError("the jit path loaded the native core")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
