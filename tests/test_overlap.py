"""Overlap scheduling layer (horovod_tpu/ops/overlap.py) — identity
contract, bitwise numerics vs the monolithic path, int8-wire error bound,
lowered-HLO interleaving, the pipelined optimizer leg, the autotune
overlap dimension, and double-buffered device prefetch.  All CPU on the
simulated 8-device mesh."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu import optimizer as hvd_opt
from horovod_tpu import step_pipeline
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.data.loader import AsyncDataLoader, prefetch_to_device
from horovod_tpu.ops import device as dev
from horovod_tpu.ops import overlap as ovl
from horovod_tpu.ops.optim_kernels import fused_sgd


def _smap_kw():
    """check_rep/check_vma off where the kwarg exists: pre-vma JAX has
    no replication rule for pallas_call (same pattern as
    tests/test_optim_kernels.py)."""
    sig = inspect.signature(jax.shard_map).parameters
    if "check_rep" in sig:
        return {"check_rep": False}
    if "check_vma" in sig:
        return {"check_vma": False}
    return {}


@pytest.fixture()
def overlap_on(monkeypatch):
    monkeypatch.setenv("HVDT_OVERLAP", "on")
    ovl.reset()
    ovl.reset_accounting()
    yield ovl.get_scheduler()
    ovl.reset()


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(8, 64, 3), jnp.float32),
        "b": jnp.asarray(rng.randn(8, 300), jnp.float32),
        "c": jnp.asarray(rng.randn(8, 17), jnp.float32),
    }


# ---------------------------------------------------------------------------
# zero-wrapper identity: HVDT_OVERLAP unset returns the exact
# pre-existing code objects (same contract as telemetry/faults)
# ---------------------------------------------------------------------------


class TestIdentity:
    def test_unset_scheduler_is_none(self, monkeypatch):
        monkeypatch.delenv("HVDT_OVERLAP", raising=False)
        ovl.reset()
        assert ovl.get_scheduler() is None
        assert not ovl.enabled()

    def test_unset_exchange_fn_is_fused_allreduce(self, monkeypatch):
        monkeypatch.delenv("HVDT_OVERLAP", raising=False)
        ovl.reset()
        assert ovl.exchange_fn() is dev.fused_allreduce

    def test_off_values_stay_off(self, monkeypatch):
        for off in ("", "0", "off", "false"):
            monkeypatch.setenv("HVDT_OVERLAP", off)
            ovl.reset()
            assert ovl.get_scheduler() is None
        ovl.reset()

    def test_on_builds_scheduler(self, overlap_on):
        assert overlap_on is not None
        assert ovl.exchange_fn() == overlap_on.exchange


# ---------------------------------------------------------------------------
# schedule planning
# ---------------------------------------------------------------------------


class TestSchedule:
    def test_reverse_topological_order(self):
        leaves = [jnp.ones((1024,), jnp.float32) for _ in range(4)]
        sched = ovl.overlap_schedule(leaves, threshold_bytes=8192)
        # 4 KiB leaves, 8 KiB buckets: two buckets, LAST leaves first
        assert sched == [[3, 2], [1, 0]]

    def test_reuses_fused_allreduce_buckets(self):
        leaves = [jnp.ones((256 * i + 64,), jnp.float32)
                  for i in range(1, 5)]
        sched = ovl.overlap_schedule(leaves, threshold_bytes=4096)
        flat = sorted(i for b in sched for i in b)
        assert flat == [0, 1, 2, 3]
        n = len(leaves)
        rev = dev.fused_allreduce_buckets(list(reversed(leaves)), 4096)
        assert sched == [[n - 1 - i for i in b] for b in rev]

    def test_bucket_plan_deterministic_across_dtype_order(self):
        """Satellite: same leaves, any dtype interleaving → same plan."""
        rng = np.random.RandomState(0)
        f = [jnp.asarray(rng.randn(64), jnp.float32) for _ in range(3)]
        i = [jnp.asarray(rng.randint(0, 9, 32), jnp.int32)
             for _ in range(2)]
        h = [jnp.asarray(rng.randn(128), jnp.bfloat16)]

        def ident_plan(leaves):
            ids = {id(l): k for k, l in enumerate(leaves)}
            plan = dev.fused_allreduce_buckets(leaves, 1 << 20)
            return [[ids[id(leaves[j])] for j in b] for b in plan]

        # interleavings that preserve within-dtype relative order
        order1 = f[:1] + i[:1] + f[1:] + h + i[1:]
        order2 = i + h + f
        order3 = h + f + i
        key1 = [[order1[j] for j in b]
                for b in dev.fused_allreduce_buckets(order1, 1 << 20)]
        for other in (order2, order3):
            key2 = [[other[j] for j in b]
                    for b in dev.fused_allreduce_buckets(other, 1 << 20)]
            assert [[id(x) for x in b] for b in key1] == \
                   [[id(x) for x in b] for b in key2]

    def test_dtype_groups_in_canonical_order(self):
        a = [jnp.ones((8,), jnp.int32), jnp.ones((8,), jnp.float32)]
        b = [jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.int32)]
        pa = dev.fused_allreduce_buckets(a, 1 << 20)
        pb = dev.fused_allreduce_buckets(b, 1 << 20)
        # bfloat16 < float32 < int32 by name; group ORDER is canonical
        assert [str(a[i].dtype) for bkt in pa for i in bkt] == \
               [str(b[i].dtype) for bkt in pb for i in bkt]


class TestThresholdValidation:
    """Satellite: HVDT_FUSION_THRESHOLD garbage must not reach planning."""

    def test_env_nonpositive_clamps_to_default(self, monkeypatch):
        from horovod_tpu.common import config

        monkeypatch.setenv("HVDT_FUSION_THRESHOLD", "-5")
        assert dev._validated_threshold() == \
            config.KNOBS["HVDT_FUSION_THRESHOLD"].default

    def test_env_garbage_clamps_to_default(self, monkeypatch):
        from horovod_tpu.common import config

        monkeypatch.setenv("HVDT_FUSION_THRESHOLD", "not-a-number")
        assert dev._validated_threshold() == \
            config.KNOBS["HVDT_FUSION_THRESHOLD"].default

    def test_caller_zero_clamps(self):
        from horovod_tpu.common import config

        default = config.KNOBS["HVDT_FUSION_THRESHOLD"].default
        assert dev._validated_threshold(0) == default
        assert dev._validated_threshold(-1) == default
        assert dev._validated_threshold("junk") == default

    def test_valid_values_pass_through(self):
        assert dev._validated_threshold(4096) == 4096
        assert dev._validated_threshold("8192") == 8192

    def test_warns_once(self, monkeypatch, caplog):
        import logging

        monkeypatch.setattr(dev, "_threshold_warned", False)
        with caplog.at_level(logging.WARNING,
                             logger="hvdt.horovod_tpu.ops.device"):
            dev._validated_threshold(-3)
            dev._validated_threshold(-3)
        msgs = [r for r in caplog.records
                if "fusion threshold" in r.getMessage()]
        assert len(msgs) <= 1

    def test_bucket_planning_survives_garbage_threshold(self):
        leaves = [jnp.ones((64,), jnp.float32)]
        plan = dev.fused_allreduce_buckets(leaves, threshold_bytes=-7)
        assert plan == [[0]]


# ---------------------------------------------------------------------------
# numerics: bitwise-identical to the monolithic path (acceptance)
# ---------------------------------------------------------------------------


class TestExchangeNumerics:
    def test_bitwise_identical_f32_grads(self, mesh8, overlap_on):
        tree = _tree()

        def run(fused):
            def body(a, b, c):
                out = fused({"a": a[0], "b": b[0], "c": c[0]}, "dp",
                            ReduceOp.AVERAGE, threshold_bytes=512)
                return out["a"], out["b"], out["c"]

            return shard_map(body, mesh=mesh8, in_specs=(P("dp"),) * 3,
                             out_specs=(P(),) * 3)(
                                 tree["a"], tree["b"], tree["c"])

        got = run(overlap_on.exchange)
        want = run(dev.fused_allreduce)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_bitwise_identical_updated_params(self, mesh8, overlap_on,
                                              monkeypatch):
        """Full train-step parity: HVDT_OVERLAP=on routes
        allreduce_gradients through the scheduler and the updated params
        must be bitwise identical to the off path."""
        grads = _tree(3)
        params = jax.tree.map(lambda l: jnp.ones(l.shape[1:]), grads)

        def run():
            tx = hvd_opt.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                              threshold_bytes=512)
            state = tx.init(params)

            def body(a, b, c):
                u, _ = tx.update({"a": a[0], "b": b[0], "c": c[0]},
                                 state, params)
                p2 = optax.apply_updates(params, u)
                return p2["a"], p2["b"], p2["c"]

            return shard_map(body, mesh=mesh8, in_specs=(P("dp"),) * 3,
                             out_specs=(P(),) * 3)(
                                 grads["a"], grads["b"], grads["c"])

        on = run()
        monkeypatch.delenv("HVDT_OVERLAP")
        ovl.reset()
        assert ovl.get_scheduler() is None
        off = run()
        for g, w in zip(on, off):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_int8_wire_within_established_bound(self, mesh8, overlap_on):
        """Quantized wire through the pipelined start/finish split keeps
        the block-scale/2 per-stage bound (same tolerance family as
        tests/test_quant.py)."""
        rng = np.random.RandomState(5)
        w = jnp.asarray(rng.randn(8, 33, 9), jnp.float32)
        b = jnp.asarray(rng.randn(8, 300), jnp.float32) * 0.01

        def body(wl, bl):
            out = overlap_on.exchange(
                {"w": wl[0], "b": bl[0]}, "dp", ReduceOp.AVERAGE,
                wire_dtype="int8_blockwise", threshold_bytes=1 << 20)
            return out["w"], out["b"]

        wq, bq = shard_map(body, mesh=mesh8,
                           in_specs=(P("dp"), P("dp")),
                           out_specs=(P(), P()))(w, b)
        tol = max(np.abs(np.asarray(l)).max() for l in (w, b)) / 127.0 \
            + 1e-6
        for got, leaf in ((wq, w), (bq, b)):
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(leaf).mean(0), atol=tol)

    def test_quant_start_finish_composes_to_flat(self, mesh8):
        """finish(start(x)) traces the same program as the monolithic
        quantized_allreduce_flat (the split must not drift)."""
        from horovod_tpu.quant import collectives as qc

        x = jnp.asarray(np.random.RandomState(6).randn(8, 512), jnp.float32)

        def split_body(xl):
            return qc.quantized_allreduce_finish(
                qc.quantized_allreduce_start(xl[0], "dp",
                                             block_size=128))

        def mono_body(xl):
            return qc.quantized_allreduce_flat(xl[0], "dp",
                                               block_size=128)

        got = shard_map(split_body, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P())(x)
        want = shard_map(mono_body, mesh=mesh8, in_specs=(P("dp"),),
                         out_specs=P())(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_empty_and_nonfloat_leaves(self, mesh8, overlap_on):
        assert overlap_on.exchange({}) == {}

        def body(i):
            out = overlap_on.exchange({"i": i[0], "s": jnp.int32(7)},
                                      "dp", ReduceOp.SUM,
                                      threshold_bytes=512)
            return out["i"], out["s"]

        iv = jnp.asarray(np.arange(8 * 4).reshape(8, 4), jnp.int32)
        got_i, got_s = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                                 out_specs=(P(), P()))(iv)
        np.testing.assert_array_equal(np.asarray(got_i),
                                      np.asarray(iv).sum(0))
        assert int(got_s) == 7 * 8


# ---------------------------------------------------------------------------
# lowered HLO: bucket collectives interleave with VJP segment compute
# ---------------------------------------------------------------------------


def _collectives_and_dots(lowered):
    """Text offsets of the all-reduces and of the matmuls in a lowered
    step, in the order the program issues them."""
    txt = lowered.as_text().lower()
    ar = [m.start() for m in re.finditer(r"all[-_]reduce", txt)]
    dots = [m.start() for m in re.finditer(r"dot_general|\bdot\(", txt)]
    return ar, dots


class TestHloInterleaving:
    def _stages(self, rng, depth=3):
        sizes = [(16, 32)] + [(32, 32)] * (depth - 2) + [(32, 1)]
        params = [{"w": jnp.asarray(rng.randn(*s), jnp.float32) * 0.1}
                  for s in sizes]

        def mk(i, last):
            def f(p, a):
                out = a @ p["w"]
                return jnp.mean(out ** 2) if last else jnp.tanh(out)

            return f

        stages = [mk(i, last=(i == depth - 1)) for i in range(depth)]
        return stages, params

    def test_segmented_grads_bitwise_vs_monolithic(self, mesh8,
                                                   overlap_on):
        rng = np.random.RandomState(7)
        stages, params = self._stages(rng)
        x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)
        ovg = ovl.overlap_value_and_grad(stages, axis="dp",
                                         threshold_bytes=1 << 20)

        def body_seg(xl, *ps):
            loss, grads = ovg(list(ps), xl[0])
            return (jax.lax.pmean(loss, "dp"),) + tuple(
                g["w"] for g in grads)

        def loss_all(ps, a):
            for f, p in zip(stages, ps):
                a = f(p, a)
            return a

        def body_mono(xl, *ps):
            loss, grads = jax.value_and_grad(loss_all)(list(ps), xl[0])
            grads = dev.fused_allreduce(grads, "dp", ReduceOp.AVERAGE)
            return (jax.lax.pmean(loss, "dp"),) + tuple(
                g["w"] for g in grads)

        specs = dict(in_specs=(P("dp"),) + (P(),) * 3,
                     out_specs=(P(),) * 4)
        seg = shard_map(body_seg, mesh=mesh8, **specs)(x, *params)
        mono = shard_map(body_mono, mesh=mesh8, **specs)(x, *params)
        for a, b in zip(seg, mono):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_lowered_hlo_interleaves_collectives_with_vjp(self, mesh8,
                                                          overlap_on):
        """Acceptance: per-bucket collectives are issued BETWEEN VJP
        segments in the lowered step, not as one trailing block."""
        rng = np.random.RandomState(8)
        stages, params = self._stages(rng, depth=4)
        x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)
        ovg = ovl.overlap_value_and_grad(stages, axis="dp",
                                         threshold_bytes=1 << 20)

        def body(xl, *ps):
            loss, grads = ovg(list(ps), xl[0])
            return (jax.lax.pmean(loss, "dp"),) + tuple(
                g["w"] for g in grads)

        fn = jax.jit(shard_map(body, mesh=mesh8,
                               in_specs=(P("dp"),) + (P(),) * 4,
                               out_specs=(P(),) * 5))
        ar, dots = _collectives_and_dots(fn.lower(x, *params))
        assert len(ar) >= 4, "expected one collective per stage"
        assert dots, "expected dot ops in the lowered text"
        # interleaved: backward matmuls appear AFTER the first issued
        # collective, and collectives appear BEFORE the last matmul —
        # i.e. NOT one trailing collective block.
        assert any(d > ar[0] for d in dots)
        assert any(a < dots[-1] for a in ar)

    def test_monolithic_trailing_block_by_contrast(self, mesh8):
        """The off path traces every collective after the whole
        backward — the contrast that makes the interleaving assertion
        meaningful."""
        rng = np.random.RandomState(9)
        stages, params = self._stages(rng, depth=4)
        x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)

        def loss_all(ps, a):
            for f, p in zip(stages, ps):
                a = f(p, a)
            return a

        def body(xl, *ps):
            # The product's monolithic path: gradients w.r.t. pvary'd
            # params stay per-rank, and the exchange after the backward
            # reduces them.  W.r.t. replicated params jax's vma autodiff
            # psums each gradient inside the backward itself and leaves
            # fused_allreduce nothing to do: no trailing block to see.
            loss, grads = jax.value_and_grad(loss_all)(
                hvd_opt.pvary_tree(list(ps), "dp"), xl[0])
            grads = [dev.fused_allreduce(g, "dp", ReduceOp.AVERAGE)
                     for g in grads]
            return (jax.lax.pmean(loss, "dp"),) + tuple(
                g["w"] for g in grads)

        fn = jax.jit(shard_map(body, mesh=mesh8,
                               in_specs=(P("dp"),) + (P(),) * 4,
                               out_specs=(P(),) * 5))
        ar, dots = _collectives_and_dots(fn.lower(x, *params))
        # one all_reduce per stage's gradient and the loss's pmean, every
        # one of them after the last backward matmul
        assert len(ar) == len(stages) + 1
        assert all(a > dots[-1] for a in ar)

    def test_rejects_nonscalar_last_stage(self, overlap_on):
        ovg = ovl.overlap_value_and_grad(
            [lambda p, a: a * p["w"]], axis="dp")
        with pytest.raises(ValueError, match="scalar"):
            ovg([{"w": jnp.ones((4,))}], jnp.ones((4,)))


# ---------------------------------------------------------------------------
# pipelined optimizer leg (exchange_and_update / pipelined_sgd)
# ---------------------------------------------------------------------------


class TestPipelinedUpdate:
    def test_pipelined_sgd_bitwise_vs_chain(self, mesh8, overlap_on):
        rng = np.random.RandomState(10)
        grads = {"w": jnp.asarray(rng.randn(8, 16, 128), jnp.float32),
                 "b": jnp.asarray(rng.randn(8, 33), jnp.float32)}
        params = jax.tree.map(lambda l: jnp.zeros(l.shape[1:]), grads)
        tx_pipe = ovl.pipelined_sgd(0.1, momentum=0.9,
                                    threshold_bytes=4096)
        tx_ref = optax.chain(
            hvd_opt.DistributedGradientTransformation(
                threshold_bytes=4096),
            fused_sgd(0.1, momentum=0.9))

        def trace_of(s):
            if hasattr(s, "trace"):
                return s.trace
            return next(sub.trace for sub in s if hasattr(sub, "trace"))

        def run(tx):
            state = tx.init(params)

            def body(w, b):
                u, s2 = tx.update({"w": w[0], "b": b[0]}, state, params)
                return u["w"], u["b"], trace_of(s2)["w"]

            return shard_map(body, mesh=mesh8,
                             in_specs=(P("dp"), P("dp")),
                             out_specs=(P(), P(), P()), **_smap_kw())(
                                 grads["w"], grads["b"])

        got = run(tx_pipe)
        want = run(tx_ref)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_pipelined_sgd_state_feeds_unpipelined_chain(self, overlap_on):
        """Hot-swap contract: both legs keep ONE state tree."""
        params = {"w": jnp.ones((4, 128)), "b": jnp.ones((33,))}
        tx_pipe = ovl.pipelined_sgd(0.1, momentum=0.9)
        tx_ref = fused_sgd(0.1, momentum=0.9)
        s_pipe = tx_pipe.init(params)
        s_ref = tx_ref.init(params)
        assert jax.tree.structure(s_pipe) == jax.tree.structure(s_ref)
        # unbound axis: plain update path; the ref chain consumes the
        # pipelined leg's state unchanged
        u, s2 = tx_ref.update(params, s_pipe, params)
        assert jax.tree.structure(s2) == jax.tree.structure(s_pipe)

    def test_exchange_and_update_multi_output(self, mesh8, overlap_on):
        rng = np.random.RandomState(11)
        grads = {"w": jnp.asarray(rng.randn(8, 24), jnp.float32)}
        aux = {"w": jnp.full((24,), 2.0, jnp.float32)}

        def body(w):
            d, m = ovl.exchange_and_update(
                {"w": w[0]}, lambda g, m: (g * -1.0, m + g),
                aux_trees=(aux,), threshold_bytes=4096)
            return d["w"], m["w"]

        d, m = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                         out_specs=(P(), P()))(grads["w"])
        mean = np.asarray(grads["w"]).mean(0)
        np.testing.assert_allclose(np.asarray(d), -mean, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(m), 2.0 + mean, rtol=1e-6)

    def test_pipelined_sgd_no_momentum(self, mesh8, overlap_on):
        rng = np.random.RandomState(12)
        g = jnp.asarray(rng.randn(8, 40), jnp.float32)
        tx = ovl.pipelined_sgd(0.5)

        def body(gl):
            u, _ = tx.update({"g": gl[0]}, tx.init({"g": gl[0]}))
            return u["g"]

        out = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P())(g)
        np.testing.assert_allclose(np.asarray(out),
                                   -0.5 * np.asarray(g).mean(0),
                                   rtol=1e-6)

    def test_pipelined_sgd_rejects_schedule(self):
        with pytest.raises(ValueError, match="float learning_rate"):
            ovl.pipelined_sgd(lambda step: 0.1, momentum=0.9)


# ---------------------------------------------------------------------------
# overlap accounting + telemetry gauge
# ---------------------------------------------------------------------------


class TestAccounting:
    def test_fraction_counts_all_but_last_bucket(self, mesh8, overlap_on):
        tree = _tree(13)

        def body(a, b, c):
            out = overlap_on.exchange({"a": a[0], "b": b[0], "c": c[0]},
                                      "dp", threshold_bytes=512)
            return out["a"], out["b"], out["c"]

        shard_map(body, mesh=mesh8, in_specs=(P("dp"),) * 3,
                  out_specs=(P(),) * 3)(tree["a"], tree["b"], tree["c"])
        frac = ovl.overlap_fraction()
        assert frac is not None and 0.0 < frac < 1.0
        sched = ovl.last_schedule()
        assert sched["buckets"] >= 2
        assert sched["hidden_buckets"] == sched["buckets"] - 1

    def test_single_bucket_hides_nothing(self, overlap_on, mesh8):
        x = jnp.ones((8, 16), jnp.float32)
        shard_map(lambda xl: overlap_on.exchange([xl[0]], "dp")[0],
                  mesh=mesh8, in_specs=(P("dp"),), out_specs=P())(x)
        sched = ovl.last_schedule()
        assert sched["buckets"] == 1 and sched["hidden_buckets"] == 0

    def test_telemetry_gauge_fed(self, mesh8, overlap_on, monkeypatch):
        from horovod_tpu.telemetry import instrument as ti
        from horovod_tpu.telemetry import metrics as tm

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        ti.reset()
        tm.reset_default_registry()
        rec = ti.get_recorder()
        assert rec is not None
        tree = _tree(14)

        def body(a, b, c):
            out = overlap_on.exchange({"a": a[0], "b": b[0], "c": c[0]},
                                      "dp", threshold_bytes=512)
            return out["a"], out["b"], out["c"]

        shard_map(body, mesh=mesh8, in_specs=(P("dp"),) * 3,
                  out_specs=(P(),) * 3)(tree["a"], tree["b"], tree["c"])
        g = rec.registry.gauge("hvdt_overlap_fraction")
        assert 0.0 < g.value() < 1.0
        assert rec.registry.counter(
            "hvdt_overlap_bytes_total").value() > 0
        ti.reset()
        tm.reset_default_registry()


# ---------------------------------------------------------------------------
# autotune overlap dimension (state-compatible hot-swap legs)
# ---------------------------------------------------------------------------


class TestAutotuneOverlapDimension:
    def test_parameter_manager_gains_overlap_column(self):
        from horovod_tpu.autotune import ParameterManager

        pm = ParameterManager(tune_overlap=True, tune_quant=False,
                              tune_fused_optimizer=False)
        assert pm._bo.candidates.shape[1] == 3
        pm._current = np.array([24.0, 1.0, 1.0])
        assert pm.overlap_schedule is True
        pm._current = np.array([24.0, 1.0, 0.0])
        assert pm.overlap_schedule is False
        pm5 = ParameterManager(tune_overlap=True, tune_quant=True,
                               tune_fused_optimizer=True)
        assert pm5._bo.candidates.shape[1] == 5
        pm5._current = np.array([24.0, 1.0, 0.0, 1.0, 1.0])
        assert (pm5.fused_optimizer is False and pm5.quant_wire is True
                and pm5.overlap_schedule is True)

    def test_autotuned_step_forwards_overlap_kw(self, monkeypatch):
        from horovod_tpu.autotune import AutotunedStep

        monkeypatch.setenv("HVDT_AUTOTUNE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_OVERLAP", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_WARMUP_SAMPLES", "0")
        seen = []

        def builder(threshold_bytes, overlap=False):
            seen.append((threshold_bytes, overlap))

            def step(x):
                return x * 2.0

            return step

        st = AutotunedStep(builder, tree_example=jnp.ones((256,)),
                           steps_per_sample=1)
        x = jnp.ones((4,))
        for _ in range(8):
            x = st(x)
        # build 0 pins the env leg; later rebuilds carry the tuned leg
        assert seen[0] == (None, False)
        assert len(seen) > 1
        assert all(isinstance(o, (bool, np.bool_)) for _, o in seen)

    def test_hot_swap_shares_state_and_compiled_legs(self, mesh8,
                                                     monkeypatch):
        """Acceptance: flipping the overlap leg must not recompile the
        non-overlap leg's cached program — a leg-memoizing builder flips
        back to the SAME jitted callable (same state tree throughout)."""
        rng = np.random.RandomState(15)
        grads = {"w": jnp.asarray(rng.randn(8, 16, 8), jnp.float32)}
        params = {"w": jnp.zeros((16, 8))}
        legs = {}
        compiles = {"n": 0}

        def build(threshold_bytes, overlap):
            key = bool(overlap)
            if key in legs:
                return legs[key]
            if overlap:
                monkeypatch.setenv("HVDT_OVERLAP", "on")
            else:
                monkeypatch.delenv("HVDT_OVERLAP", raising=False)
            ovl.reset()
            tx = hvd_opt.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9), threshold_bytes=512)
            state = tx.init(params)

            def body(w, s):
                u, s2 = tx.update({"w": w[0]}, s, params)
                return u["w"], s2

            smapped = shard_map(
                body, mesh=mesh8,
                in_specs=(P("dp"), P()), out_specs=(P(), P()))

            @jax.jit
            def step(w, s):
                compiles["n"] += 1   # counted at trace time
                return smapped(w, s)

            legs[key] = (step, state)
            return legs[key]

        step_off, state = build(None, overlap=False)
        u_off, _ = step_off(grads["w"], state)
        n_after_off = compiles["n"]
        step_on, state_on = build(1 << 20, overlap=True)
        # state tree is shared between legs (hot-swap contract)
        assert jax.tree.structure(state) == jax.tree.structure(state_on)
        u_on, _ = step_on(grads["w"], state)
        # flipping BACK to the off leg reuses the cached program
        step_off2, _ = build(1 << 20, overlap=False)
        assert step_off2 is step_off
        u_off2, _ = step_off2(grads["w"], state)
        assert compiles["n"] == n_after_off + 1, \
            "non-overlap leg recompiled when the overlap leg flipped"
        np.testing.assert_array_equal(np.asarray(u_off),
                                      np.asarray(u_off2))
        np.testing.assert_array_equal(np.asarray(u_off),
                                      np.asarray(u_on))
        monkeypatch.delenv("HVDT_OVERLAP", raising=False)
        ovl.reset()


# ---------------------------------------------------------------------------
# latency-hiding flag engagement (guarded for jax 0.4.37)
# ---------------------------------------------------------------------------


class TestLatencyHiding:
    def test_off_is_noop(self, monkeypatch):
        monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
        assert ovl.enable_latency_hiding("off") is None
        assert "LIBTPU_INIT_ARGS" not in __import__("os").environ

    def test_auto_skips_non_tpu_platform(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
        assert ovl.enable_latency_hiding("auto") is None

    def test_on_appends_flags_idempotently(self, monkeypatch):
        monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
        first = ovl.enable_latency_hiding("on")
        assert first and "--xla_tpu_enable_async_collective_fusion" in first
        again = ovl.enable_latency_hiding("on")
        assert again == first   # no duplicates

    def test_preserves_existing_args(self, monkeypatch):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--foo=1")
        out = ovl.enable_latency_hiding("on")
        assert out.startswith("--foo=1")

    def test_env_knob_default_auto(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.delenv("HVDT_XLA_LATENCY_HIDING", raising=False)
        monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
        assert ovl.enable_latency_hiding() is None


# ---------------------------------------------------------------------------
# double-buffered input: prefetch_to_device + overlap_step + async loader
# ---------------------------------------------------------------------------


class _Buf:
    def __init__(self, payload, log):
        self.payload = payload
        self._log = log

    def delete(self):
        self._log.append(self.payload)


class TestPrefetchOverlap:
    def test_size_zero_raises_eagerly(self):
        with pytest.raises(ValueError, match="size >= 1"):
            prefetch_to_device([1, 2], size=0)
        with pytest.raises(ValueError, match="size >= 1"):
            prefetch_to_device([1], size=-2)

    def test_close_drops_queued_buffers(self):
        deleted = []
        puts = []

        def put(b):
            puts.append(b)
            return _Buf(b, deleted)

        it = prefetch_to_device(range(10), size=3, put=put)
        first = next(it)
        assert first.payload == 0
        it.close()
        # the queued (never-yielded) buffers were dropped and deleted
        assert deleted == [1, 2]
        with pytest.raises(StopIteration):
            next(it)

    def test_abandonment_via_gc_drops_buffers(self):
        deleted = []
        it = prefetch_to_device(range(6), size=2,
                                put=lambda b: _Buf(b, deleted))
        next(it)
        del it
        import gc

        gc.collect()
        assert deleted == [1]

    def test_normal_exhaustion_deletes_nothing(self):
        deleted = []
        out = list(prefetch_to_device(
            range(4), size=2, put=lambda b: _Buf(b, deleted)))
        assert [b.payload for b in out] == [0, 1, 2, 3]
        assert deleted == []

    def test_per_leaf_sharding_pytree(self):
        import jax.sharding as jsh

        devs = jax.devices()
        s_repl = jsh.SingleDeviceSharding(devs[0])
        batches = [{"x": np.ones((4, 2), np.float32),
                    "step": np.int32(i)} for i in range(3)]
        out = list(prefetch_to_device(
            batches, size=2, sharding={"x": s_repl, "step": s_repl}))
        assert len(out) == 3
        assert all(isinstance(b["x"], jax.Array) for b in out)

    def test_prefetch_under_async_loader(self):
        """Satellite: prefetch_to_device composes with the async
        (background-thread) loader — the overlap_step input path."""
        loader = AsyncDataLoader(
            [np.full((2,), i, np.float32) for i in range(8)],
            async_loader_queue_size=4)
        try:
            got = [np.asarray(b)[0] for b in
                   prefetch_to_device(loader, size=2)]
            assert got == [float(i) for i in range(8)]
        finally:
            loader.close()

    def test_overlap_step_run_computes(self):
        st = step_pipeline.overlap_step(
            lambda s, b: (s + jnp.sum(b),), donate_argnums=(),
            prefetch_size=2)
        (total,) = st.run((jnp.zeros(()),),
                          [np.full((3,), i, np.float32)
                           for i in range(4)])
        assert float(total) == sum(3.0 * i for i in range(4))

    def test_overlap_step_run_double_buffers(self):
        """batch N+1's put happens before step N consumes it — the h2d
        rides under the step (host-side driver contract; the jitted fn
        is swapped for a host fn so call order is observable)."""
        calls = []

        def put(b):
            calls.append(("put", int(b[0])))
            return jnp.asarray(b)

        def step(acc, batch):
            calls.append(("step", int(batch[0])))
            return (acc + float(jnp.sum(batch)),)

        st = step_pipeline.overlap_step(step, donate_argnums=(),
                                        prefetch_size=2, put=put)
        st._fn = step
        (total,) = st.run((0.0,),
                          [np.full((3,), i, np.float32)
                           for i in range(4)])
        assert total == sum(3.0 * i for i in range(4))
        first_put_2 = calls.index(("put", 2))
        first_step_1 = calls.index(("step", 1))
        assert first_put_2 < first_step_1

    def test_overlap_step_forwards_attributes(self):
        st = step_pipeline.overlap_step(lambda s, b: (s + b,),
                                        donate_argnums=())
        assert hasattr(st, "lower")
        with pytest.raises(ValueError, match="prefetch_size >= 1"):
            step_pipeline.overlap_step(lambda s: s, prefetch_size=0)

    def test_overlap_step_closes_prefetch_on_error(self):
        deleted = []

        def put(b):
            return _Buf(b, deleted)

        def step(acc, batch):
            if batch.payload >= 1:
                raise RuntimeError("boom")
            return (acc,)

        st = step_pipeline.overlap_step(step, donate_argnums=(),
                                        prefetch_size=3, put=put)
        st._fn = step     # bypass jit: the driver contract is host-side
        with pytest.raises(RuntimeError):
            st.run((0,), list(range(6)))
        assert deleted, "queued buffers must be dropped on error exit"
