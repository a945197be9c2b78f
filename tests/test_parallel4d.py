"""4D-parallel acceptance battery: (pp, ep, dp) on the simulated
8-device mesh.

The acceptance scenario of the 4D subsystem: a 2-stage x 2-expert x
2-dp mesh trains a model whose TOTAL parameter bytes exceed a single
simulated chip's budget (each chip only ever holds its stage/expert
slice), the loss trajectory matches a single-chip dense reference
within float tolerance, the expert wire flips to block-scaled int8 with
one HVDT_TRANSPORT line, the priced pipeline-bubble fraction agrees
with the observed per-stage phase histograms within 25%, the trained
state checkpoint round-trips across a CHANGED parallelism layout, and
the optimizer wrapper enforces the sharded-axis reduce-group contract.
All CPU on the simulated 8-device mesh (conftest pins it).
"""

import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

try:
    from jax import shard_map as _shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map as _shard_map

from horovod_tpu.analysis import costmodel as cm
from horovod_tpu.parallel import (
    bubble_fraction,
    moe_capacity,
    moe_dispatch_combine,
    pipeline_1f1b,
    report_pipeline_mfu,
)

_SMAP_SIG = inspect.signature(_shard_map).parameters
_SMAP_KW = ({"check_rep": False} if "check_rep" in _SMAP_SIG
            else ({"check_vma": False} if "check_vma" in _SMAP_SIG
                  else {}))


def shard_map(*args, **kw):
    kw.update(_SMAP_KW)
    # jitted: an eager shard_map compiles every primitive of its body
    # as a multi-device program of its own
    return jax.jit(_shard_map(*args, **kw))


# Acceptance geometry: 2 stages x 2 experts x 2 dp on 8 chips.
PP, EP, DP = 2, 2, 2
DIM = 128
N_MB, TOK = 4, 8            # microbatches per step, tokens per ep rank
CAPACITY = 4.0              # generous: zero drops, so dense ref is exact

# The single-chip budget the model must NOT fit into whole.  The sliced
# per-chip footprint (one stage's weights + one expert) must fit.
CHIP_BUDGET_BYTES = 256 * 1024


def _mesh3():
    devs = jax.devices()
    assert len(devs) == 8
    return Mesh(np.asarray(devs, dtype=object).reshape(PP, EP, DP),
                ("pp", "ep", "dp"))


def _init_params(key):
    kw, kr, ke = jax.random.split(key, 3)
    scale = 0.5 / np.sqrt(DIM)
    return {
        "w": jax.random.normal(kw, (PP, DIM, DIM), jnp.float32) * scale,
        "rw": jax.random.normal(kr, (PP, DIM, EP), jnp.float32),
        "we": jax.random.normal(ke, (PP, EP, DIM, DIM),
                                jnp.float32) * scale,
    }


def _stage_fn_factory():
    """(stage_params, x) -> y for one pipeline stage: in-proj then the
    MoE layer over the ep axis (one expert per rank)."""

    def stage_fn(sp, x):
        sw, srw, swe = sp
        h = jnp.tanh(x @ sw)
        y, _aux = moe_dispatch_combine(
            h, h @ srw,
            lambda blk: jnp.tanh(jnp.einsum("ecd,df->ecf", blk, swe)),
            axis="ep", experts_per_rank=1,
            capacity_factor=CAPACITY, top_k=1)
        return x + y

    return stage_fn


def _make_loss_4d(mesh):
    stage_fn = _stage_fn_factory()

    def local(params, x, tgt):
        sp = (params["w"][0], params["rw"][0], params["we"][0, 0])
        out = pipeline_1f1b(stage_fn, sp, x[0, :, 0], axis="pp")
        loss = jnp.mean((out - tgt[0, :, 0]) ** 2)
        return lax.pmean(loss, ("ep", "dp"))

    specs = {"w": P("pp"), "rw": P("pp"), "we": P("pp", "ep")}
    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(specs, P("dp", None, "ep"), P("dp", None, "ep")),
        out_specs=P()))


def _dense_reference(params, x, tgt):
    """Single-chip dense reference: sequential stages, argmax top-1
    routing — exactly the MoE math when nothing is dropped (CAPACITY is
    generous; at top_k=1 the renormalized gate is identically 1)."""
    out_mb = []
    for d in range(DP):
        for mb in range(N_MB):
            h = x[d, mb].reshape(EP * TOK, DIM)
            for s in range(PP):
                a = jnp.tanh(h @ params["w"][s])
                logits = a @ params["rw"][s]
                sel = jnp.argmax(logits, axis=-1)
                expert_out = jnp.stack(
                    [jnp.tanh(a @ params["we"][s, e])
                     for e in range(EP)])           # [E, T, D]
                y = jnp.take_along_axis(
                    expert_out, sel[None, :, None], axis=0)[0]
                h = h + y
            out_mb.append(jnp.mean(
                (h - tgt[d, mb].reshape(EP * TOK, DIM)) ** 2))
    return jnp.mean(jnp.stack(out_mb))


class TestAcceptance4D:
    def test_model_exceeds_single_chip_budget(self):
        params = _init_params(jax.random.PRNGKey(0))
        total = sum(l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(params))
        per_chip = (params["w"][0].size + params["rw"][0].size
                    + params["we"][0, 0].size) * 4
        assert total > CHIP_BUDGET_BYTES, (
            "acceptance model must not fit one simulated chip")
        assert per_chip < CHIP_BUDGET_BYTES, (
            "the (stage, expert) slice must fit one chip")

    def test_4d_training_matches_single_chip_reference(self):
        """5 SGD steps on the (pp=2, ep=2, dp=2) mesh track the dense
        1-chip reference loss for a model bigger than one chip."""
        mesh = _mesh3()
        key = jax.random.PRNGKey(42)
        kp, kx, kt = jax.random.split(key, 3)
        params = _init_params(kp)
        x = jax.random.normal(kx, (DP, N_MB, EP * TOK, DIM), jnp.float32)
        tgt = jax.random.normal(kt, (DP, N_MB, EP * TOK, DIM),
                                jnp.float32) * 0.1
        # shard_map token layout: [dp, M, ep, TOK, DIM]
        x4 = x.reshape(DP, N_MB, EP, TOK, DIM)
        t4 = tgt.reshape(DP, N_MB, EP, TOK, DIM)

        loss_4d = _make_loss_4d(mesh)
        grad_4d = jax.jit(jax.grad(
            lambda p, xx, tt: loss_4d(p, xx, tt)))
        ref_loss = jax.jit(_dense_reference)
        ref_grad = jax.jit(jax.grad(_dense_reference))

        p_4d = params
        p_ref = params
        lr = 0.1
        for step in range(5):
            l4 = float(loss_4d(p_4d, x4, t4))
            lr_ref = float(ref_loss(p_ref, x, tgt))
            np.testing.assert_allclose(l4, lr_ref, rtol=2e-4, atol=1e-6)
            g4 = grad_4d(p_4d, x4, t4)
            gr = ref_grad(p_ref, x, tgt)
            p_4d = jax.tree.map(lambda a, b: a - lr * b, p_4d, g4)
            p_ref = jax.tree.map(lambda a, b: a - lr * b, p_ref, gr)
        # loss went DOWN: the 4D composition actually trains
        assert float(loss_4d(p_4d, x4, t4)) < float(
            loss_4d(params, x4, t4))

    def test_int8_expert_wire_one_policy_line(self, monkeypatch):
        """HVDT_TRANSPORT=ep:ring:int8:64M flips the expert dispatch to
        the block-scaled int8 wire — same results within the quant
        bound, no code change."""
        from horovod_tpu.transport import policy as tpolicy

        mesh = _mesh3()
        key = jax.random.PRNGKey(7)
        kp, kx, kt = jax.random.split(key, 3)
        params = _init_params(kp)
        x4 = jax.random.normal(kx, (DP, N_MB, EP, TOK, DIM), jnp.float32)
        t4 = jax.random.normal(kt, (DP, N_MB, EP, TOK, DIM),
                               jnp.float32) * 0.1

        monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
        tpolicy.reset()
        try:
            base = float(_make_loss_4d(mesh)(params, x4, t4))
            monkeypatch.setenv("HVDT_TRANSPORT", "ep:ring:int8:64M")
            tpolicy.reset()
            # fresh closure: jit caches executables per callable
            quant = float(_make_loss_4d(mesh)(params, x4, t4))
        finally:
            monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
            tpolicy.reset()
        assert quant == pytest.approx(base, rel=0.05)


class TestBubbleAccounting:
    @pytest.fixture()
    def telemetry(self, monkeypatch):
        from horovod_tpu.telemetry import instrument as ti
        from horovod_tpu.telemetry import metrics as tm

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        ti.reset()
        tm.reset_default_registry()
        yield ti.get_recorder()
        ti.reset()
        tm.reset_default_registry()

    @pytest.mark.parametrize("p,m", [(2, 6), (4, 4)])
    def test_priced_vs_observed_phase_histograms(self, telemetry, p, m):
        """Acceptance: the cost model's (p-1)/(m+p-1) agrees with the
        observed per-stage phase histograms (tick units) within 25%."""
        devs = jax.devices()[:p]
        mesh = Mesh(np.asarray(devs, dtype=object), ("pp",))
        w = jnp.eye(DIM // 4) * 0.5
        mbs = jnp.ones((m, 4, DIM // 4), jnp.float32)

        step = jax.jit(shard_map(
            lambda wl, xl: pipeline_1f1b(
                lambda sp, x: x @ sp, wl, xl, axis="pp"),
            mesh=mesh, in_specs=(P(), P()), out_specs=P()))
        step(w, mbs).block_until_ready()

        reg = telemetry.registry
        idle = active = 0.0
        for s in range(p):
            for phase, bucket in (("WARMUP", "idle"),
                                  ("ACTIVE", "active"),
                                  ("COOLDOWN", "idle")):
                summ = reg.get(
                    f"hvdt_phase_PIPELINE_STAGE{s}_{phase}_seconds")
                val = summ.sum if summ is not None else 0.0
                if bucket == "idle":
                    idle += val
                else:
                    active += val
        assert active > 0
        observed = idle / (idle + active)
        priced = cm.CostModel(cm.Calibration()).pipeline_bubble_fraction(
            p, m)
        assert priced == pytest.approx(bubble_fraction(p, m))
        assert abs(observed - priced) <= 0.25 * priced

    def test_mfu_reporter_returns_ratio(self, telemetry):
        mfu = report_pipeline_mfu(flops_per_step=1e9, step_seconds=0.01,
                                  peak_flops_per_sec=1e12)
        assert mfu == pytest.approx(0.1)
        g = telemetry.registry.get("hvdt_pipeline_mfu")
        assert g is not None and g.value() == pytest.approx(0.1)


class TestLayoutChangeRoundTrip:
    def test_trained_4d_state_restores_flat(self, tmp_path):
        """The 4D model's per-stage optimizer state saved under
        (pp=2, dp=4) restores into a flat (dp=8) layout — the logical
        vector is preserved stage-major, SHA-verified."""
        from horovod_tpu import checkpoint as ckpt
        from horovod_tpu.ops import zero as z

        params = _init_params(jax.random.PRNGKey(3))
        stage_trees = [
            {"w": params["w"][s], "rw": params["rw"][s],
             "we": params["we"][s]} for s in range(PP)]
        txs, states, metas = [], [], []
        for s, tree in enumerate(stage_trees):
            tx = z.zero_adam(1e-3, axis="dp", num_shards=4,
                             threshold_bytes=4096)
            st = tx.init(tree)
            g = jax.tree.map(jnp.ones_like, tree)
            _, st = tx.update(g, st, tree)
            txs.append(tx)
            states.append(st)
            metas.append(z.state_metadata(tx, tree))
        ckpt.save_zero_state_4d(str(tmp_path), states, metas, step=1)

        combined = {f"stage{s}": t for s, t in enumerate(stage_trees)}
        tx8 = z.zero_adam(1e-3, axis="dp", num_shards=8,
                          threshold_bytes=4096)
        out, out_metas, step = ckpt.restore_zero_state_4d(
            str(tmp_path), [z.state_metadata(tx8, combined)])
        assert step == 1 and out_metas[0]["num_shards"] == 8
        got = z.flatten_state_buffers(out[0], out_metas[0])
        want = np.concatenate(
            [np.asarray(z.flatten_state_buffers(st, me)["mu"])
             for st, me in zip(states, metas)])
        np.testing.assert_array_equal(np.asarray(got["mu"]), want)


class TestOptimizerContract4D:
    def test_reduce_axis_may_not_overlap_sharded_axes(self):
        import optax

        import horovod_tpu as hvd

        with pytest.raises(ValueError, match="parameter-SHARDED"):
            hvd.DistributedOptimizer(optax.sgd(0.1), axis=("dp", "pp"),
                                     pipeline="pp")
        with pytest.raises(ValueError, match="parameter-SHARDED"):
            hvd.DistributedOptimizer(optax.sgd(0.1), axis=("dp", "ep"),
                                     expert="ep")
        # disjoint axes build fine
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis="dp",
                                       pipeline="pp", expert="ep")
        assert opt is not None


class TestPricing4D:
    def test_pp_ep_tier_classification(self):
        from horovod_tpu.analysis.topology import (TIER_DCN, TIER_ICI,
                                                   classify_axis)

        axes = ("pp", "ep", "dp")
        assert classify_axis("pp", axes) == TIER_DCN
        assert classify_axis("ep", axes) == TIER_ICI

    def test_alltoall_and_pipeline_priced(self):
        model = cm.CostModel(cm.Calibration())
        a2a = model.alltoall_seconds(1 << 20, 8)
        assert a2a["seconds"] > 0 and a2a["wire_bytes"] > 0
        pipe = model.pipeline_seconds(1 << 16, num_stages=2,
                                      num_microbatches=8)
        assert pipe["seconds"] > 0 and pipe["ticks"] == 9
        assert pipe["bubble_fraction"] == pytest.approx(
            bubble_fraction(2, 8))

    def test_predict_leg_order_has_4d_verdicts(self):
        out = cm.predict_leg_order(
            cm.Calibration(), cm.TopologySpec(pods=2, chips_per_pod=4))
        assert "moe" in out and "pipeline" in out
        assert isinstance(out["moe"], (bool, np.bool_))

    def test_capacity_floor(self):
        assert moe_capacity(8, 2, top_k=1, capacity_factor=1.0) == 4
        assert moe_capacity(1, 64, top_k=1, capacity_factor=1.0) == 1
