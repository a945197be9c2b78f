"""Sub-byte wire + low-precision compute (horovod_tpu/quant int4 leg,
quant/fp8) — the int4 pack/unpack kernels, the int4 route through the
two-stage quantized allreduce, error-feedback hot-swaps across the
f32/int8/int4 legs, the transport grammar's int4 vocabulary, the
autotune quant_leg dimension, the cost model's int4 pricing, and the
fp8 (e4m3) matmul gate.  All CPU: XLA lowering everywhere, plus
interpret-mode Pallas in the kernel-equivalence tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu import optimizer as hvd_opt
from horovod_tpu import quant
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.ops import device as dev
from horovod_tpu.ops.compression import (Compression, Int4Compressor,
                                         Int8Compressor)
from horovod_tpu.quant import fp8
from horovod_tpu.quant import kernels as qk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK = 128          # XLA-fallback block (block/2 = 64 < 128 lanes)
KBLOCK = 256         # Pallas-eligible int4 block (block/2 = 128 lanes)


def _np_block_scales4(x: np.ndarray, block: int) -> np.ndarray:
    """Reference per-block absmax/7 scales for a flat vector."""
    flat = x.astype(np.float32).ravel()
    pad = (-flat.size) % block
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    return np.abs(flat.reshape(-1, block)).max(1) / 7.0


# ---------------------------------------------------------------------------
# kernels: pack/unpack, error bound, Pallas == XLA, wire accounting
# ---------------------------------------------------------------------------


class TestInt4Kernels:
    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.RandomState(0)
        x = rng.randn(1000).astype(np.float32) * 3.0
        out = np.asarray(quant.quantize_dequantize_int4(
            jnp.asarray(x), BLOCK))
        scales = np.repeat(_np_block_scales4(x, BLOCK), BLOCK)[:x.size]
        # per-element: |x - q*scale| <= scale/2 = absmax/7/2 (+f32 eps)
        assert np.all(np.abs(out - x) <= scales * 0.5 + 1e-6)

    def test_grid_values_exact(self):
        rng = np.random.RandomState(1)
        nblocks = 8
        # Per block: scale s, values s * k for k in [-7, 7] with 7
        # present so absmax/7 reproduces s exactly.
        scales = 2.0 ** rng.randint(-8, 8, nblocks).astype(np.float32)
        ks = rng.randint(-7, 8, (nblocks, BLOCK)).astype(np.float32)
        ks[:, 0] = 7.0
        x = jnp.asarray(ks * scales[:, None]).reshape(-1)
        out = quant.quantize_dequantize_int4(x, BLOCK)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_packed_payload_is_half_the_elements(self):
        x = jnp.asarray(np.random.RandomState(2).randn(4 * BLOCK),
                        jnp.float32)
        q, s = quant.quantize_flat_int4(x, BLOCK)
        assert q.shape == (2 * BLOCK,) and q.dtype == jnp.int8
        assert s.shape == (4,)
        back = quant.dequantize_flat_int4(q, s, BLOCK)
        scales = np.repeat(np.asarray(s), BLOCK)
        assert np.all(np.abs(np.asarray(back) - np.asarray(x))
                      <= scales * 0.5 + 1e-6)

    def test_negative_nibbles_roundtrip(self):
        # Every representable lane value, both nibble positions: the
        # two's-complement pack/unpack must be lossless on the grid.
        ks = np.tile(np.arange(-7, 8, dtype=np.float32), BLOCK)[
            :2 * BLOCK]
        ks[0], ks[BLOCK] = 7.0, 7.0   # pin absmax -> scale 1
        x = jnp.asarray(ks)
        np.testing.assert_array_equal(
            np.asarray(quant.quantize_dequantize_int4(x, BLOCK)), ks)

    def test_pallas_kernel_matches_xla(self):
        rng = np.random.RandomState(3)
        # 64 blocks of 256: int4 kernel-eligible (block/2 = 128 lanes)
        flat = jnp.asarray(rng.randn(64 * KBLOCK), jnp.float32)
        qp, sp = quant.quantize_flat_int4(flat, KBLOCK, use_kernels=True)
        qx, sx = quant.quantize_flat_int4(flat, KBLOCK,
                                          use_kernels=False)
        np.testing.assert_array_equal(np.asarray(qp), np.asarray(qx))
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sx),
                                   rtol=1e-6)
        dp_ = quant.dequantize_flat_int4(qp, sp, KBLOCK,
                                         use_kernels=True)
        dx = quant.dequantize_flat_int4(qx, sx, KBLOCK,
                                        use_kernels=False)
        np.testing.assert_allclose(np.asarray(dp_), np.asarray(dx),
                                   rtol=1e-6)

    def test_kernel_eligibility_gate(self):
        assert qk.quant_kernel_eligible_int4(64 * 256, 256)
        # block 128 packs to 64 bytes/block — below the 128-lane tile
        assert not qk.quant_kernel_eligible_int4(64 * 128, 128)
        assert not qk.quant_kernel_eligible_int4(100, 256)   # partial
        assert not qk.quant_kernel_eligible_int4(0, 256)

    def test_rejects_partial_blocks_and_odd_blocks(self):
        with pytest.raises(ValueError, match="whole number"):
            quant.quantize_flat_int4(jnp.ones((100,)), BLOCK)
        with pytest.raises(ValueError, match="even"):
            quant.quantize_flat_int4(jnp.ones((127,)), 127)

    def test_wire_bytes_accounting(self):
        # packed payload (2 lanes/byte, padded to blocks) + f32 scales
        assert quant.wire_bytes_int4(256, 256) == 128 + 4
        assert quant.wire_bytes_int4(257, 256) == 256 + 8
        assert quant.wire_bytes_int4(1000, 256) == 512 + 16

    def test_wire_ratio_vs_int8_below_055(self):
        # Acceptance: int4 wire bytes <= 0.55x of int8 at the
        # calibration sweep sizes (4 KiB .. 64 MiB of f32 elements).
        for nbytes in (1 << 12, 1 << 16, 1 << 20, 1 << 26):
            n = nbytes // 4
            ratio = quant.wire_bytes_int4(n, 256) / quant.wire_bytes(
                n, 256)
            assert ratio <= 0.55, (nbytes, ratio)


# ---------------------------------------------------------------------------
# collectives: the int4 route through the two-stage allreduce
# ---------------------------------------------------------------------------


class TestInt4Allreduce:
    # AVERAGE over a length that needs padding to the blocks, SUM over
    # one that does not: two lossy stages, each bounded by its block's
    # absmax / 7 / 2, and a sum carries eight ranks' worth of it.
    @pytest.mark.parametrize("op,seed,n,ranks_of_error", [
        (ReduceOp.AVERAGE, 4, 500, 1), (ReduceOp.SUM, 5, 512, 8)],
        ids=["average", "sum"])
    def test_matches_f32_allreduce_within_bound(self, mesh8, op, seed, n,
                                                ranks_of_error):
        x = jnp.asarray(np.random.RandomState(seed).randn(8, n),
                        jnp.float32)

        def body(xl):
            return quant.quantized_allreduce_flat(
                xl[0], "dp", op, block_size=BLOCK, wire="int4")

        out = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P())(x)
        want = (np.asarray(x).mean(0) if op == ReduceOp.AVERAGE
                else np.asarray(x).sum(0))
        tol = ranks_of_error * np.abs(np.asarray(x)).max() / 7.0 + 1e-5
        np.testing.assert_allclose(np.asarray(out), want, atol=tol)

    def test_identical_on_grid_ranks_exact(self, mesh8):
        # All ranks hold the same on-grid values: both lossy stages are
        # exact, so the collective is end-to-end bit-exact.
        ks = np.random.RandomState(6).randint(
            -7, 8, (4 * BLOCK,)).astype(np.float32)
        ks[::BLOCK] = 7.0
        x = jnp.tile(jnp.asarray(ks)[None, :], (8, 1))

        def body(xl):
            return quant.quantized_allreduce_flat(
                xl[0], "dp", ReduceOp.AVERAGE, block_size=BLOCK,
                wire="int4")

        out = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P())(x)
        np.testing.assert_array_equal(np.asarray(out), ks)

    def test_rejects_unknown_wire(self, mesh8):
        with pytest.raises(ValueError, match="int4"):
            quant.quantized_allreduce_flat(jnp.ones((BLOCK,)), "dp",
                                           wire="int2")

    def test_fused_allreduce_int4_wire_mode(self, mesh8):
        rng = np.random.RandomState(7)
        tree = {"w": jnp.asarray(rng.randn(8, 33, 9), jnp.float32),
                "b": jnp.asarray(rng.randn(8, 300) * 0.01, jnp.float32)}

        def body(w, b):
            out = dev.fused_allreduce(
                {"w": w[0], "b": b[0], "step": jnp.int32(7)},
                "dp", ReduceOp.AVERAGE,
                wire_dtype=Compression.int4.wire_dtype)
            return out["w"], out["b"], out["step"]

        w, b, step = shard_map(
            body, mesh=mesh8, in_specs=(P("dp"), P("dp")),
            out_specs=(P(), P(), P()))(tree["w"], tree["b"])
        assert int(step) == 7   # non-float leaf took the exact path
        tol = max(np.abs(np.asarray(l)).max()
                  for l in tree.values()) / 7.0 + 1e-6
        for got, leaf in ((w, tree["w"]), (b, tree["b"])):
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(leaf).mean(0),
                                       atol=tol)


# ---------------------------------------------------------------------------
# error feedback: int4 residuals + leg hot-swaps carry state
# ---------------------------------------------------------------------------


class TestInt4ErrorFeedback:
    def test_residual_is_local_int4_quantization_error(self):
        tx = quant.with_error_feedback(optax.identity(),
                                       block_size=BLOCK, wire="int4")
        g = {"p": jnp.asarray(
            np.random.RandomState(8).randn(500), jnp.float32)}
        params = {"p": jnp.zeros(500)}
        state = tx.init(params)
        sent, state = tx.update(g, state, params)
        qdq = quant.quantize_dequantize_int4(g["p"], BLOCK)
        np.testing.assert_allclose(np.asarray(sent["p"]),
                                   np.asarray(qdq), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(state.residual["p"]),
                                   np.asarray(g["p"] - qdq),
                                   rtol=1e-5, atol=1e-7)

    def test_rejects_unknown_wire(self):
        with pytest.raises(ValueError, match="int4"):
            quant.with_error_feedback(optax.identity(), BLOCK,
                                      wire="fp4")

    def test_hot_swap_int8_int4_carries_residual(self):
        # The residual tree is plain f32 on EVERY leg: an int8 step's
        # residual must flow into the next int4 step's pre-quantization
        # gradient unchanged (and vice versa) — the autotune
        # no-state-drop contract across leg flips.
        g = {"p": jnp.asarray(
            np.random.RandomState(9).randn(512), jnp.float32)}
        params = {"p": jnp.zeros(512)}
        tx8 = quant.with_error_feedback(optax.identity(), BLOCK,
                                        wire="int8")
        tx4 = quant.with_error_feedback(optax.identity(), BLOCK,
                                        wire="int4")
        s = tx8.init(params)
        assert (jax.tree.structure(s)
                == jax.tree.structure(tx4.init(params)))
        _, s = tx8.update(g, s, params)
        res8 = np.asarray(s.residual["p"])
        sent4, s = tx4.update(g, s, params)
        # the int4 leg quantized (g + int8's residual), not bare g
        want = quant.quantize_dequantize_int4(
            g["p"] + jnp.asarray(res8), BLOCK)
        np.testing.assert_allclose(np.asarray(sent4["p"]),
                                   np.asarray(want), rtol=1e-6)
        # ...and the new residual closes the loop
        np.testing.assert_allclose(
            np.asarray(s.residual["p"]),
            np.asarray(g["p"] + res8 - want), rtol=1e-5, atol=1e-7)

    def test_mlp_200_steps_matches_f32_wire_within_tolerance(
            self, devices):
        # Acceptance: tiny regression MLP, 2-device dp mesh, int4 wire
        # + error feedback vs f32 wire — same init, same data.  The
        # 4-bit grid is coarse, so the band is wider than int8's 5%.
        mesh2 = Mesh(np.asarray(devices[:2], dtype=object), ("dp",))
        rng = np.random.RandomState(10)
        xd = rng.randn(64, 16).astype(np.float32)
        wt = rng.randn(16, 1).astype(np.float32)
        yd = (xd @ wt + 0.1 * rng.randn(64, 1)).astype(np.float32)
        p0 = {
            "w1": jnp.asarray(rng.randn(16, 32) * 0.3, jnp.float32),
            "b1": jnp.zeros((32,), jnp.float32),
            "w2": jnp.asarray(rng.randn(32, 1) * 0.3, jnp.float32),
            "b2": jnp.zeros((1,), jnp.float32),
        }

        def loss_fn(p, x, y):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] + p["b2"] - y) ** 2)

        def run(compression, wire):
            tx = quant.with_error_feedback(
                hvd_opt.DistributedOptimizer(optax.sgd(0.05),
                                             compression=compression),
                block_size=BLOCK, enabled=wire is not None,
                wire=wire or "int8")
            state = quant.tile_residual(tx.init(p0), 2)

            def step(p, s, x, y):
                def body(p, sr, si, xl, yl):
                    s = quant.unstack_residual(
                        quant.ErrorFeedbackState(sr, si))
                    g = jax.grad(loss_fn)(p, xl, yl)
                    u, s2 = tx.update(g, s, p)
                    s2 = quant.stack_residual(s2)
                    return (optax.apply_updates(p, u), s2.residual,
                            s2.inner)

                p2, sr, si = shard_map(
                    body, mesh=mesh2,
                    in_specs=(P(), P("dp"), P(), P("dp"), P("dp")),
                    out_specs=(P(), P("dp"), P()))(
                        p, s.residual, s.inner, x, y)
                return p2, quant.ErrorFeedbackState(sr, si)

            step = jax.jit(step)
            p = p0
            state_ = state
            for _ in range(200):
                p, state_ = step(p, state_, xd, yd)
            return float(loss_fn(p, jnp.asarray(xd), jnp.asarray(yd)))

        loss_f32 = run(Compression.none, None)
        loss_int4 = run(Compression.int4, "int4")
        assert loss_int4 <= loss_f32 * 1.25 + 1e-8, (loss_int4,
                                                     loss_f32)


# ---------------------------------------------------------------------------
# transport grammar: int4 vocabulary + slow-axis-only contract
# ---------------------------------------------------------------------------


class TestTransportInt4Grammar:
    def test_parse_int4_slow_axis(self):
        from horovod_tpu import transport as tp

        entries = tp.parse_transport("ici:ring:f32:64M,dcn:ring:int4:8M")
        assert entries["dcn"].wire == "int4"
        assert entries["dcn"].threshold_bytes == 8 << 20

    def test_int4_on_fast_axis_raises_slow(self):
        from horovod_tpu import transport as tp

        with pytest.raises(ValueError, match="slow"):
            tp.parse_transport("ici:ring:int4")

    def test_int8_on_fast_axis_message_lists_vocabulary(self):
        # Satellite fix: the rejection enumerates the FULL wire
        # vocabulary (and which wires are quantized/dcn-only), not just
        # the one that failed.
        from horovod_tpu import transport as tp

        with pytest.raises(ValueError, match="bf16") as ei:
            tp.parse_transport("ici:ring:int8")
        assert "int4" in str(ei.value) and "slow" in str(ei.value)

    def test_unknown_wire_lists_int4(self):
        from horovod_tpu import transport as tp

        with pytest.raises(ValueError, match="int4"):
            tp.parse_transport("dcn:ring:f64")

    def test_compound_wire_threshold_negatives(self):
        # Negative grammar for compound specs: a bad threshold on the
        # quantized entry must raise even when the other entry is
        # valid, and vice versa (the error must not be masked by the
        # healthy entry parsing first).
        from horovod_tpu import transport as tp

        for bad in ("ici:ring:f32:64M,dcn:ring:int4:64X",
                    "ici:ring:f32:1.5M,dcn:ring:int4:8M",
                    "ici:ring:f32:64M,dcn:ring:int4:-1"):
            with pytest.raises(ValueError, match="threshold"):
                tp.parse_transport(bad)
        with pytest.raises(ValueError, match="slow"):
            tp.parse_transport("ici:ring:int4:64M,dcn:ring:f32:8M")


# ---------------------------------------------------------------------------
# compressor + env selection
# ---------------------------------------------------------------------------


class TestInt4Compressor:
    def test_wire_sentinel_matches_collectives(self):
        assert Compression.int4.wire_dtype == quant.INT4_WIRE
        assert quant.quant_wire_leg(quant.INT4_WIRE) == "int4"
        assert quant.quant_wire_leg(quant.INT8_WIRE) == "int8"
        assert quant.quant_wire_leg("int4") == "int4"
        assert quant.quant_wire_leg("bf16") is None

    def test_from_env_int4(self, monkeypatch):
        monkeypatch.setenv("HVDT_COMPRESSION", "int4")
        assert Compression.from_env() is Int4Compressor
        # HVDT_QUANT shorthand still means int8
        monkeypatch.setenv("HVDT_QUANT", "1")
        assert Compression.from_env() is Int8Compressor

    def test_host_compressor_values_on_grid(self):
        rng = np.random.RandomState(11)
        x = rng.randn(513).astype(np.float32)
        once, _ = Int4Compressor.compress(x)
        twice, _ = Int4Compressor.compress(once)
        # on-grid values are a fixed point of the host wire simulation
        # up to f32 rounding of the absmax/7 scale (1/7 is not exactly
        # representable, unlike int8's benign 1/127 case)
        np.testing.assert_allclose(once, twice, rtol=1e-6, atol=1e-7)
        # ...and the grid is coarser than int8's (for non-grid input)
        snap8, _ = Int8Compressor.compress(x)
        assert (np.abs(np.asarray(once) - x).max()
                >= np.abs(np.asarray(snap8) - x).max())


# ---------------------------------------------------------------------------
# autotune: the three-leg quant dimension
# ---------------------------------------------------------------------------


class TestAutotuneQuantLeg:
    def test_candidates_span_three_legs(self):
        from horovod_tpu.autotune import ParameterManager

        assert ParameterManager.QUANT_CANDIDATES == (0.0, 1.0, 2.0)

    def test_quant_leg_property_decodes_column(self):
        from horovod_tpu.autotune import ParameterManager

        pm = ParameterManager(tune_quant=True,
                              tune_fused_optimizer=False)
        for v, leg, wire in ((0.0, "f32", False), (1.0, "int8", True),
                             (2.0, "int4", True)):
            pm._current = np.array([24.0, 1.0, v])
            assert pm.quant_leg == leg
            assert pm.quant_wire is wire

    def test_env_leg_resolution(self, monkeypatch):
        from horovod_tpu import autotune as at

        monkeypatch.setenv("HVDT_COMPRESSION", "int4")
        assert at._env_quant_leg() == "int4"
        assert at._env_quant_wire() is True
        monkeypatch.setenv("HVDT_COMPRESSION", "int8")
        assert at._env_quant_leg() == "int8"
        monkeypatch.setenv("HVDT_COMPRESSION", "bf16")
        assert at._env_quant_leg() == "f32"
        assert at._env_quant_wire() is False
        monkeypatch.delenv("HVDT_COMPRESSION")
        monkeypatch.setenv("HVDT_QUANT", "1")
        assert at._env_quant_leg() == "int8"

    def test_autotuned_step_forwards_quant_leg_kw(self, monkeypatch):
        from horovod_tpu.autotune import AutotunedStep

        monkeypatch.setenv("HVDT_AUTOTUNE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_QUANT", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_WARMUP_SAMPLES", "0")
        seen = []

        def builder(threshold_bytes, quant_leg="f32"):
            seen.append((threshold_bytes, quant_leg))

            def step(x):
                return x * 2.0

            return step

        st = AutotunedStep(builder, tree_example=jnp.ones((256,)),
                           steps_per_sample=1)
        x = jnp.ones((4,))
        for _ in range(8):
            x = st(x)
        # build 0 pins the env leg; later rebuilds carry the tuned leg
        assert seen[0] == (None, "f32")
        assert len(seen) > 1
        assert all(q in ("f32", "int8", "int4") for _, q in seen)

    def test_leg_flips_do_not_recompile(self, mesh8):
        # Acceptance: int8<->int4<->f32 flips share one jitted step —
        # the leg rides a traced arg (the EF-residual tree shape is
        # identical), so flipping never recompiles.  Here: one step
        # function parameterized only by already-traced state, executed
        # under each leg's quantize_dequantize with identical
        # input/output trees.
        g = jnp.asarray(np.random.RandomState(12).randn(512),
                        jnp.float32)

        traces = []

        @jax.jit
        def snap(x, leg_code):
            traces.append(1)
            qdq8 = quant.quantize_dequantize(x, BLOCK)
            qdq4 = quant.quantize_dequantize_int4(x, BLOCK)
            return jnp.where(leg_code == 0, x,
                             jnp.where(leg_code == 1, qdq8, qdq4))

        outs = [np.asarray(snap(g, jnp.int32(c))) for c in (0, 1, 2, 1)]
        assert len(traces) == 1          # one compile, four leg flips
        np.testing.assert_array_equal(outs[0], np.asarray(g))
        np.testing.assert_array_equal(
            outs[1], np.asarray(quant.quantize_dequantize(g, BLOCK)))
        np.testing.assert_array_equal(
            outs[2],
            np.asarray(quant.quantize_dequantize_int4(g, BLOCK)))
        np.testing.assert_array_equal(outs[1], outs[3])


# ---------------------------------------------------------------------------
# cost model: int4 pricing
# ---------------------------------------------------------------------------


class TestInt4CostModel:
    def test_wire_shrink_knows_int4(self):
        from horovod_tpu.analysis import costmodel as cm

        assert cm.wire_shrink("int4") == pytest.approx(
            0.125 + 1.0 / 256.0)
        assert cm.wire_shrink("int4") < cm.wire_shrink("int8") * 0.55

    def test_quant_gamma_default_knows_int4(self):
        from horovod_tpu.analysis import topology as tp_

        assert "int4" in tp_.DEFAULT_QUANT_GAMMA_S_PER_BYTE

    def test_predict_leg_order_evaluates_int4(self):
        from horovod_tpu.analysis import costmodel as cm
        from horovod_tpu.analysis import topology as tp_

        cal = cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME))
        out = cm.predict_leg_order(
            cal, tp_.TopologySpec(pods=2, chips_per_pod=4))
        assert set(out) == {"transport", "quant", "overlap",
                            "moe", "pipeline"}
        assert isinstance(out["quant"], bool)

    def test_int4_sweep_prediction_within_25pct(self):
        """Acceptance: the fitted model prices the int4-dcn
        hierarchical sweep within the 25% band of the checked-in
        CPU-sim measurement."""
        import json as _json

        from horovod_tpu.analysis import costmodel as cm
        from horovod_tpu.analysis import topology as tp_

        path = os.path.join(REPO, "tools", "calibration",
                            "hier_cpu8_int4.json")
        with open(path) as f:
            meas = _json.load(f)
        assert "int4" in meas["transport"]
        cal = cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME))
        model = cm.CostModel(cal)
        mesh = meas["mesh"]
        pred = model.hierarchical_speedup(
            meas["at_bytes"],
            tp_.TopologySpec(pods=mesh["dcn"],
                             chips_per_pod=mesh["ici"]),
            dcn_wire="int4")
        assert abs(pred - meas["value"]) / meas["value"] <= 0.25, (
            pred, meas["value"])


# ---------------------------------------------------------------------------
# fp8: the e4m3 matmul gate
# ---------------------------------------------------------------------------


class TestFp8:
    def test_mode_validation(self, monkeypatch):
        monkeypatch.setenv("HVDT_FP8", "off")
        assert fp8.fp8_mode() == "off"
        assert not fp8.matmul_enabled()
        monkeypatch.setenv("HVDT_FP8", "matmul")
        assert fp8.fp8_mode() == "matmul"
        monkeypatch.setenv("HVDT_FP8", "wat")
        with pytest.raises(ValueError, match="matmul"):
            fp8.fp8_mode()

    def test_gate_identity_when_unavailable(self, monkeypatch):
        # Acceptance: fp8 gate is a PROVABLE no-op when the dtype /
        # backend support is absent — fp8_matmul IS the plain matmul.
        monkeypatch.setattr(fp8, "_probe_result", False)
        x = jnp.asarray(np.random.RandomState(13).randn(4, 16),
                        jnp.bfloat16)
        w = jnp.asarray(np.random.RandomState(14).randn(16, 8),
                        jnp.float32)
        out = fp8.fp8_matmul(x, w)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(x @ w.astype(x.dtype)))
        assert not fp8.matmul_enabled()
        out2, st = fp8.fp8_matmul_delayed(x, w, fp8.init_amax_state())
        np.testing.assert_array_equal(
            np.asarray(out2), np.asarray(x @ w.astype(x.dtype)))
        assert np.all(np.asarray(st.x) == 0)   # state untouched

    @pytest.mark.skipif(not fp8.fp8_available(),
                        reason="no fp8 dot support in this jax build")
    def test_hlo_contains_f8_convert_dot(self):
        x = jnp.ones((8, 64), jnp.bfloat16)
        w = jnp.ones((64, 32), jnp.float32)
        hlo = jax.jit(fp8.fp8_matmul).lower(x, w).compile().as_text()
        assert "f8e4m3" in hlo

    @pytest.mark.skipif(not fp8.fp8_available(),
                        reason="no fp8 dot support in this jax build")
    def test_matmul_accuracy_within_e4m3_resolution(self):
        rng = np.random.RandomState(15)
        x = rng.randn(16, 64).astype(np.float32)
        w = rng.randn(64, 32).astype(np.float32)
        out = np.asarray(fp8.fp8_matmul(jnp.asarray(x),
                                        jnp.asarray(w)))
        want = x @ w
        # e4m3 has a 3-bit mantissa: per-operand relative error ~2^-4,
        # accumulated over k=64 — a loose but real sanity band.
        assert np.abs(out - want).max() <= 0.25 * np.abs(want).max()

    @pytest.mark.skipif(not fp8.fp8_available(),
                        reason="no fp8 dot support in this jax build")
    def test_overflow_clips_instead_of_nan(self):
        # e4m3 has no inf: values past +-448*scale must clip, not NaN.
        x = jnp.asarray([[1e6, -1e6, 1.0, 0.0]], jnp.float32)
        w = jnp.ones((4, 2), jnp.float32)
        out = np.asarray(fp8.fp8_matmul(x, w, amax_x=jnp.float32(1.0)))
        assert np.all(np.isfinite(out))

    @pytest.mark.skipif(not fp8.fp8_available(),
                        reason="no fp8 dot support in this jax build")
    def test_delayed_scaling_state_rolls(self):
        x = jnp.full((4, 8), 3.0, jnp.float32)
        w = jnp.full((8, 2), 5.0, jnp.float32)
        st = fp8.init_amax_state(history=4)
        out, st = fp8.fp8_matmul_delayed(x, w, st)
        assert float(st.x[-1]) == 3.0 and float(st.w[-1]) == 5.0
        assert np.all(np.asarray(st.x[:-1]) == 0)
        # history max governs the next step's scale even if the operand
        # shrinks — run again with smaller values, state still carries 3
        _, st2 = fp8.fp8_matmul_delayed(x * 0.1, w, st)
        assert float(st2.x[-1]) == pytest.approx(0.3, rel=1e-5)
        assert float(jnp.max(st2.x)) == 3.0

    @pytest.mark.skipif(not fp8.fp8_available(),
                        reason="no fp8 dot support in this jax build")
    def test_transformer_projections_lower_to_f8(self, monkeypatch):
        from horovod_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_loss)

        monkeypatch.setenv("HVDT_FP8", "matmul")
        cfg = TransformerConfig(vocab=64, layers=1, d_model=32,
                                heads=2, kv_heads=2, d_ff=64,
                                max_seq=16)
        p = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((2, 16), jnp.int32)
        hlo = jax.jit(lambda pp: transformer_loss(
            pp, toks, cfg)).lower(p).compile().as_text()
        assert "f8e4m3" in hlo
        # ...and the gate off leaves no f8 anywhere
        monkeypatch.setenv("HVDT_FP8", "off")
        hlo_off = jax.jit(lambda pp: transformer_loss(
            pp, toks, cfg)).lower(p).compile().as_text()
        assert "f8e4m3" not in hlo_off
