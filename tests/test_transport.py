"""Transport-policy layer (horovod_tpu/transport) — strict grammar
battery, mesh transport-class helpers, policy resolution, zero-wrapper
identity when unset, mesh-8 (2x4) hierarchical parity vs the flat
``fused_allreduce``, the int8 slow-axis wire bound, composition with the
overlap scheduler's bucket schedules, per-axis telemetry counters, the
autotune transport dimension (hot-swap without recompile on flip-back),
and the bench seed loop.  All CPU on the simulated 8-device mesh."""

import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu import optimizer as hvd_opt
from horovod_tpu import transport
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.ops import device as dev
from horovod_tpu.ops import overlap as ovl
from horovod_tpu.parallel import mesh as pmesh
from horovod_tpu.transport import hierarchy as th
from horovod_tpu.transport import policy as tp


def _smap_kw():
    """check_rep/check_vma off where the kwarg exists (same pattern as
    tests/test_overlap.py)."""
    sig = inspect.signature(jax.shard_map).parameters
    if "check_rep" in sig:
        return {"check_rep": False}
    if "check_vma" in sig:
        return {"check_vma": False}
    return {}


@pytest.fixture(autouse=True)
def _clean_transport(monkeypatch):
    """The policy cache is process-wide and env-keyed; every test starts
    and ends unset."""
    monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
    transport.reset()
    yield
    transport.reset()


@pytest.fixture(scope="module")
def mesh_hier():
    """The two-level 2x4 topology: outer axis crosses DCN, inner rides
    ICI (the bench_allreduce --hierarchical mesh)."""
    devs = jax.devices()
    assert len(devs) == 8
    return Mesh(np.asarray(devs, dtype=object).reshape(2, 4),
                ("dcn", "ici"))


@pytest.fixture(scope="module")
def mesh3d():
    devs = jax.devices()
    return Mesh(np.asarray(devs, dtype=object).reshape(2, 2, 2),
                ("dp", "fsdp", "tp"))


def _set_policy(monkeypatch, spec):
    monkeypatch.setenv("HVDT_TRANSPORT", spec)
    transport.reset()


def _int_tree(seed=0):
    """Integer-valued f32 leaves: every per-tier partial sum is exactly
    representable, so flat-vs-hierarchical reassociation is bitwise."""
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randint(-40, 40, (8, 64, 3)), jnp.float32),
        "b": jnp.asarray(rng.randint(-40, 40, (8, 301)), jnp.float32),
        "c": jnp.asarray(rng.randint(-40, 40, (8, 17)), jnp.float32),
    }


def _flat_reduce(mesh, tree, op=ReduceOp.AVERAGE, **kw):
    axes = mesh.axis_names

    def body(*leaves):
        out = dev.fused_allreduce(list(leaves), axes, op, **kw)
        return tuple(out)

    leaves = list(tree.values())
    return shard_map(body, mesh=mesh, in_specs=(P(axes),) * len(leaves),
                     out_specs=(P(),) * len(leaves), **_smap_kw())(*leaves)


# ---------------------------------------------------------------------------
# grammar battery (strict validation — the HVDT_COMPRESSION idiom)
# ---------------------------------------------------------------------------


class TestGrammar:
    def test_parse_full_spec(self):
        entries = tp.parse_transport("ici:ring:f32:64M,dcn:tree:int8:8M")
        assert entries["ici"] == tp.AxisPolicy("ring", "f32", 64 << 20)
        assert entries["dcn"] == tp.AxisPolicy("tree", "int8", 8 << 20)

    def test_threshold_suffixes(self):
        for suf, mult in (("", 1), ("K", 1 << 10), ("k", 1 << 10),
                          ("M", 1 << 20), ("G", 1 << 30)):
            got = tp.parse_transport(f"dcn:tree:f32:3{suf}")
            assert got["dcn"].threshold_bytes == 3 * mult

    def test_case_insensitive_and_whitespace(self):
        entries = tp.parse_transport(" ICI:Ring:F32 , dcn:TREE:bf16:4m ")
        assert entries["ici"].algorithm == "ring"
        assert entries["dcn"].wire == "bf16"

    def test_unknown_axis_lists_vocabulary(self):
        with pytest.raises(ValueError, match="ici"):
            tp.parse_transport("nvlink:ring:f32")

    def test_unknown_algorithm_lists_vocabulary(self):
        with pytest.raises(ValueError, match="2d_ring"):
            tp.parse_transport("ici:butterfly:f32")

    def test_unknown_wire_lists_vocabulary(self):
        with pytest.raises(ValueError, match="bf16"):
            tp.parse_transport("ici:ring:f64")

    def test_garbage_threshold_raises(self):
        for bad in ("64X", "-1", "1.5M", "lots"):
            with pytest.raises(ValueError, match="threshold"):
                tp.parse_transport(f"ici:ring:f32:{bad}")

    def test_malformed_entry_raises(self):
        for bad in ("ici", "ici:ring", "ici:ring:f32:1M:extra"):
            with pytest.raises(ValueError, match="expected"):
                tp.parse_transport(bad)

    def test_duplicate_axis_raises(self):
        with pytest.raises(ValueError, match="duplicate"):
            tp.parse_transport("ici:ring:f32,ici:tree:f32")

    def test_empty_spec_raises(self):
        with pytest.raises(ValueError, match="empty"):
            tp.parse_transport(" , ")

    def test_int8_on_ici_raises(self):
        with pytest.raises(ValueError, match="slow"):
            tp.parse_transport("ici:ring:int8")

    def test_auto_policy(self):
        pol = tp.TransportPolicy.parse("auto")
        assert pol.entries["ici"] == tp.AxisPolicy("ring", "f32", None)
        assert pol.entries["dcn"].algorithm == "tree"
        assert pol.entries["dcn"].threshold_bytes == 8 << 20

    def test_invalid_spec_fails_hvd_init(self, monkeypatch):
        """The satellite contract: a typo fails at hvd.init() with the
        valid vocabulary, not at the first traced step."""
        import horovod_tpu as hvd

        _set_policy(monkeypatch, "ici:warp:f32")
        with pytest.raises(ValueError, match="ring"):
            hvd.init()

    def test_validate_env_returns_parsed_policy(self, monkeypatch):
        _set_policy(monkeypatch, "dcn:tree:fp16")
        pol = transport.validate_env()
        assert pol is not None and pol.entries["dcn"].wire == "fp16"


# ---------------------------------------------------------------------------
# mesh transport-class helpers
# ---------------------------------------------------------------------------


class TestMeshHelpers:
    def test_innermost_axis_is_ici(self):
        assert pmesh.axis_transport_class("tp", ("dp", "tp")) == "ici"
        assert pmesh.axis_transport_class("dp", ("dp", "tp")) == "dcn"

    def test_single_axis_group_is_ici(self):
        assert pmesh.axis_transport_class("dp", ("dp",)) == "ici"

    def test_unknown_axis_raises(self):
        with pytest.raises(ValueError, match="not in reduce group"):
            pmesh.axis_transport_class("tp", ("dp",))

    def test_split_default_width(self):
        assert pmesh.split_transport_axes(("dp", "fsdp", "tp")) == \
            (("dp", "fsdp"), ("tp",))

    def test_split_width_two(self):
        assert pmesh.split_transport_axes(("dp", "fsdp", "tp"), 2) == \
            (("dp",), ("fsdp", "tp"))

    def test_split_keeps_one_slow_axis(self):
        # fast_width >= len(axes): one axis always stays slow when the
        # group is splittable at all
        assert pmesh.split_transport_axes(("dp", "tp"), 5) == \
            (("dp",), ("tp",))

    def test_split_single_axis(self):
        assert pmesh.split_transport_axes(("dp",), 2) == ((), ("dp",))

    def test_split_empty_raises(self):
        with pytest.raises(ValueError, match="empty"):
            pmesh.split_transport_axes(())


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


class TestResolution:
    def test_multi_axis_goes_hierarchical(self, monkeypatch):
        _set_policy(monkeypatch, "ici:ring:f32:64M,dcn:tree:int8:8M")
        res = transport.resolve_axis(("dcn", "ici"))
        assert res.kind == "hierarchical"
        assert res.fast_axes == ("ici",) and res.slow_axes == ("dcn",)
        assert res.slow.wire == "int8"
        assert res.threshold_bytes == 64 << 20  # fast entry wins

    def test_exact_axis_name_beats_class(self, monkeypatch):
        _set_policy(monkeypatch, "tp:tree:bf16,ici:ring:f32")
        res = transport.resolve_axis(("dp", "tp"))
        assert res.fast == tp.AxisPolicy("tree", "bf16", None)

    def test_2d_ring_widens_fast_tier(self, monkeypatch):
        _set_policy(monkeypatch, "ici:2d_ring:f32,dcn:tree:f32")
        res = transport.resolve_axis(("dp", "fsdp", "tp"))
        assert res.fast_axes == ("fsdp", "tp")
        assert res.slow_axes == ("dp",)

    def test_2d_ring_on_two_axis_group_stays_width_one(self, monkeypatch):
        _set_policy(monkeypatch, "ici:2d_ring:f32")
        res = transport.resolve_axis(("dcn", "ici"))
        assert res.fast_axes == ("ici",) and res.slow_axes == ("dcn",)

    def test_int8_needs_single_slow_axis(self, monkeypatch):
        _set_policy(monkeypatch, "dcn:tree:int8")
        with pytest.raises(ValueError, match="ONE mesh axis"):
            transport.resolve_axis(("dp", "fsdp", "tp"))

    def test_single_axis_flat_override(self, monkeypatch):
        _set_policy(monkeypatch, "dp:ring:bf16:2M")
        res = transport.resolve_axis("dp")
        assert res.kind == "flat"
        assert res.fast.wire == "bf16"
        assert res.threshold_bytes == 2 << 20

    def test_single_axis_without_entry_is_none(self, monkeypatch):
        _set_policy(monkeypatch, "dcn:tree:f32")
        assert transport.resolve_axis("dp") is None

    def test_off_values_stay_off(self, monkeypatch):
        for off in ("", "0", "off", "none", "false"):
            monkeypatch.setenv("HVDT_TRANSPORT", off)
            transport.reset()
            assert transport.get_policy() is None
            assert not transport.enabled()
            assert transport.resolve_axis(("dcn", "ici")) is None

    def test_env_change_rebuilds_cached_policy(self, monkeypatch):
        _set_policy(monkeypatch, "auto")
        assert transport.get_policy().entries["dcn"].algorithm == "tree"
        # cache keys on the raw env string — no reset() needed
        monkeypatch.setenv("HVDT_TRANSPORT", "dcn:ring:f32")
        assert transport.get_policy().entries["dcn"].algorithm == "ring"

    def test_bucket_threshold_explicit_wins(self, monkeypatch):
        _set_policy(monkeypatch, "ici:ring:f32:64M")
        assert transport.bucket_threshold("dp", 1234) == 1234
        assert transport.bucket_threshold("dp") == 64 << 20
        monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
        transport.reset()
        assert transport.bucket_threshold("dp") is None

    def test_zero_threshold_clamps_through_validated(self, monkeypatch):
        """Satellite: per-axis thresholds reuse _validated_threshold
        clamping — a 0 entry degrades to the registry default instead of
        planning one-leaf buckets."""
        from horovod_tpu.common import config

        _set_policy(monkeypatch, "dcn:tree:f32:0")
        raw = transport.bucket_threshold("dcn")
        assert raw == 0
        assert dev._validated_threshold(raw) == \
            config.get_int("HVDT_FUSION_THRESHOLD")


# ---------------------------------------------------------------------------
# zero-wrapper identity when unset
# ---------------------------------------------------------------------------


class TestIdentity:
    def test_unset_policy_is_none(self):
        assert transport.get_policy() is None

    def test_unset_exchange_fn_is_fused_allreduce(self, monkeypatch):
        """Acceptance: with HVDT_TRANSPORT unset, exchange_fn() resolves
        to the pre-existing flat path as the IDENTICAL code object."""
        monkeypatch.delenv("HVDT_OVERLAP", raising=False)
        ovl.reset()
        assert ovl.exchange_fn() is dev.fused_allreduce

    def test_unset_traces_identical_flat_program(self, mesh_hier):
        """Belt and braces on the same contract: the traced program text
        with the layer importable-but-unset matches a trace after a
        cache reset — no policy residue in the jaxpr."""
        x = jnp.ones((8, 64), jnp.float32)

        def body(xl):
            return dev.fused_allreduce([xl], ("dcn", "ici"),
                                       ReduceOp.AVERAGE)[0]

        def lower():
            return jax.jit(shard_map(
                body, mesh=mesh_hier, in_specs=(P(("dcn", "ici")),),
                out_specs=P(), **_smap_kw())).lower(x).as_text()

        first = lower()
        transport.reset()
        assert lower() == first
        assert "all-to-all" not in first  # no quant wire crept in


# ---------------------------------------------------------------------------
# hierarchical data plane: parity vs flat fused_allreduce
# ---------------------------------------------------------------------------


class TestHierarchicalParity:
    def test_bitwise_f32_parity_vs_flat(self, mesh_hier, monkeypatch):
        """Acceptance: mesh-8 (2x4) hierarchical f32 allreduce is
        bitwise-equal to flat fused_allreduce on the same inputs."""
        tree = _int_tree(0)
        want = _flat_reduce(mesh_hier, tree)
        _set_policy(monkeypatch, "auto")
        got = _flat_reduce(mesh_hier, tree)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_bitwise_sum_parity(self, mesh_hier, monkeypatch):
        tree = _int_tree(1)
        want = _flat_reduce(mesh_hier, tree, ReduceOp.SUM)
        _set_policy(monkeypatch, "auto")
        got = _flat_reduce(mesh_hier, tree, ReduceOp.SUM)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_tree_fast_tier_parity(self, mesh_hier, monkeypatch):
        tree = _int_tree(2)
        want = _flat_reduce(mesh_hier, tree)
        _set_policy(monkeypatch, "ici:tree:f32,dcn:tree:f32")
        got = _flat_reduce(mesh_hier, tree)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_2d_ring_parity(self, mesh3d, monkeypatch):
        tree = _int_tree(3)
        want = _flat_reduce(mesh3d, tree)
        _set_policy(monkeypatch, "ici:2d_ring:f32,dcn:tree:f32")
        got = _flat_reduce(mesh3d, tree)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_prescale_postscale_parity(self, mesh_hier, monkeypatch):
        tree = _int_tree(4)
        kw = dict(prescale_factor=0.5, postscale_factor=2.0)
        want = _flat_reduce(mesh_hier, tree, **kw)
        _set_policy(monkeypatch, "auto")
        got = _flat_reduce(mesh_hier, tree, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_int8_slow_axis_within_established_bound(self, mesh_hier,
                                                     monkeypatch):
        """The int8 wire rides the slow tier on the fast tier's 1/4
        shard; the established block-scale/2 per-stage bound applies to
        the ici-reduced partial sums."""
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(8, 600), jnp.float32)
        want = np.asarray(x).mean(0)
        _set_policy(monkeypatch, "ici:ring:f32,dcn:tree:int8")

        def body(xl):
            return dev.fused_allreduce([xl[0]], ("dcn", "ici"),
                                       ReduceOp.AVERAGE)[0]

        got = shard_map(body, mesh=mesh_hier, in_specs=(P(("dcn", "ici")),),
                        out_specs=P(), **_smap_kw())(x)
        # two lossy stages on the ici-summed shard (absmax <= 4x leaf),
        # divided back by the full group size
        tol = 4 * np.abs(np.asarray(x)).max() / 127.0 + 1e-6
        np.testing.assert_allclose(np.asarray(got), want, atol=tol)
        assert np.abs(np.asarray(got) - want).max() > 0  # actually lossy

    def test_nonfloat_bucket_keeps_exact_path(self, mesh_hier,
                                              monkeypatch):
        _set_policy(monkeypatch, "auto")
        i = jnp.asarray(np.arange(8 * 32).reshape(8, 32), jnp.int32)

        def body(il):
            return dev.fused_allreduce([il[0]], ("dcn", "ici"),
                                       ReduceOp.SUM)[0]

        got = shard_map(body, mesh=mesh_hier, in_specs=(P(("dcn", "ici")),),
                        out_specs=P(), **_smap_kw())(i)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(i).sum(0))

    def test_start_finish_composes_to_flat(self, mesh_hier, monkeypatch):
        """finish(start(x)) traces the same program as
        hierarchical_allreduce_flat (the split must not drift)."""
        _set_policy(monkeypatch, "auto")
        x = jnp.asarray(np.random.RandomState(6).randn(8, 512),
                        jnp.float32)
        res = transport.get_policy().resolve(("dcn", "ici"))

        def split_body(xl):
            return th.hierarchical_allreduce_finish(
                th.hierarchical_allreduce_start(xl.reshape(-1), res))

        def mono_body(xl):
            return th.hierarchical_allreduce_flat(xl.reshape(-1), res)

        got = shard_map(split_body, mesh=mesh_hier,
                        in_specs=(P(("dcn", "ici")),), out_specs=P(),
                        **_smap_kw())(x)
        want = shard_map(mono_body, mesh=mesh_hier,
                         in_specs=(P(("dcn", "ici")),), out_specs=P(),
                         **_smap_kw())(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_flat_single_axis_wire_override(self, mesh8, monkeypatch):
        """A single-axis policy entry only swaps the wire dtype — same
        program as passing wire_dtype explicitly."""
        x = jnp.asarray(np.random.RandomState(7).randn(8, 256),
                        jnp.float32)

        def body_policy(xl):
            return dev.fused_allreduce([xl[0]], "dp",
                                       ReduceOp.AVERAGE)[0]

        def body_explicit(xl):
            return dev.fused_allreduce([xl[0]], "dp", ReduceOp.AVERAGE,
                                       wire_dtype=jnp.bfloat16)[0]

        want = shard_map(body_explicit, mesh=mesh8, in_specs=(P("dp"),),
                         out_specs=P(), **_smap_kw())(x)
        _set_policy(monkeypatch, "dp:ring:bf16")
        got = shard_map(body_policy, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P(), **_smap_kw())(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_explicit_wire_keeps_precedence_over_flat_override(
            self, mesh8, monkeypatch):
        """Compression's explicit wire wins over the policy entry."""
        _set_policy(monkeypatch, "dp:ring:bf16")
        x = jnp.asarray(np.random.RandomState(8).randint(
            -40, 40, (8, 128)), jnp.float32)

        def body(xl):
            return dev.fused_allreduce([xl[0]], "dp", ReduceOp.AVERAGE,
                                       wire_dtype=jnp.float32)[0]

        got = shard_map(body, mesh=mesh8, in_specs=(P("dp"),),
                        out_specs=P(), **_smap_kw())(x)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(x).mean(0))


# ---------------------------------------------------------------------------
# composition with the overlap scheduler (HVDT_OVERLAP bucket schedules)
# ---------------------------------------------------------------------------


class TestOverlapComposition:
    @pytest.fixture()
    def overlap_on(self, monkeypatch):
        monkeypatch.setenv("HVDT_OVERLAP", "on")
        ovl.reset()
        ovl.reset_accounting()
        yield ovl.get_scheduler()
        ovl.reset()

    def test_bitwise_parity_through_overlap_buckets(self, mesh_hier,
                                                    overlap_on,
                                                    monkeypatch):
        tree = _int_tree(10)
        want = _flat_reduce(mesh_hier, tree)
        _set_policy(monkeypatch, "auto")

        def body(*leaves):
            out = overlap_on.exchange(
                dict(zip("abc", leaves)), ("dcn", "ici"),
                ReduceOp.AVERAGE, threshold_bytes=4096)
            return out["a"], out["b"], out["c"]

        got = shard_map(body, mesh=mesh_hier,
                        in_specs=(P(("dcn", "ici")),) * 3,
                        out_specs=(P(),) * 3, **_smap_kw())(
                            *tree.values())
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_int8_slow_axis_through_overlap(self, mesh_hier, overlap_on,
                                            monkeypatch):
        rng = np.random.RandomState(11)
        x = jnp.asarray(rng.randn(8, 600), jnp.float32)
        _set_policy(monkeypatch, "ici:ring:f32,dcn:tree:int8")

        def body(xl):
            return overlap_on.exchange({"x": xl[0]}, ("dcn", "ici"),
                                       ReduceOp.AVERAGE)["x"]

        got = shard_map(body, mesh=mesh_hier,
                        in_specs=(P(("dcn", "ici")),), out_specs=P(),
                        **_smap_kw())(x)
        tol = 4 * np.abs(np.asarray(x)).max() / 127.0 + 1e-6
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(x).mean(0), atol=tol)

    def test_allreduce_gradients_end_to_end(self, mesh_hier, overlap_on,
                                            monkeypatch):
        """optimizer.allreduce_gradients -> exchange_fn() -> overlap
        scheduler -> hierarchical path, vs the everything-off flat
        reference."""
        tree = _int_tree(12)

        def run():
            def body(*leaves):
                out = hvd_opt.allreduce_gradients(
                    dict(zip("abc", leaves)), axis=("dcn", "ici"))
                return out["a"], out["b"], out["c"]

            return shard_map(body, mesh=mesh_hier,
                             in_specs=(P(("dcn", "ici")),) * 3,
                             out_specs=(P(),) * 3, **_smap_kw())(
                                 *tree.values())

        _set_policy(monkeypatch, "auto")
        got = run()
        monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
        monkeypatch.delenv("HVDT_OVERLAP", raising=False)
        transport.reset()
        ovl.reset()
        want = run()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_policy_threshold_feeds_overlap_schedule(self, mesh_hier,
                                                     overlap_on,
                                                     monkeypatch):
        """The per-axis fusion threshold reaches the scheduler's bucket
        plan: a tiny ici threshold forces a multi-bucket schedule and
        the accounting reports hidden (hierarchical) bytes."""
        _set_policy(monkeypatch, "ici:ring:f32:1K,dcn:tree:f32")
        ovl.reset_accounting()
        tree = _int_tree(13)

        def body(*leaves):
            out = overlap_on.exchange(dict(zip("abc", leaves)),
                                      ("dcn", "ici"), ReduceOp.AVERAGE)
            return out["a"], out["b"], out["c"]

        shard_map(body, mesh=mesh_hier,
                  in_specs=(P(("dcn", "ici")),) * 3,
                  out_specs=(P(),) * 3, **_smap_kw())(*tree.values())
        sched = ovl.last_schedule()
        assert sched is not None and sched["buckets"] > 1
        assert ovl.overlap_fraction() > 0


# ---------------------------------------------------------------------------
# per-axis telemetry (satellite: axis label + hvdt_wire_bytes_total)
# ---------------------------------------------------------------------------


class TestTelemetryAxis:
    @pytest.fixture()
    def telemetry_on(self, monkeypatch):
        from horovod_tpu.telemetry import instrument as tinst
        from horovod_tpu.telemetry import metrics as tmetrics

        monkeypatch.setenv("HVDT_TELEMETRY", "1")
        monkeypatch.setenv("HVDT_METRICS_PORT", "0")
        tmetrics.reset_default_registry()
        tinst.reset()
        yield tmetrics.default_registry()
        tmetrics.reset_default_registry()
        tinst.reset()

    def test_hierarchical_books_per_axis_wire_bytes(self, mesh_hier,
                                                    telemetry_on,
                                                    monkeypatch):
        _set_policy(monkeypatch, "auto")
        x = jnp.ones((8, 256), jnp.float32)

        def body(xl):
            return dev.fused_allreduce([xl[0]], ("dcn", "ici"),
                                       ReduceOp.AVERAGE)[0]

        shard_map(body, mesh=mesh_hier, in_specs=(P(("dcn", "ici")),),
                  out_specs=P(), **_smap_kw())(x)
        wb = telemetry_on.get("hvdt_wire_bytes_total")
        # ring RS over ici (k=4): 3/4 of the 1 KiB shard, twice (RS+AG)
        assert wb.value(axis="ici", wire="f32") == 2 * 256 * 4 * 3 // 4
        # the slow tier exchanges the 1/4 shard: 2*(1/2)*256 B
        assert wb.value(axis="dcn", wire="f32") == 256
        c = telemetry_on.get("hvdt_collective_bytes_total")
        assert c.value(op="reduce_scatter", dtype="float32", wire="f32",
                       path="jit", axis="ici") > 0
        assert c.value(op="allreduce", dtype="float32", wire="f32",
                       path="jit", axis="dcn") > 0

    def test_flight_recorder_event_carries_axis(self, mesh_hier,
                                                monkeypatch):
        from horovod_tpu.telemetry import flight_recorder as frm

        monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
        frm.reset()
        _set_policy(monkeypatch, "auto")
        x = jnp.ones((8, 64), jnp.float32)

        def body(xl):
            return dev.fused_allreduce([xl[0]], ("dcn", "ici"),
                                       ReduceOp.AVERAGE)[0]

        shard_map(body, mesh=mesh_hier, in_specs=(P(("dcn", "ici")),),
                  out_specs=P(), **_smap_kw())(x)
        evs = [e for e in frm.get_flight_recorder().events()
               if e["name"].startswith("hier.")]
        assert evs and evs[0]["axis"] == "dcn+ici"
        assert evs[0]["wire"] == "f32/f32"
        frm.reset()


# ---------------------------------------------------------------------------
# autotune transport dimension
# ---------------------------------------------------------------------------


class TestAutotuneTransportDimension:
    def test_parameter_manager_gains_transport_column(self):
        from horovod_tpu.autotune import ParameterManager

        pm = ParameterManager(tune_transport=True, tune_overlap=False,
                              tune_quant=False,
                              tune_fused_optimizer=False)
        assert pm._bo.candidates.shape[1] == 3
        pm._current = np.array([24.0, 1.0, 1.0])
        assert pm.transport_policy is True
        pm._current = np.array([24.0, 1.0, 0.0])
        assert pm.transport_policy is False
        pm6 = ParameterManager(tune_transport=True, tune_overlap=True,
                               tune_quant=True,
                               tune_fused_optimizer=True)
        assert pm6._bo.candidates.shape[1] == 6

    def test_env_transport_starting_leg(self, monkeypatch, tmp_path):
        from horovod_tpu.autotune import _env_transport

        monkeypatch.delenv("HVDT_AUTOTUNE_TRANSPORT_SEED", raising=False)
        assert _env_transport() is False
        _set_policy(monkeypatch, "auto")
        assert _env_transport() is True

    def test_seed_file_verdict(self, monkeypatch, tmp_path):
        """Satellite: the transport dimension seeds from MEASURED
        bench_allreduce output — speedup > 1 starts hierarchical."""
        from horovod_tpu.autotune import _env_transport

        seed = tmp_path / "sweep.json"
        seed.write_text(json.dumps(
            {"hierarchical_speedup_vs_flat_at_peak": 1.31}))
        monkeypatch.setenv("HVDT_AUTOTUNE_TRANSPORT_SEED", str(seed))
        assert _env_transport() is True
        seed.write_text(json.dumps(
            {"hierarchical_speedup_vs_flat_at_peak": 0.97}))
        assert _env_transport() is False
        seed.write_text("not json")
        assert _env_transport() is False
        monkeypatch.setenv("HVDT_AUTOTUNE_TRANSPORT_SEED",
                           str(tmp_path / "missing.json"))
        assert _env_transport() is False

    def test_autotuned_step_forwards_transport_kw(self, monkeypatch):
        from horovod_tpu.autotune import AutotunedStep

        monkeypatch.setenv("HVDT_AUTOTUNE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_TRANSPORT", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_WARMUP_SAMPLES", "0")
        seen = []

        def builder(threshold_bytes, transport=False):
            seen.append((threshold_bytes, transport))

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
        assert all(isinstance(t, (bool, np.bool_)) for _, t in seen)

    def test_hot_swap_shares_state_and_compiled_legs(self, mesh_hier,
                                                     monkeypatch):
        """Acceptance: autotune can flip a live step between the flat
        and hierarchical legs with SHARED optimizer state, and flipping
        back must reuse the flat leg's compiled program (no re-jit)."""
        rng = np.random.RandomState(15)
        grads = {"w": jnp.asarray(rng.randint(-40, 40, (8, 16, 8)),
                                  jnp.float32)}
        params = {"w": jnp.zeros((16, 8))}
        legs = {}
        compiles = {"n": 0}

        def build(threshold_bytes, transport):
            key = bool(transport)
            if key in legs:
                return legs[key]
            if transport:
                monkeypatch.setenv("HVDT_TRANSPORT", "auto")
            else:
                monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
            import horovod_tpu.transport as _t

            _t.reset()
            tx = hvd_opt.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9), axis=("dcn", "ici"),
                threshold_bytes=512)
            state = tx.init(params)

            def body(w, s):
                u, s2 = tx.update({"w": w[0]}, s, params)
                return u["w"], s2

            smapped = shard_map(
                body, mesh=mesh_hier,
                in_specs=(P(("dcn", "ici")), P()),
                out_specs=(P(), P()), **_smap_kw())

            @jax.jit
            def step(w, s):
                compiles["n"] += 1   # counted at trace time
                return smapped(w, s)

            legs[key] = (step, state)
            return legs[key]

        step_flat, state = build(None, transport=False)
        u_flat, _ = step_flat(grads["w"], state)
        n_after_flat = compiles["n"]
        step_hier, state_hier = build(1 << 20, transport=True)
        # one optimizer state tree across both legs (hot-swap contract)
        assert jax.tree.structure(state) == jax.tree.structure(state_hier)
        u_hier, _ = step_hier(grads["w"], state)
        # flipping BACK to the flat leg reuses the cached program
        step_flat2, _ = build(1 << 20, transport=False)
        assert step_flat2 is step_flat
        u_flat2, _ = step_flat2(grads["w"], state)
        assert compiles["n"] == n_after_flat + 1, \
            "flat leg recompiled when the transport leg flipped"
        np.testing.assert_array_equal(np.asarray(u_flat),
                                      np.asarray(u_flat2))
        # integer-valued grads: hierarchical == flat bitwise
        np.testing.assert_array_equal(np.asarray(u_flat),
                                      np.asarray(u_hier))


# ---------------------------------------------------------------------------
# bench rows (satellite: axis/algorithm/hierarchical_speedup_vs_flat)
# ---------------------------------------------------------------------------


@pytest.mark.integration
class TestBenchHierarchicalSweep:
    def test_sweep_emits_per_axis_rows_and_verdict(self, tmp_path):
        import os
        import subprocess
        import sys

        out = tmp_path / "sweep.json"
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        env.pop("HVDT_TRANSPORT", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench_allreduce.py"),
             "--hierarchical", "--min-bytes", "4096",
             "--max-bytes", "4096", "--iters", "1", "--warmup", "0",
             "--inner", "1", "--json-out", str(out)],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=200)
        assert proc.returncode == 0, proc.stderr[-2000:]
        doc = json.loads(out.read_text())
        assert doc["schema_version"] >= 1
        axes = {r["axis"] for r in doc["rows"]}
        assert axes == {"ici", "dcn", "ici+dcn"}
        combined = [r for r in doc["rows"] if r["axis"] == "ici+dcn"]
        by_alg = {r["algorithm"] for r in combined}
        assert by_alg == {"flat", "hierarchical"}
        hier = [r for r in combined if r["algorithm"] == "hierarchical"]
        assert hier[0]["hierarchical_speedup_vs_flat"] > 0
        assert doc["hierarchical_speedup_vs_flat_at_peak"] > 0
        assert doc["mesh"] == {"dcn": 2, "ici": 4}
        for r in doc["rows"]:
            # the normalized fitter schema every row carries
            assert {"axis", "algorithm", "wire", "bytes_on_wire",
                    "size_bytes", "seconds", "axis_size"} <= set(r)
            assert r["seconds"] > 0 and r["axis_size"] >= 2
