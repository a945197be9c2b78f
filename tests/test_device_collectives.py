"""Device-collective correctness over an 8-device mesh.

Reference analog: test/parallel/test_torch.py TorchTests — per-collective
correctness incl. average/prescale/postscale (test_torch.py:59+), here
expressed through shard_map over a simulated 8-device CPU mesh (SURVEY.md §4).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu.common.types import ReduceOp
from horovod_tpu.ops import device as dev


def _per_rank(mesh, fn, x, in_spec=P("dp"), out_spec=P("dp")):
    return shard_map(fn, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec)(x)


def test_allreduce_sum(mesh8):
    x = jnp.arange(8.0 * 4).reshape(8, 4)
    out = _per_rank(mesh8, lambda t: dev.allreduce(t, "dp", ReduceOp.SUM), x)
    expected = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_allreduce_average(mesh8):
    x = jnp.arange(8.0 * 4).reshape(8, 4)
    out = _per_rank(mesh8, lambda t: dev.allreduce(t, "dp", ReduceOp.AVERAGE), x)
    expected = np.tile(np.asarray(x).mean(0, keepdims=True), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


@pytest.mark.parametrize("op,np_fn", [(ReduceOp.MIN, np.min), (ReduceOp.MAX, np.max)])
def test_allreduce_minmax(mesh8, op, np_fn):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 5), dtype=jnp.float32)
    out = _per_rank(mesh8, lambda t: dev.allreduce(t, "dp", op), x)
    expected = np.tile(np_fn(np.asarray(x), axis=0, keepdims=True), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_allreduce_prescale_postscale(mesh8):
    x = jnp.ones((8, 3))
    out = _per_rank(
        mesh8,
        lambda t: dev.allreduce(t, "dp", ReduceOp.SUM,
                                prescale_factor=0.5, postscale_factor=2.0),
        x)
    np.testing.assert_allclose(out, np.full((8, 3), 8.0), rtol=1e-6)


def test_allgather(mesh8):
    x = jnp.arange(8.0 * 2).reshape(8, 2)
    out = _per_rank(mesh8, lambda t: dev.allgather(t, "dp"), x,
                    out_spec=P("dp"))
    # each rank's output block is the full gathered array (8,2) → global (64,2)
    assert out.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(out)[:8], np.asarray(x))


def test_reduce_scatter(mesh8):
    # every rank holds the same (8, 4) block; reduce_scatter sums over ranks
    # and hands rank r the r-th row → stacking shards reconstructs 8*x.
    x = jnp.arange(8.0 * 4).reshape(8, 4)
    out = _per_rank(mesh8, lambda t: dev.reduce_scatter(t, "dp"), x,
                    in_spec=P(), out_spec=P("dp"))
    assert out.shape == (8, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 8.0)


def test_reduce_scatter_average(mesh8):
    x = jnp.arange(8.0 * 4).reshape(8, 4)
    out = _per_rank(
        mesh8,
        lambda t: dev.reduce_scatter(t, "dp", op=ReduceOp.AVERAGE), x,
        in_spec=P(), out_spec=P("dp"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_broadcast(mesh8):
    x = jnp.arange(8.0)[:, None] * jnp.ones((8, 3))  # rank r holds r's
    out = _per_rank(mesh8, lambda t: dev.broadcast(t, root_rank=3, axis="dp"), x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 3), 3.0))


def test_broadcast_int(mesh8):
    x = (jnp.arange(8)[:, None] * jnp.ones((8, 2), jnp.int32)).astype(jnp.int32)
    out = _per_rank(mesh8, lambda t: dev.broadcast(t, root_rank=5, axis="dp"), x)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.full((8, 2), 5, np.int32))


def test_alltoall(mesh8):
    # rank r sends value 100*r+c to rank c (per-rank block: 8 values)
    x = jnp.asarray([100 * r + c for r in range(8) for c in range(8)],
                    dtype=jnp.float32)
    out = _per_rank(mesh8, lambda t: dev.alltoall(t, "dp"), x)
    expected = np.asarray([100 * c + r for r in range(8) for c in range(8)],
                          dtype=np.float32)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_axis_rank_size(mesh8):
    out = _per_rank(mesh8,
                    lambda t: t * 0 + dev.axis_rank("dp") + dev.axis_size("dp"),
                    jnp.zeros((8, 1)))
    np.testing.assert_allclose(np.asarray(out)[:, 0], np.arange(8) + 8)


def test_fused_allreduce_pytree(mesh8):
    tree = {
        "w": jnp.ones((8, 4, 3)),
        "b": jnp.arange(8.0)[:, None] * jnp.ones((8, 5)),
        "i_cast": jnp.ones((8, 2), jnp.bfloat16),
    }
    fn = lambda t: dev.fused_allreduce(t, "dp", ReduceOp.SUM,
                                       threshold_bytes=1 << 20)
    out = shard_map(fn, mesh=mesh8,
                    in_specs=({"w": P("dp"), "b": P("dp"), "i_cast": P("dp")},),
                    out_specs={"w": P("dp"), "b": P("dp"), "i_cast": P("dp")})(tree)
    np.testing.assert_allclose(np.asarray(out["w"]), np.full((8, 4, 3), 8.0))
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.full((8, 5), np.arange(8.0).sum()))
    assert out["i_cast"].dtype == jnp.bfloat16


def test_fused_allreduce_bucket_planning():
    leaves = [jnp.ones((1024,), jnp.float32),   # 4 KiB
              jnp.ones((1024,), jnp.float32),
              jnp.ones((16,), jnp.int32),
              jnp.ones((1024,), jnp.float32)]
    buckets = dev.fused_allreduce_buckets(leaves, threshold_bytes=8192)
    # three f32 leaves: two fit per 8 KiB bucket; int32 goes separately
    assert sorted(len(b) for b in buckets) == [1, 1, 2]
    covered = sorted(i for b in buckets for i in b)
    assert covered == [0, 1, 2, 3]


def test_fused_allreduce_wire_dtype(mesh8):
    tree = [jnp.full((8, 64), 1.5, jnp.float32)]
    fn = lambda t: dev.fused_allreduce(t, "dp", ReduceOp.SUM,
                                       wire_dtype=jnp.bfloat16)
    out = shard_map(fn, mesh=mesh8, in_specs=([P("dp")],),
                    out_specs=[P("dp")])(tree)
    assert out[0].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out[0]), np.full((8, 64), 12.0),
                               rtol=1e-2)


def test_allreduce_product_mixed_signs(mesh8):
    vals = np.asarray([1.0, -2.0, 3.0, -1.0, 0.5, 1.0, 2.0, -1.0], np.float32)
    x = jnp.asarray(vals)[:, None]
    out = _per_rank(mesh8,
                    lambda t: dev.allreduce(t, "dp", ReduceOp.PRODUCT), x)
    np.testing.assert_allclose(np.asarray(out)[:, 0],
                               np.full(8, vals.prod()), rtol=1e-5)


def test_allreduce_product_with_zero(mesh8):
    vals = np.asarray([1.0, -2.0, 0.0, -1.0, 0.5, 1.0, 2.0, -1.0], np.float32)
    x = jnp.asarray(vals)[:, None]
    out = _per_rank(mesh8,
                    lambda t: dev.allreduce(t, "dp", ReduceOp.PRODUCT), x)
    np.testing.assert_allclose(np.asarray(out)[:, 0], np.zeros(8))


def test_broadcast_ignores_nan_on_nonroot(mesh8):
    # non-root shards hold NaN (uninitialized buffers); broadcast must not
    # let them poison the result
    vals = np.full((8, 2), np.nan, np.float32)
    vals[2] = 7.0
    out = _per_rank(mesh8,
                    lambda t: dev.broadcast(t, root_rank=2, axis="dp"),
                    jnp.asarray(vals))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 2), 7.0))


class TestHierarchicalAllreduce:
    @pytest.mark.parametrize("op_name", ["SUM", "AVERAGE"])
    def test_matches_flat_allreduce(self, hvd, op_name):
        """Two-level (2x4 mesh) hierarchical == flat allreduce over both
        axes (ref: NCCLHierarchicalAllreduce equivalence)."""
        from horovod_tpu.common.types import ReduceOp
        from horovod_tpu.ops import device
        from horovod_tpu.parallel import make_mesh

        op = ReduceOp[op_name]
        mesh = make_mesh(dp=2, tp=4, devices=jax.devices()[:8])

        # 8 distinct contributions; element count NOT divisible by the
        # inner axis (exercises padding)
        xs = jnp.arange(8.0 * 13).reshape(8, 13)

        def local(x):
            x = x.reshape(13)
            return device.hierarchical_allreduce(
                x, inner_axis="tp", outer_axis="dp", op=op)

        got = shard_map(
            local, mesh=mesh,
            in_specs=P(("dp", "tp")), out_specs=P())(xs)
        want = xs.sum(0) if op == ReduceOp.SUM else xs.mean(0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_prescale_postscale(self, hvd):
        from horovod_tpu.common.types import ReduceOp
        from horovod_tpu.ops import device
        from horovod_tpu.parallel import make_mesh

        mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
        xs = jnp.ones((4, 4))

        got = shard_map(
            lambda x: device.hierarchical_allreduce(
                x.reshape(4), inner_axis="tp", outer_axis="dp",
                op=ReduceOp.SUM, prescale_factor=2.0,
                postscale_factor=0.5),
            mesh=mesh, in_specs=P(("dp", "tp")), out_specs=P())(xs)
        np.testing.assert_allclose(np.asarray(got), np.full(4, 4.0))


class TestShardedAdasum:
    @pytest.mark.parametrize("count", [64, 61])  # 61: pad path
    def test_matches_host_tree(self, hvd, count):
        """The sharded jit Adasum equals the host binary tree on full
        vectors (exact dots via psum)."""
        from horovod_tpu.ops.adasum import _np_adasum_tree, adasum_allreduce

        n = 8
        rng = np.random.default_rng(3)
        inputs = rng.normal(size=(n, count)).astype(np.float32)
        mesh = hvd.mesh()

        got = shard_map(
            lambda x: adasum_allreduce(x.reshape(count), axis="dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P())(
                jnp.asarray(inputs).reshape(n * count))
        want = _np_adasum_tree(list(inputs))
        np.testing.assert_allclose(np.asarray(got), want.astype(np.float32),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("op,np_fn", [
    (ReduceOp.MIN, np.min), (ReduceOp.MAX, np.max),
    (ReduceOp.PRODUCT, np.prod)])
def test_reduce_scatter_min_max_product(mesh8, op, np_fn):
    # rank r holds a distinct (8, 3) block; rank r's output row-block is the
    # elementwise op over all ranks' r-th slice (scatter dim = 1 row/rank).
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.uniform(0.5, 2.0, size=(8, 8, 3)), jnp.float32)
    out = _per_rank(
        mesh8, lambda t: dev.reduce_scatter(t[0], "dp", op=op), x,
        in_spec=P("dp"), out_spec=P("dp"))
    expected = np_fn(np.asarray(x), axis=0)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_allgather_ragged(mesh8):
    # rank r contributes r+1 valid rows (padded to 8); result is the exact
    # sum(sizes)-row concatenation, identical on every rank.
    sizes = [r + 1 for r in range(8)]
    blocks = [np.full((sizes[r], 2), 10 * r, np.float32) + np.arange(
        sizes[r], dtype=np.float32)[:, None] for r in range(8)]
    padded = np.stack([
        np.concatenate([b, np.full((8 - len(b), 2), -1, np.float32)])
        for b in blocks])
    out = _per_rank(
        mesh8, lambda t: dev.allgather_ragged(t[0], sizes, "dp"),
        jnp.asarray(padded), in_spec=P("dp"), out_spec=P("dp"))
    expected = np.concatenate(blocks)          # (36, 2)
    assert out.shape == (8 * 36, 2)
    for r in range(8):                         # every rank sees the same
        np.testing.assert_allclose(np.asarray(out)[r * 36:(r + 1) * 36],
                                   expected)


def test_allgather_ragged_rejects_bad_pad(mesh8):
    with pytest.raises(ValueError, match="padded to max"):
        _per_rank(mesh8,
                  lambda t: dev.allgather_ragged(t[0], [1] * 8, "dp"),
                  jnp.zeros((8, 4, 2)), in_spec=P("dp"), out_spec=P("dp"))


def test_alltoall_uneven(mesh8):
    # splits[r][j] = (r + j) % 3; pad rows so every rank's sends sum to the
    # same input length.
    n = 8
    M = [[(r + j) % 3 for j in range(n)] for r in range(n)]
    in_rows = max(sum(row) for row in M)
    for row in M:                              # top-up last split to equalize
        row[-1] += in_rows - sum(row)
    rng = np.random.RandomState(2)
    data = [rng.randn(in_rows, 2).astype(np.float32) for _ in range(n)]

    def body(t):
        out, cnt = dev.alltoall_uneven(t[0], M, "dp")
        return out, jnp.broadcast_to(cnt, (1,))

    out, cnts = _per_rank(mesh8, body, jnp.stack(data),
                          in_spec=P("dp"), out_spec=(P("dp"), P("dp")))
    recv_totals = [sum(M[r][j] for r in range(n)) for j in range(n)]
    max_out = max(recv_totals)
    assert out.shape == (n * max_out, 2)
    np.testing.assert_array_equal(np.asarray(cnts), recv_totals)
    for j in range(n):                         # reassemble expected recv
        parts, got = [], np.asarray(out)[j * max_out:(j + 1) * max_out]
        for r in range(n):
            off = sum(M[r][:j])
            parts.append(data[r][off:off + M[r][j]])
        expected = np.concatenate(parts) if parts else np.zeros((0, 2))
        np.testing.assert_allclose(got[:recv_totals[j]], expected, rtol=1e-6)
        np.testing.assert_allclose(got[recv_totals[j]:], 0.0)


def test_alltoall_uneven_rejects_bad_splits(mesh8):
    with pytest.raises(ValueError, match="sum to the same"):
        M = [[1] * 8 for _ in range(8)]
        M[3][0] = 2                            # rank 3 sends 9 rows, others 8
        _per_rank(mesh8,
                  lambda t: dev.alltoall_uneven(t[0], M, "dp")[0],
                  jnp.zeros((8, 8, 2)), in_spec=P("dp"), out_spec=P("dp"))


# ---------------------------------------------------------------------------
# fused_allreduce: a bucket is a collective; its payload's form follows the
# wire (PR 47).  The exact and the cast wires send the leaves as they are,
# the wires that cut the payload up (block-scaled, reduce-scatter) and Adasum
# keep one flat vector.
# ---------------------------------------------------------------------------


def _flat_oracle(leaves, axis, op, threshold_bytes, prescale=1.0,
                 postscale=1.0, wire_dtype=None):
    """The flat-buffer exchange as it stood before PR 47, on the same
    bucket plan: ravel + concatenate, one reduction of the vector, slice
    + reshape back."""
    out = [None] * len(leaves)
    for bucket in dev.fused_allreduce_buckets(leaves, threshold_bytes):
        parts = [leaves[i] for i in bucket]
        flat = jnp.concatenate([jnp.ravel(p) for p in parts])
        orig = flat.dtype
        if wire_dtype is not None:
            flat = flat.astype(wire_dtype)
        red = dev.allreduce(flat, axis, op, prescale, postscale)
        red = red.astype(orig)
        offset = 0
        for i in bucket:
            out[i] = jax.lax.dynamic_slice_in_dim(
                red, offset, leaves[i].size).reshape(leaves[i].shape)
            offset += leaves[i].size
    return out


def _mixed_leaves(seed=0):
    """Per-rank leaves of unlike shapes and dtypes, stacked over 8 ranks."""
    rng = np.random.RandomState(seed)
    f32 = lambda *s: jnp.asarray(rng.randn(8, *s), jnp.float32)
    return [f32(3, 5), f32(7), f32(), f32(4, 2, 3),
            jnp.asarray(rng.randn(8, 6, 2), jnp.bfloat16),
            jnp.asarray(rng.randint(-50, 50, (8, 9)), jnp.int32),
            jnp.asarray(rng.randn(8, 11), jnp.bfloat16)]


@pytest.mark.parametrize("threshold", [64, 1 << 20],
                         ids=["a_leaf_a_bucket", "many_leaves_a_bucket"])
@pytest.mark.parametrize("wire", [None, jnp.bfloat16], ids=["exact", "bf16"])
@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN,
                                ReduceOp.MAX], ids=lambda op: op.name)
def test_fused_allreduce_on_leaves_equals_the_flat_buffer_bitwise(
        mesh8, op, wire, threshold):
    leaves = _mixed_leaves()
    plan = dev.fused_allreduce_buckets([l[0] for l in leaves], threshold)
    assert (max(map(len, plan)) == 1) == (threshold == 64)

    pre, post = (0.5, 3.0) if op == ReduceOp.SUM else (1.0, 1.0)

    def body(*ls):
        ls = list(ls)
        got = dev.fused_allreduce(ls, "dp", op, threshold_bytes=threshold,
                                  prescale_factor=pre, postscale_factor=post,
                                  wire_dtype=wire)
        want = _flat_oracle(ls, "dp", op, threshold, pre, post, wire)
        return tuple(got), tuple(want)

    n = len(leaves)
    got, want = shard_map(body, mesh=mesh8, in_specs=(P("dp"),) * n,
                          out_specs=((P(),) * n, (P(),) * n))(*leaves)
    for g, w, leaf in zip(got, want, leaves):
        assert g.dtype == w.dtype == leaf.dtype
        assert g.shape == w.shape == (1,) + leaf.shape[1:]
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


def _exchange_ops(text):
    """(op, operand types, result types) of every StableHLO operation
    under the ``hvdt.exchange`` scope in
    ``Lowered.as_text(debug_info=True)``; a region op (all_reduce) carries
    its types and location on the line that closes it."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found, opened = [], None
    for line in text.splitlines():
        op = re.search(r'"?(stablehlo\.\w+)"?', line)
        where = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if line.rstrip().endswith("({"):
            opened = op.group(1)
            continue
        if line.lstrip().startswith("}) :"):
            op_name, opened = opened, None
        elif op and where:
            op_name = op.group(1)
        else:
            continue
        if where and "hvdt.exchange" in names.get(where.group(1), ""):
            types = line.split(" : ", 1)[1] if " : " in line else ""
            found.append((op_name, *(re.findall(r"tensor<([^>]*)>", side)
                                     for side in types.partition("->")[::2])))
    return found


_STEP_PARAMS = {"w": (6, 8, 16), "b": (16,), "e": (32, 8), "g": ()}


def _dp_step_text(hvd, mesh, axis, **optimizer_kwargs):
    """The lowered text of a step shaped like ``benchmark/harness.py``
    ``build_dp_step``: per-rank gradients of ``pvary_tree``'d parameters
    into ``DistributedOptimizer``'s update."""
    import optax

    opt = hvd.DistributedOptimizer(optax.adamw(1e-3), axis=axis,
                                   **optimizer_kwargs)
    params = {k: jnp.ones(s) for k, s in _STEP_PARAMS.items()}
    state = opt.init(params)

    def local_step(params, state, x):
        diff = hvd.optimizer.pvary_tree(params, axis)
        loss, grads = jax.value_and_grad(
            lambda p: sum((l ** 2).sum() for l in jax.tree.leaves(p))
            * x.mean())(diff)
        updates, state = opt.update(grads, state, params)
        return (optax.apply_updates(params, updates), state,
                jax.lax.pmean(loss, axis))

    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh, in_specs=(P(), P(), P(axis)),
        out_specs=(P(), P(), P())))
    return step.lower(params, state,
                      jnp.ones((16, 4))).as_text(debug_info=True)


_RELAYOUTS = {"stablehlo.reshape", "stablehlo.concatenate",
              "stablehlo.dynamic_slice"}


@pytest.mark.parametrize("wire,elem", [("none", "f32"), ("bf16", "bf16")])
def test_a_dp_step_exchanges_the_leaves_in_their_own_shapes(hvd, mesh8, wire,
                                                            elem):
    from horovod_tpu.ops.compression import Compression

    ops = _exchange_ops(_dp_step_text(
        hvd, mesh8, "dp", compression=getattr(Compression, wire)))
    assert not _RELAYOUTS & {op for op, _, _ in ops}
    reduced = sorted(t[0] for op, t, _ in ops
                     if op == "stablehlo.all_reduce")
    leaves = sorted("x".join(map(str, s)) + ("x" if s else "") + elem
                    for s in _STEP_PARAMS.values())
    assert reduced == leaves


def _int8_step(hvd, mesh8, monkeypatch):
    from horovod_tpu.ops.compression import Compression

    return _dp_step_text(hvd, mesh8, "dp", compression=Compression.int8)


def _hierarchical_step(hvd, mesh8, monkeypatch):
    from jax.sharding import Mesh

    from horovod_tpu import transport

    monkeypatch.setenv("HVDT_TRANSPORT", "auto")
    transport.reset()
    try:
        return _dp_step_text(
            hvd, Mesh(mesh8.devices.reshape(2, 4), ("dcn", "ici")),
            ("dcn", "ici"))
    finally:
        transport.reset()


def _adasum_step(hvd, mesh8, monkeypatch):
    return _dp_step_text(hvd, mesh8, "dp", op=ReduceOp.ADASUM)


@pytest.mark.parametrize("lower,collective", [
    (_int8_step, "stablehlo.all_to_all"),
    (_hierarchical_step, "stablehlo.reduce_scatter"),
    (_adasum_step, "stablehlo.all_to_all"),
], ids=["int8", "hierarchical", "adasum"])
def test_wires_that_cut_the_payload_up_keep_the_flat_bucket(
        hvd, mesh8, monkeypatch, lower, collective):
    ops = _exchange_ops(lower(hvd, mesh8, monkeypatch))
    assert {"stablehlo.dynamic_slice", collective} <= {op for op, _, _ in ops}
    # The bucket's leaves are packed into one vector of their total size.
    total = sum(int(np.prod(s)) for s in _STEP_PARAMS.values())
    assert [f"{total}xf32"] in [out for op, _, out in ops
                                if op == "stablehlo.concatenate"]


@pytest.mark.parametrize("op,payload", [(ReduceOp.SUM, "leaves"),
                                        (ReduceOp.ADASUM, "flat")])
def test_the_recorders_say_which_form_a_bucket_took(mesh8, monkeypatch, op,
                                                    payload):
    from horovod_tpu.telemetry import flight_recorder as frm
    from horovod_tpu.telemetry import instrument as tinst
    from horovod_tpu.telemetry import metrics as tmetrics

    monkeypatch.setenv("HVDT_TELEMETRY", "1")
    monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
    tmetrics.reset_default_registry()
    tinst.reset()
    frm.reset()
    try:
        leaves = [jnp.ones((8, 64, 4)), jnp.ones((8, 256))]
        shard_map(lambda a, b: tuple(dev.fused_allreduce([a, b], "dp", op)),
                  mesh=mesh8, in_specs=(P("dp"), P("dp")),
                  out_specs=(P(), P()))(*leaves)
        (event,) = [e for e in frm.get_flight_recorder().events()
                    if e["name"] == "fused.b0"]
        # Either form: the leaves a collective carries and their size.
        assert (event["payload"], event["count"], event["shape"]) == (
            payload, 2, [512])
        count = tmetrics.default_registry().get("hvdt_collectives_total")
        assert count.value(op="allreduce", dtype="float32", wire="float32",
                           path="jit", axis="dp", payload=payload) == 2
    finally:
        tmetrics.reset_default_registry()
        tinst.reset()
        frm.reset()
