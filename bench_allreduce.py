"""Allreduce bus-bandwidth microbenchmark: the reference's primary metric.

The reference's headline numbers are allreduce scaling efficiency measured
with dedicated benchmark harnesses (ref: docs/benchmarks.rst:8-43; the
synthetic harnesses :64-80).  This sweeps message sizes through the
data-plane allreduce on the dp mesh and reports, per size:

* ``algbw`` — algorithm bandwidth: message bytes / op time;
* ``busbw`` — bus bandwidth: ``algbw * 2(n-1)/n``, the ring-allreduce
  wire-traffic accounting, comparable across device counts (the
  convention the reference's NCCL-based numbers use).

Paths measured:

* ``jit`` (default) — the XLA device collective (``psum`` over the dp
  mesh axis), i.e. what ``DistributedOptimizer``'s fused gradient
  allreduce lowers to.  On multi-chip TPU this rides ICI.
* ``eager`` (``--eager``) — the negotiated eager path
  (``hvd.allreduce``), measuring the full controller+data-plane
  round trip per op (the reference's per-op latency analog).

``--wire {f32,bf16,fp16,int8,int4}`` selects the wire format of the jit leg:
dtype casts around the psum for bf16/fp16 (``Compression.bf16/.fp16``),
or the block-scaled quantized two-stage collective for int8/int4
(``Compression.int8`` / ``.int4`` — horovod_tpu/quant; int4 packs two
4-bit lanes per byte on the wire).  Non-f32 wires also time
the f32 leg and report ``speedup_vs_f32``; ``--json-out FILE`` writes
the sweep (bytes_on_wire, GB/s, speedup) as a JSON result file
(what ``tools/fit_costmodel.py`` and the autotune seeds read).

``--hierarchical`` measures the transport-policy data plane
(horovod_tpu/transport) on a two-level (outer × inner) mesh: per size
it times the flat psum over both axes, the hierarchical allreduce under
``--transport`` (default ``auto``), and each tier in isolation —
emitting one row per (axis, algorithm, wire, size) plus a measured
``hierarchical_speedup_vs_flat`` column.  The summary's
``hierarchical_speedup_vs_flat_at_peak`` is what
``HVDT_AUTOTUNE_TRANSPORT_SEED`` reads to seed the autotuner's
transport dimension — policies are measured, not guessed.

Runs anywhere: 8-device CPU sim for correctness/CI, a TPU slice for real
numbers.  Prints one human line per size and a final JSON summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Normalized row schema (the horovod_tpu.analysis.costmodel fitter's
# input contract): every sweep row carries `axis`, `algorithm`, `wire`,
# `size_bytes`, `seconds`, `axis_size` next to its legacy columns, and
# every summary carries `schema_version`.  tools/fit_costmodel.py
# regenerates the checked-in calibration from any set of these files.
SCHEMA_VERSION = 1


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024:
            return f"{n:.0f}{unit}"
        n /= 1024
    return f"{n:.0f}TiB"


def wire_payload_bytes(count: int, dtype, wire: str) -> int:
    """Bytes one allreduce message occupies in the selected wire format
    (the compression accounting the JSON result file carries)."""
    import jax.numpy as jnp

    if wire in ("bf16", "fp16"):
        return count * 2
    if wire == "int8":
        from horovod_tpu.quant import wire_bytes

        return wire_bytes(count)
    if wire == "int4":
        from horovod_tpu.quant import wire_bytes_int4

        return wire_bytes_int4(count)
    return count * jnp.dtype(dtype).itemsize


def bench_jit(mesh, nbytes: int, dtype, inner: int, iters: int,
              warmup: int, wire: str = "f32"):
    """Per-op seconds for a chained allreduce of ``nbytes`` over the
    selected wire format."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.sharding import pcast_to_union

    n = mesh.devices.size
    count = max(1, nbytes // jnp.dtype(dtype).itemsize)
    x = jax.device_put(
        jnp.ones((n, count), dtype),
        NamedSharding(mesh, P("dp")))
    cast_to = {"bf16": jnp.bfloat16, "fp16": jnp.float16}.get(wire)

    def body(xl):
        # inner chained allreduces per call amortize dispatch overhead;
        # the 1/n rescale keeps values bounded AND makes each iteration
        # depend on the last (no overlap/elision).
        def one(_, acc):
            if wire in ("int8", "int4"):
                from horovod_tpu.common.types import ReduceOp
                from horovod_tpu.quant import quantized_allreduce_flat

                red = quantized_allreduce_flat(
                    acc.reshape(-1), "dp",
                    op=ReduceOp.AVERAGE, wire=wire).reshape(acc.shape)
            else:
                w = acc.astype(cast_to) if cast_to is not None else acc
                red = (lax.psum(w, "dp") * (1.0 / n)).astype(acc.dtype)
            # psum output is replicated; pcast back to varying so the
            # fori_loop carry type is stable.
            return pcast_to_union(red, extra=("dp",))
        return lax.fori_loop(0, inner, one, xl)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp")))

    def run_and_wait():
        # End the timed region with a host fetch of a scalar that
        # data-depends on the result.
        float(jnp.sum(f(x)[..., :1].astype(jnp.float32)))

    for _ in range(warmup):
        run_and_wait()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_and_wait()
        times.append((time.perf_counter() - t0) / inner)
    return min(times)


def _build_mesh2d(outer: int):
    """(outer × inner) mesh with ('dcn', 'ici') axes — the two-level
    topology the hierarchical sweep measures (outer = the slow tier)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs)
    if outer < 2 or n % outer:
        outer = 2 if (n >= 4 and n % 2 == 0) else 0
    if not outer:
        raise SystemExit(
            f"--hierarchical needs an even device count >= 4 to split "
            f"into (outer, inner); have {n}")
    return Mesh(np.asarray(devs, dtype=object).reshape(outer, n // outer),
                ("dcn", "ici"))


def bench_hier_jit(mesh, nbytes: int, dtype, inner: int, iters: int,
                   warmup: int, leg: str):
    """Per-op seconds for one leg of the hierarchical sweep on the
    ('dcn', 'ici') mesh: ``flat`` = psum over both axes, ``hier`` = the
    transport-policy hierarchical allreduce, ``ici``/``dcn`` = one tier
    in isolation (fast reduce-scatter+allgather / slow shard
    exchange)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.sharding import pcast_to_union

    from horovod_tpu.common.types import ReduceOp
    from horovod_tpu.ops import device as hdev

    n_dcn, n_ici = (mesh.devices.shape[0], mesh.devices.shape[1])
    n = n_dcn * n_ici
    count = max(n_ici, nbytes // jnp.dtype(dtype).itemsize)
    count -= count % n_ici      # shard evenly over the fast tier
    if leg == "dcn":
        count //= n_ici         # the slow tier moves the 1/n_ici shard
    x = jax.device_put(jnp.ones((n, count), dtype),
                       NamedSharding(mesh, P(("dcn", "ici"))))

    def body(xl):
        def one(_, acc):
            if leg == "flat":
                red = lax.psum(acc, ("dcn", "ici")) * (1.0 / n)
            elif leg == "hier":
                # fused_allreduce resolves the HVDT_TRANSPORT policy at
                # trace time and routes hierarchically.
                red = hdev.fused_allreduce(
                    [acc.reshape(-1)], ("dcn", "ici"),
                    ReduceOp.AVERAGE)[0].reshape(acc.shape)
            elif leg == "ici":
                shard = lax.psum_scatter(acc.reshape(-1), "ici",
                                         tiled=True)
                red = hdev.invariant_allgather_shards(
                    shard, "ici").reshape(acc.shape) * (1.0 / n_ici)
            else:   # dcn: the slow shard exchange in isolation
                red = lax.psum(acc, "dcn") * (1.0 / n_dcn)
            return pcast_to_union(red, extra=("dcn", "ici"))

        return lax.fori_loop(0, inner, one, xl)

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                             in_specs=P(("dcn", "ici")),
                             out_specs=P(("dcn", "ici"))))

    def run_and_wait():
        float(jnp.sum(f(x)[..., :1].astype(jnp.float32)))

    for _ in range(warmup):
        run_and_wait()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_and_wait()
        times.append((time.perf_counter() - t0) / inner)
    return min(times)


def bench_rs_jit(mesh, nbytes: int, dtype, inner: int, iters: int,
                 warmup: int, leg: str):
    """Per-op seconds for one leg of the reduce-scatter sweep on the dp
    mesh: ``allreduce`` = the flat psum, ``rs_ag`` = the explicit
    reduce-scatter + invariant-allgather split (the HVDT_ZERO=grads
    wire), ``rs`` = the reduce-scatter hop alone (what the deeper ZeRO
    stages pay per step when the allgather is deferred into the
    parameter-delta path)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.sharding import pcast_to_union

    from horovod_tpu.ops import device as hdev

    n = mesh.devices.size
    count = max(n, nbytes // jnp.dtype(dtype).itemsize)
    count -= count % n
    x = jax.device_put(jnp.ones((n, count), dtype),
                       NamedSharding(mesh, P("dp")))

    def body(xl):
        def one(_, acc):
            flat = acc.reshape(-1)
            if leg == "allreduce":
                red = lax.psum(flat, "dp") * (1.0 / n)
            elif leg == "rs_ag":
                shard = hdev.reduce_scatter_flat(flat, "dp")
                red = hdev.allgather_flat_shards(shard, "dp") * (1.0 / n)
            else:   # rs: the wire hop alone; tile back so the carry
                    # chains (labelled approximate — the tile is local)
                shard = hdev.reduce_scatter_flat(flat, "dp")
                red = jnp.tile(shard, n) * (1.0 / n)
            red = red.reshape(acc.shape)
            return pcast_to_union(red, extra=("dp",))

        return lax.fori_loop(0, inner, one, xl)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp")))

    def run_and_wait():
        float(jnp.sum(f(x)[..., :1].astype(jnp.float32)))

    for _ in range(warmup):
        run_and_wait()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_and_wait()
        times.append((time.perf_counter() - t0) / inner)
    return min(times)


def _run_reduce_scatter(args) -> None:
    """--reduce-scatter: measure the ZeRO wire split against the flat
    allreduce per message size and emit ``rs_ag_speedup_vs_allreduce``
    rows — the measured seed ``HVDT_AUTOTUNE_ZERO_SEED`` reads (the
    autotuner's replicated-vs-sharded starting leg comes from this
    file, not a guess — mirrors HVDT_AUTOTUNE_TRANSPORT_SEED)."""
    import jax

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.devices.size
    dev0 = jax.devices()[0]
    print(f"# reduce-scatter sweep on {n}x "
          f"{dev0.platform}:{dev0.device_kind} "
          f"(rs_ag = explicit RS+AG split, the HVDT_ZERO wire)",
          file=sys.stderr)

    rows = []
    size = args.min_bytes
    while size <= args.max_bytes:
        t = {leg: bench_rs_jit(mesh, size, args.dtype, args.inner,
                               args.iters, args.warmup, leg)
             for leg in ("allreduce", "rs_ag", "rs")}
        speedup = (t["allreduce"] / t["rs_ag"]
                   if t["rs_ag"] > 0 else None)
        rows.append({
            "bytes": size, "size_bytes": size,
            "axis": "dp", "axis_size": int(n),
            "algorithm": "rs_ag", "wire": "f32",
            "seconds": t["rs_ag"],
            "allreduce_us": t["allreduce"] * 1e6,
            "rs_ag_us": t["rs_ag"] * 1e6,
            "rs_us": t["rs"] * 1e6,
            "rs_ag_algbw_gbps": size / t["rs_ag"] / 1e9,
            "rs_ag_speedup_vs_allreduce": speedup,
            "deferred_ag_fraction": (1.0 - t["rs"] / t["rs_ag"]
                                     if t["rs_ag"] > 0 else None),
        })
        print(f"{_fmt_bytes(size):>8}  allreduce "
              f"{t['allreduce']*1e6:>9.1f}us  rs+ag "
              f"{t['rs_ag']*1e6:>9.1f}us  rs {t['rs']*1e6:>9.1f}us  "
              f"speedup {speedup:>5.2f}x", file=sys.stderr)
        size *= 4

    peak = max(rows, key=lambda r: r["rs_ag_algbw_gbps"])
    summary = {
        "metric": "reduce_scatter_sweep",
        "schema_version": SCHEMA_VERSION,
        "value": round(peak["rs_ag_speedup_vs_allreduce"], 3),
        "unit": "speedup_vs_allreduce",
        "n_devices": int(n),
        "platform": dev0.platform,
        "at_bytes": peak["bytes"],
        "rs_ag_speedup_vs_allreduce_at_peak": round(
            peak["rs_ag_speedup_vs_allreduce"], 3),
        "rows": rows,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


def bench_a2a_jit(mesh, nbytes: int, dtype, inner: int, iters: int,
                  warmup: int, wire: str = "f32"):
    """Per-op seconds for a chained ``all_to_all`` of ``nbytes`` on the
    dp mesh — the MoE expert-dispatch exchange
    (``parallel/moe._a2a_transport``), measured through the production
    transport path so an int8 leg times exactly what an
    ``HVDT_TRANSPORT=ep:ring:int8:...`` policy line buys: block-scaled
    int8 payload + f32 scale alltoalls with quantize/dequantize on
    either side (the gamma term), not a bare int8 exchange."""
    import os

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.sharding import pcast_to_union

    from horovod_tpu.parallel.moe import _a2a_transport
    from horovod_tpu.transport import policy as tpolicy

    n = mesh.devices.size
    count = max(n, nbytes // jnp.dtype(dtype).itemsize)
    count -= count % n
    c = count // n
    # Global [n, n, c] sharded on dim 0: each rank holds one [n, c]
    # dispatch block whose slice i is bound for rank i — the MoE
    # dispatch layout.
    x = jax.device_put(jnp.ones((n, n, c), dtype),
                       NamedSharding(mesh, P("dp")))

    prev = os.environ.get("HVDT_TRANSPORT")
    if wire == "f32":
        os.environ.pop("HVDT_TRANSPORT", None)
    else:
        os.environ["HVDT_TRANSPORT"] = f"dp:ring:{wire}:64M"
    tpolicy.reset()
    try:
        def body(xl):
            def one(_, acc):
                # a2a permutes blocks across ranks, so chaining the
                # output back as the next input keeps values bounded
                # while forcing each iteration to wait for the last.
                out = _a2a_transport(acc[0], "dp", "bench")[None]
                return pcast_to_union(out, extra=("dp",))

            return lax.fori_loop(0, inner, one, xl)

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                                 out_specs=P("dp")))

        def run_and_wait():
            float(jnp.sum(f(x)[..., :1].astype(jnp.float32)))

        for _ in range(warmup):
            run_and_wait()
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run_and_wait()
            times.append((time.perf_counter() - t0) / inner)
        return min(times)
    finally:
        if prev is None:
            os.environ.pop("HVDT_TRANSPORT", None)
        else:
            os.environ["HVDT_TRANSPORT"] = prev
        tpolicy.reset()


def _run_a2a(args) -> None:
    """--a2a: sweep the expert-dispatch ``all_to_all`` per message size,
    f32 against the block-scaled int8 MoE wire, and emit
    ``op="all_to_all"`` rows.

    The rows feed ``analysis.costmodel.fit_from_bench`` (via
    tools/fit_costmodel.py) alongside the allreduce sweeps: (alpha,
    beta) are LINK constants with per-op geometry factored out row by
    row, so a2a rows sharpen the same fit that prices
    ``CostModel.alltoall_seconds`` — which is what the autotuner's
    MoE capacity-factor dimension's model seed
    (``predict_leg_order(...)["moe"]``) consults.  Rows deliberately
    omit ``bytes_on_wire`` so the fitter applies a2a geometry
    (``(n-1)/n``) itself."""
    import jax

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.devices.size
    dev0 = jax.devices()[0]
    print(f"# all_to_all sweep on {n}x "
          f"{dev0.platform}:{dev0.device_kind} "
          f"(the MoE expert-dispatch wire; int8 = block-scaled "
          f"payload + f32 scales)", file=sys.stderr)

    import numpy as np

    rows = []
    size = args.min_bytes
    while size <= args.max_bytes:
        t_f32 = bench_a2a_jit(mesh, size, args.dtype, args.inner,
                              args.iters, args.warmup, wire="f32")
        t_int8 = bench_a2a_jit(mesh, size, args.dtype, args.inner,
                               args.iters, args.warmup, wire="int8")
        count = max(1, size // np.dtype(args.dtype).itemsize)
        speedup = t_f32 / t_int8 if t_int8 > 0 else None
        for wire, secs in (("f32", t_f32), ("int8", t_int8)):
            rows.append({
                "bytes": size, "size_bytes": size,
                "axis": "dp", "axis_size": int(n),
                "algorithm": "ring", "wire": wire,
                "op": "all_to_all",
                "seconds": secs,
                "a2a_us": secs * 1e6,
                "a2a_algbw_gbps": size / secs / 1e9,
                "a2a_wire_bytes": wire_payload_bytes(
                    count, args.dtype, wire),
                "int8_speedup_vs_f32": speedup,
            })
        print(f"{_fmt_bytes(size):>8}  f32 {t_f32*1e6:>9.1f}us  "
              f"int8 {t_int8*1e6:>9.1f}us  "
              f"speedup {speedup:>5.2f}x", file=sys.stderr)
        size *= 4

    peak = max((r for r in rows if r["wire"] == "f32"),
               key=lambda r: r["a2a_algbw_gbps"])
    summary = {
        "metric": "a2a_sweep",
        "schema_version": SCHEMA_VERSION,
        "value": round(peak["int8_speedup_vs_f32"], 3),
        "unit": "int8_speedup_vs_f32",
        "n_devices": int(n),
        "platform": dev0.platform,
        "at_bytes": peak["bytes"],
        "int8_a2a_speedup_vs_f32_at_peak": round(
            peak["int8_speedup_vs_f32"], 3),
        "rows": rows,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


def _run_hierarchical(args) -> None:
    """--hierarchical: the per-(axis, algorithm, wire, size) sweep of
    the transport-policy data plane, with the measured
    hierarchical-vs-flat verdict the autotune transport dimension
    seeds from."""
    import os

    os.environ.setdefault("HVDT_TRANSPORT", args.transport or "auto")

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.quant import wire_bytes as q_wire_bytes
    from horovod_tpu.transport import get_policy

    hvd.init()
    mesh = _build_mesh2d(args.outer)
    n_dcn, n_ici = mesh.devices.shape
    pol = get_policy()
    res = pol.resolve(("dcn", "ici"))
    dev0 = jax.devices()[0]
    item = 4 if args.dtype == "float32" else 2
    print(f"# hierarchical allreduce sweep on {n_dcn}x{n_ici} "
          f"{dev0.platform}:{dev0.device_kind} policy={pol.describe()}",
          file=sys.stderr)

    def _wire_item(wire):
        return {"bf16": 2, "fp16": 2}.get(wire, item)

    rows = []
    size = args.min_bytes
    while size <= args.max_bytes:
        count = max(n_ici, size // item)
        count -= count % n_ici
        shard = count // n_ici
        t = {leg: bench_hier_jit(mesh, size, args.dtype, args.inner,
                                 args.iters, args.warmup, leg)
             for leg in ("flat", "hier", "ici", "dcn")}
        # Per-tier ring wire accounting: RS+AG over ici moves
        # 2(k-1)/k of the payload; the slow tier exchanges the 1/k
        # shard (int8: payload + block scales via quant.wire_bytes).
        ici_wire = 2 * count * _wire_item(res.fast.wire) \
            * (n_ici - 1) // n_ici
        if res.slow.wire == "int8":
            dcn_wire = int(q_wire_bytes(shard))
        elif res.slow.wire == "int4":
            from horovod_tpu.quant import wire_bytes_int4 as q_wire4

            dcn_wire = int(q_wire4(shard))
        else:
            dcn_wire = 2 * shard * _wire_item(res.slow.wire) \
                * (n_dcn - 1) // max(1, n_dcn)
        speedup = t["flat"] / t["hier"] if t["hier"] > 0 else None
        n = n_dcn * n_ici
        flat_wire = 2 * count * item * (n - 1) // n
        rows.extend([
            {"bytes": size, "size_bytes": size, "axis": "ici",
             "axis_size": int(n_ici),
             "algorithm": res.fast.algorithm, "wire": res.fast.wire,
             "us": t["ici"] * 1e6, "seconds": t["ici"],
             "bytes_on_wire": ici_wire,
             "wire_gbps": ici_wire / t["ici"] / 1e9},
            {"bytes": size, "size_bytes": size, "axis": "dcn",
             "axis_size": int(n_dcn),
             "algorithm": res.slow.algorithm, "wire": res.slow.wire,
             "us": t["dcn"] * 1e6, "seconds": t["dcn"],
             "bytes_on_wire": dcn_wire,
             "wire_gbps": dcn_wire / t["dcn"] / 1e9},
            {"bytes": size, "size_bytes": size, "axis": "ici+dcn",
             "axis_size": int(n), "algorithm": "flat",
             "wire": args.dtype if args.dtype != "float32" else "f32",
             "us": t["flat"] * 1e6, "seconds": t["flat"],
             "bytes_on_wire": flat_wire,
             "wire_gbps": flat_wire / t["flat"] / 1e9},
            {"bytes": size, "size_bytes": size, "axis": "ici+dcn",
             "axis_size": int(n), "algorithm": "hierarchical",
             "wire": f"{res.fast.wire}/{res.slow.wire}",
             "us": t["hier"] * 1e6, "seconds": t["hier"],
             "flat_us": t["flat"] * 1e6,
             "bytes_on_wire": ici_wire + dcn_wire,
             "jit_algbw_gbps": size / t["hier"] / 1e9,
             "hierarchical_speedup_vs_flat": speedup},
        ])
        print(f"{_fmt_bytes(size):>8}  flat {t['flat']*1e6:>9.1f}us  "
              f"hier {t['hier']*1e6:>9.1f}us  speedup {speedup:>5.2f}x  "
              f"(ici {t['ici']*1e6:.1f}us dcn {t['dcn']*1e6:.1f}us)",
              file=sys.stderr)
        size *= 4

    hier_rows = [r for r in rows if r["algorithm"] == "hierarchical"]
    peak = max(hier_rows, key=lambda r: r["jit_algbw_gbps"])
    summary = {
        "metric": "allreduce_hierarchical_sweep",
        "schema_version": SCHEMA_VERSION,
        "value": round(peak["hierarchical_speedup_vs_flat"], 3),
        "unit": "speedup_vs_flat",
        "n_devices": int(n_dcn * n_ici),
        "mesh": {"dcn": int(n_dcn), "ici": int(n_ici)},
        "platform": dev0.platform,
        "transport": os.environ.get("HVDT_TRANSPORT", ""),
        "at_bytes": peak["bytes"],
        "hierarchical_speedup_vs_flat_at_peak": round(
            peak["hierarchical_speedup_vs_flat"], 3),
        "rows": rows,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


def bench_eager(hvd, nbytes: int, dtype, iters: int, warmup: int):
    """Per-op seconds for the negotiated eager allreduce path."""
    import numpy as np

    count = max(1, nbytes // np.dtype(dtype).itemsize)
    x = np.ones((count,), dtype)
    for i in range(warmup):
        hvd.allreduce(x, name=f"bw_warm_{nbytes}_{i}")
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        np.asarray(hvd.allreduce(x, name=f"bw_{nbytes}_{i}"))
        times.append(time.perf_counter() - t0)
    return min(times)


def _eager_worker(sizes, dtype, iters):
    """Per-rank body for --np multi-process eager measurement: measures
    the full negotiate+host-collective round trip across real processes
    (the reference's per-op latency regime)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    rows = []
    for nbytes in sizes:
        t = bench_eager(hvd, nbytes, dtype, iters, 2)
        rows.append({"bytes": nbytes, "eager_us": t * 1e6,
                     "eager_algbw_gbps": nbytes / t / 1e9})
    return {"rank": hvd.rank(), "size": hvd.size(), "rows": rows}


def _run_eager_multiproc(args) -> None:
    """--np N: spawn N real worker processes via the programmatic runner
    and report the negotiated eager path's latency/bandwidth sweep."""
    import functools

    from horovod_tpu import runner

    sizes = []
    s = args.min_bytes
    while s <= args.max_bytes:
        sizes.append(s)
        s *= 4
    results = runner.run(
        functools.partial(_eager_worker, sizes, args.dtype, args.iters),
        np=args.np)
    rows = results[0]["rows"]
    for row in rows:
        print(f"{_fmt_bytes(row['bytes']):>8}  eager {row['eager_us']:>10.1f}us "
              f"algbw {row['eager_algbw_gbps']:>8.3f} GB/s", file=sys.stderr)
    print(json.dumps({
        "metric": "eager_allreduce_sweep",
        "n_processes": args.np,
        "unit": "us",
        "rows": rows,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-bytes", type=int, default=1 << 12)
    ap.add_argument("--max-bytes", type=int, default=1 << 26)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--inner", type=int, default=10,
                    help="chained allreduces per timed call (jit path)")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--eager", action="store_true",
                    help="also measure the negotiated eager path")
    ap.add_argument("--wire",
                    choices=("f32", "bf16", "fp16", "int8", "int4"),
                    default="f32",
                    help="wire format for the jit leg (int8/int4 = the "
                         "block-scaled quantized collective, "
                         "horovod_tpu/quant; non-f32 also times the "
                         "f32 leg for speedup_vs_f32)")
    ap.add_argument("--json-out", default="",
                    help="also write the sweep JSON to this file "
                         "(axis / algorithm / bytes_on_wire / GB/s / "
                         "speedup rows)")
    ap.add_argument("--reduce-scatter", action="store_true",
                    help="measure the explicit reduce-scatter + "
                         "allgather split (the HVDT_ZERO wire) against "
                         "the flat allreduce; emits "
                         "rs_ag_speedup_vs_allreduce rows (the "
                         "HVDT_AUTOTUNE_ZERO_SEED input)")
    ap.add_argument("--a2a", action="store_true",
                    help="measure the MoE expert-dispatch all_to_all "
                         "(f32 vs the block-scaled int8 transport "
                         "wire); emits op=all_to_all rows for the "
                         "cost-model fitter and "
                         "int8_a2a_speedup_vs_f32_at_peak")
    ap.add_argument("--hierarchical", action="store_true",
                    help="two-level transport-policy sweep on an "
                         "(outer x inner) mesh: per-(axis, algorithm, "
                         "wire, size) rows + measured "
                         "hierarchical_speedup_vs_flat (the "
                         "HVDT_AUTOTUNE_TRANSPORT_SEED input)")
    ap.add_argument("--transport", default="",
                    help="HVDT_TRANSPORT policy spec for the "
                         "hierarchical sweep (e.g. 'ici:ring:f32:64M,"
                         "dcn:tree:int8:8M'; default 'auto')")
    ap.add_argument("--outer", type=int, default=2,
                    help="slow-axis (dcn) size for --hierarchical; "
                         "must divide the device count")
    ap.add_argument("--np", type=int, default=0,
                    help="measure the eager path across N real worker "
                         "processes (launched via the programmatic runner)")
    args = ap.parse_args()

    # Compile cache: JAX_COMPILATION_CACHE_DIR, else the knob, else the
    # fixed <checkout>/.xla_cache (one compile per message size).
    from horovod_tpu.step_pipeline import enable_compilation_cache

    enable_compilation_cache(default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".xla_cache"))

    if args.np > 1:
        _run_eager_multiproc(args)
        return
    if args.reduce_scatter:
        _run_reduce_scatter(args)
        return
    if args.a2a:
        _run_a2a(args)
        return
    if args.hierarchical or args.transport:
        _run_hierarchical(args)
        return

    import jax
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.devices.size
    dev = jax.devices()[0]
    print(f"# allreduce sweep on {n}x {dev.platform}:{dev.device_kind} "
          f"(busbw = algbw * 2(n-1)/n)", file=sys.stderr)

    rows = []
    size = args.min_bytes
    factor = 2.0 * (n - 1) / n if n > 1 else 1.0
    while size <= args.max_bytes:
        t_jit = bench_jit(mesh, size, args.dtype, args.inner, args.iters,
                          args.warmup, wire=args.wire)
        count = max(1, size // np.dtype(args.dtype).itemsize)
        on_wire = wire_payload_bytes(count, args.dtype, args.wire)
        row = {"bytes": size, "size_bytes": size,
               "jit_algbw_gbps": size / t_jit / 1e9,
               "jit_busbw_gbps": size / t_jit * factor / 1e9,
               "jit_us": t_jit * 1e6, "seconds": t_jit,
               "axis": "dp", "axis_size": int(n), "algorithm": "flat",
               "wire": args.wire, "bytes_on_wire": on_wire,
               "wire_gbps": on_wire / t_jit / 1e9}
        if args.wire != "f32":
            t_f32 = bench_jit(mesh, size, args.dtype, args.inner,
                              args.iters, args.warmup, wire="f32")
            row["f32_us"] = t_f32 * 1e6
            row["speedup_vs_f32"] = t_f32 / t_jit
        if args.eager:
            t_e = bench_eager(hvd, size, args.dtype,
                              max(3, args.iters // 2), 1)
            row["eager_algbw_gbps"] = size / t_e / 1e9
            row["eager_us"] = t_e * 1e6
        rows.append(row)
        msg = (f"{_fmt_bytes(size):>8}  jit {row['jit_us']:>10.1f}us "
               f"algbw {row['jit_algbw_gbps']:>8.2f} GB/s "
               f"busbw {row['jit_busbw_gbps']:>8.2f} GB/s")
        if args.wire != "f32":
            msg += (f"   wire={args.wire} {_fmt_bytes(on_wire):>8} "
                    f"speedup {row['speedup_vs_f32']:>5.2f}x")
        if args.eager:
            msg += (f"   eager {row['eager_us']:>10.1f}us "
                    f"algbw {row['eager_algbw_gbps']:>8.2f} GB/s")
        print(msg, file=sys.stderr)
        size *= 4

    peak = max(rows, key=lambda r: r["jit_busbw_gbps"])
    summary = {
        "metric": "allreduce_peak_busbw_gbps",
        "schema_version": SCHEMA_VERSION,
        "value": round(peak["jit_busbw_gbps"], 3),
        "unit": "GB/s",
        "n_devices": n,
        "platform": dev.platform,
        "at_bytes": peak["bytes"],
        "wire": args.wire,
        "rows": rows,
    }
    if args.wire != "f32":
        summary["speedup_vs_f32_at_peak"] = round(
            peak["speedup_vs_f32"], 3)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
