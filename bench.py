"""Synthetic ResNet-50 training benchmark — the reference's headline harness.

Equivalent of ref: examples/pytorch/pytorch_synthetic_benchmark.py (ResNet-50,
images/sec; SURVEY.md §6) re-built TPU-native: bf16 compute, NHWC, jitted
train step with donated params, synthetic ImageNet-shaped data, MFU from the
compiled step's XLA cost analysis.

Process contract: the parent process NEVER imports JAX (a parent that has
touched JAX holds the chip, and the child that needs it then fails or
hangs).  It runs the measurement once in a child process with a hard
timeout and prints the child's one JSON line:

  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N, "platform": "tpu",
   "device_kind": ..., "mfu": ..., ...}

A device metric comes from the device or not at all: when the child
fails, times out, or reports any platform but ``tpu``, the parent prints
no metric line and exits non-zero.  (``python bench.py --_child ...``
under ``JAX_PLATFORMS=cpu`` at small sizes drives a code path on the CPU
and says ``"platform": "cpu"``; it is not a measurement.)

Baseline: the reference's only published per-device synthetic number —
1656.82 images/sec over 16 P100s (ResNet-101, docs/benchmarks.rst:27-43) =
103.55 images/sec/device.  vs_baseline = value / 103.55.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S_PER_DEVICE = 1656.82 / 16.0
METRIC = "resnet50_images_per_sec_per_chip"
UNIT = "images/sec/chip"
ROOT = os.path.dirname(os.path.abspath(__file__))
# One attempt, bounded: a healthy run is ~100 s (compile + warm-up + 5
# timed iters); the serving modes are shorter.
TRAIN_TIMEOUT_S = 900
SERVE_TIMEOUT_S = 300


def _peak_for(device_kind: str):
    """bf16 peak FLOP/s and HBM B/s by TPU generation.  The table lives
    in telemetry/step_stats.py (one home for the MFU math); imported
    lazily because only the CHILD may import horovod_tpu (the parent
    never imports JAX)."""
    from horovod_tpu.telemetry.step_stats import peak_flops_for

    return peak_flops_for(device_kind)


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-iters", type=int, default=5)
    ap.add_argument("--num-batches-per-iter", type=int, default=50)
    ap.add_argument("--num-warmup", type=int, default=2)
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help=">1: run N steps inside one jit via lax.fori_loop "
                         "(removes per-call dispatch gaps; A/B probe for "
                         "the non-conv overlap question, VERDICT r3 #4)")
    ap.add_argument("--fused-optimizer", action="store_true",
                    help="A/B leg: run the SGD-momentum update through "
                         "the fused Pallas optimizer kernels "
                         "(ops/optim_kernels.fused_sgd) instead of stock "
                         "optax — one HBM pass per eligible parameter. "
                         "Default off pending the TPU A/B.")
    ap.add_argument("--overlap", action="store_true",
                    help="A/B leg: route the train step through the "
                         "overlap scheduling layer (HVDT_OVERLAP=on, "
                         "ops/overlap.py) — grads exchanged over a "
                         "mesh-bound dp axis with the reverse-"
                         "topological bucket schedule, XLA latency-"
                         "hiding flags engaged, telemetry on so the "
                         "hvdt_overlap_fraction gauge feeds the JSON "
                         "(overlap_fraction / overlap_schedule).")
    ap.add_argument("--fp8", action="store_true",
                    help="benchmark with the fp8 (e4m3) matmul gate on "
                         "(HVDT_FP8=matmul, quant/fp8.py) and emit the "
                         "probe/microbench evidence in the JSON")
    ap.add_argument("--transport", default="",
                    help="A/B leg: run the train step under an "
                         "HVDT_TRANSPORT policy (horovod_tpu/transport) "
                         "on a two-level ('dcn','ici') mesh so gradient "
                         "exchange goes hierarchical (fast-axis "
                         "reduce-scatter -> slow-axis shard exchange -> "
                         "allgather).  Pass a policy spec like "
                         "'ici:ring:f32:8M,dcn:tree:int8:8M' or 'auto'.")
    ap.add_argument("--zero", default="",
                    choices=("", "grads", "states", "params"),
                    help="A/B leg: ZeRO-sharded gradient exchange "
                         "(HVDT_ZERO, ops/zero.py) on a mesh-bound dp "
                         "axis — 'grads' swaps the fused allreduce for "
                         "the reduce-scatter + allgather split, "
                         "'states' shards the optimizer moments 1/n "
                         "with shard-local fused updates + delta "
                         "allgather, 'params' keeps parameters sharded "
                         "between steps (gathered on demand per step). "
                         "JSON gains zero_stage / "
                         "optimizer_state_bytes.")
    ap.add_argument("--remat", default="",
                    choices=("", "none", "full", "dots"),
                    help="A/B leg: activation rematerialization "
                         "(HVDT_REMAT) — wraps the loss in "
                         "jax.checkpoint ('full': save only inputs; "
                         "'dots': dots_with_no_batch_dims_saveable "
                         "policy).  The second half of the "
                         "memory-for-MFU trade next to --zero; JSON "
                         "gains remat.")
    ap.add_argument("--ckpt-stall", action="store_true",
                    help="measure the commit-point checkpoint stall of "
                    "the trained state, sync vs async "
                    "(HVDT_ASYNC_CKPT), and emit checkpoint_stall_ms "
                    "in the JSON")
    ap.add_argument("--serve", action="store_true",
                    help="Serving micro-benchmark instead of training: "
                         "an in-process ModelServer (MLP, shape-bucketed "
                         "engine + dynamic batcher) hammered over HTTP by "
                         "--serve-threads clients; emits latency_p50_ms / "
                         "latency_p99_ms / throughput_rps JSON alongside "
                         "the training numbers.")
    ap.add_argument("--serve-duration", type=float, default=5.0,
                    help="Seconds of sustained client fire for --serve.")
    ap.add_argument("--serve-threads", type=int, default=8,
                    help="Concurrent HTTP client threads for --serve.")
    ap.add_argument("--serve-llm", action="store_true",
                    help="LLM decode engine comparison on the CPU sim: "
                         "the same mixed-prefill-length greedy-decode "
                         "workload through the static shape-bucket "
                         "engine (full re-forward per token) and the "
                         "continuous paged-KV engine; emits tokens/s "
                         "for both and the speedup multiple.")
    ap.add_argument("--serve-llm-requests", type=int, default=12,
                    help="Concurrent sequences for --serve-llm.")
    ap.add_argument("--serve-llm-new-tokens", type=int, default=16,
                    help="Tokens generated per sequence for --serve-llm.")
    ap.add_argument("--report", action="store_true",
                    help="After the run, render the post-mortem "
                         "markdown report (analysis --report) from the "
                         "HVDT_EVENT_LOG anomaly event log to stderr — "
                         "the bench-side smoke of the attribution "
                         "plane.")
    ap.add_argument("--controller", action="store_true",
                    help="Policy-controller micro-benchmark: drive a "
                         "synthetic anomaly-event storm through "
                         "control.PolicyController (offline cost-model "
                         "pricing, guardrails, stub appliers) and emit "
                         "decisions/s plus the decision mix and mean "
                         "predicted delta as one JSON line.  Pure CPU, "
                         "in-process.")
    ap.add_argument("--controller-events", type=int, default=2000,
                    help="Synthetic events to push for --controller.")
    ap.add_argument("--moe", action="store_true",
                    help="MoE expert-axis capacity-factor sweep on the "
                         "8-device CPU sim (in-process): one row per "
                         "candidate capacity factor with tokens/s, the "
                         "measured dropped_fraction, a2a_wire_bytes, "
                         "and goodput; the summary's "
                         "capacity_factor_at_peak is the "
                         "HVDT_AUTOTUNE_MOE_SEED input.")
    ap.add_argument("--pipeline", action="store_true",
                    help="1F1B microbatch-count sweep on the CPU sim "
                         "(in-process): fixed total batch per row with "
                         "tokens/s, bubble_fraction_priced (cost "
                         "model) and bubble_fraction_observed (wall "
                         "clock); the summary's microbatches_at_peak "
                         "is the HVDT_AUTOTUNE_PIPELINE_SEED input.")
    ap.add_argument("--json-out", default="",
                    help="also write the --moe/--pipeline sweep JSON "
                         "to this file (the HVDT_AUTOTUNE_*_SEED "
                         "format)")
    ap.add_argument("--fleet", metavar="TRACE", default=None,
                    help="Fleet-scheduler trace replay: run the "
                         "trace-driven CPU chaos simulation "
                         "(horovod_tpu.fleet.simulate) for a builtin "
                         "trace name (diurnal, flash_crowd, "
                         "step_function) or a trace JSON path "
                         "(tools/traces/*.json) and emit the "
                         "goodput-vs-SLO report — goodput_fraction, "
                         "slo_compliance, reclaims, drains, "
                         "dropped_requests — as one JSON line.  Pure "
                         "CPU, in-process.")
    ap.add_argument("--fleet-pods", type=int, default=5,
                    help="Fleet size (pods) for --fleet.")
    ap.add_argument("--fleet-fault-plan", default=None,
                    help="resilience.faults plan injected into the "
                         "--fleet replay (e.g. "
                         "'pod_crash@step=12:pod=pod3').")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--_measure", action="store_true",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def _device(args):
    """``jax.devices()[0]``.  A child the parent started to take a
    measurement (``--_measure``) refuses anything but a TPU here, before
    it spends minutes producing a number nobody may print."""
    import jax

    dev = jax.devices()[0]
    if args._measure and dev.platform != "tpu":
        sys.exit(f"bench: platform {dev.platform!r} is not a TPU — "
                 "nothing measured")
    return dev


def _run_controller_bench(args) -> None:
    """Policy-controller event-storm micro-bench (in-process): N
    synthetic anomaly events of rotating classes through a full
    PolicyController — real cost-model pricing on every candidate, real
    guardrails, stub appliers — and one JSON line with decisions/s, the
    applied/suppressed mix, and the mean predicted delta of applied
    actions.  The number to watch: the control loop must price and
    decide orders of magnitude faster than the discovery tick it rides
    (one decision per tick in production)."""
    from horovod_tpu.analysis import costmodel as _cm
    from horovod_tpu.control import (ActionPricer, ControllerConfig,
                                     ControllerState, PolicyController)
    from horovod_tpu.telemetry.metrics import MetricsRegistry

    MiB = 2 ** 20
    applied = []
    ctl = PolicyController(
        cfg=ControllerConfig(cooldown_s=0.0, enter_ratio=1.2,
                             exit_ratio=1.05, recovery_window=1),
        pricer=ActionPricer(_cm.CostModel(_cm.Calibration())),
        state=ControllerState(pods=4, grad_bytes=64 * MiB,
                              bucket_bytes=32 * MiB, overlap=True,
                              step_time_s=1.0),
        registry=MetricsRegistry())
    ctl.bind_appliers(
        {k: (lambda a, _applied=applied: _applied.append(a) or True)
         for k in ("flip_transport", "retune_bucket", "toggle_overlap",
                   "toggle_zero", "evict_pod", "resize",
                   "scale_replicas")})
    kinds = ("step_time_shift", "wire_drift", "mfu_regression",
             "perf_deviation", "straggler_onset", "goodput_drop")
    n = max(1, args.controller_events)
    deltas = []
    t0 = time.perf_counter()
    for i in range(n):
        ev = {"kind": kinds[i % len(kinds)], "scope": "cluster",
              "ratio": 1.5, "step": i, "pod": "podB"}
        decisions = ctl.tick([ev], deviation_ratio=1.5,
                             observed_step_s=1.0, step=i)
        for d in decisions:
            if d.outcome == "applied" and d.chosen is not None:
                deltas.append(d.chosen.predicted_delta_s)
        # recover immediately so guardrails re-arm and every event is a
        # fresh decision, not a pile-up of pending verifications
        ctl.tick([], deviation_ratio=1.0, observed_step_s=1.0, step=i)
    elapsed = time.perf_counter() - t0
    print(json.dumps({
        "metric": "controller_decisions_per_s",
        "value": round(n / elapsed, 1),
        "unit": "decisions/s",
        "events": n,
        "applied": len(applied),
        "suppressed": int(ctl._m_suppressed.total()),
        "mean_predicted_delta_ms": round(
            1e3 * sum(deltas) / len(deltas), 3) if deltas else 0.0,
    }))


def _run_fleet_bench(args) -> None:
    """Fleet-scheduler trace replay (in-process): the REAL scheduler —
    same pricing, guardrails, and event records as the live launcher —
    against a fluid-queue serving model and a TopologySpec-priced pod
    fleet.  One JSON line: goodput_fraction, slo_compliance, reclaims,
    drains, dropped_requests (the acceptance numbers of the
    fleet-scheduler PR)."""
    from horovod_tpu.fleet.simulate import simulate_trace
    from horovod_tpu.fleet.traces import load_trace

    report = simulate_trace(
        load_trace(args.fleet), pods=max(2, args.fleet_pods),
        fault_plan=args.fleet_fault_plan)
    print(json.dumps({
        "metric": "fleet_trace_replay",
        "trace": report["trace"],
        "pods": report["pods"],
        "goodput_fraction": report["goodput_fraction"],
        "slo_compliance": report["slo_compliance"],
        "reclaims": report["reclaims"],
        "backfills": report["backfills"],
        "drains": report["drains"],
        "rollbacks": report["rollbacks"],
        "dropped_requests": report["dropped_requests"],
    }))


def _force_cpu_sim(n: int = 8) -> None:
    """Pin the 8-device CPU sim BEFORE the first jax backend init (the
    conftest / analysis-gate idiom) — the --moe/--pipeline legs are
    CPU-sim sweeps by contract, comparable across hosts."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _run_moe_bench(args) -> None:
    """--moe: expert-axis capacity-factor sweep on the CPU sim
    (in-process).

    One row per ``ParameterManager.MOE_CAPACITY_CANDIDATES`` entry:
    time ``moe_dispatch_combine`` (the production dispatch -> expert ->
    combine path, both alltoalls included) over the ep mesh with a
    skewed router, and report ``tokens_per_s``, the measured
    ``dropped_fraction``, the per-rank ``a2a_wire_bytes``, and
    ``goodput_tokens_per_s = tokens_per_s * (1 - dropped_fraction)`` —
    the objective that prices the capacity trade (bigger capacity moves
    more wire bytes but drops fewer tokens).  The summary's
    ``capacity_factor_at_peak`` is what ``HVDT_AUTOTUNE_MOE_SEED``
    reads to seed the autotuner's MoE dimension — measured, not
    guessed."""
    _force_cpu_sim(8)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import numpy as np

    from horovod_tpu.autotune import ParameterManager
    from horovod_tpu.parallel.moe import moe_capacity, moe_dispatch_combine

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs, dtype=object), ("ep",))
    shard_map = jax.shard_map
    tok, dim = 256, 64
    n_experts = n      # one expert per rank
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n * tok, dim), jnp.float32)
    # Skewed router weights: realistic imbalance so low capacity
    # factors actually drop tokens and the sweep prices the trade.
    rw = jax.random.normal(kw, (dim, n_experts), jnp.float32) * 2.0

    def make_step(cf):
        def local(xl, rwl):
            y, aux = moe_dispatch_combine(
                xl, xl @ rwl, lambda blk: blk * 2.0, axis="ep",
                experts_per_rank=1, capacity_factor=cf, top_k=1)
            return y, aux.dropped_fraction

        return jax.jit(shard_map(local, mesh=mesh,
                                 in_specs=(P("ep"), P()),
                                 out_specs=(P("ep"), P())))

    iters, warmup = max(3, args.num_iters), max(1, args.num_warmup)
    rows = []
    for cf in ParameterManager.MOE_CAPACITY_CANDIDATES:
        step = make_step(cf)

        def run_and_wait():
            y, d = step(x, rw)
            return float(jnp.sum(y[..., :1])), float(d)

        for _ in range(warmup):
            run_and_wait()
        times = []
        dropped = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            _, dropped = run_and_wait()
            times.append(time.perf_counter() - t0)
        secs = min(times)
        cap = moe_capacity(tok, n_experts, top_k=1, capacity_factor=cf)
        tps = (n * tok) / secs
        rows.append({
            "capacity_factor": cf,
            "capacity": cap,
            "seconds": secs,
            "tokens_per_s": round(tps, 1),
            "dropped_fraction": round(dropped, 6),
            "goodput_tokens_per_s": round(tps * (1.0 - dropped), 1),
            # bytes one rank puts on the a2a wire per step: the [ep,
            # cap, dim] f32 dispatch block out and the combine back
            "a2a_wire_bytes": 2 * n * cap * dim * 4,
        })
        print(f"capacity_factor {cf:>4}  cap {cap:>4}  "
              f"{secs*1e3:>8.2f}ms  dropped {dropped:>7.4f}  "
              f"goodput {rows[-1]['goodput_tokens_per_s']:>10.1f} tok/s",
              file=sys.stderr)

    peak = max(rows, key=lambda r: r["goodput_tokens_per_s"])
    summary = {
        "metric": "moe_capacity_sweep",
        "value": peak["goodput_tokens_per_s"],
        "unit": "goodput_tokens_per_s",
        "n_devices": n,
        "experts": n_experts,
        "tokens_per_rank": tok,
        "capacity_factor_at_peak": peak["capacity_factor"],
        "dropped_fraction": peak["dropped_fraction"],
        "a2a_wire_bytes": peak["a2a_wire_bytes"],
        "rows": rows,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


def _run_pipeline_bench(args) -> None:
    """--pipeline: 1F1B microbatch-count sweep on the CPU sim
    (in-process).

    Fixed total batch, one row per
    ``ParameterManager.PIPELINE_LOG2_MICROBATCH_CANDIDATES`` count m:
    time ``pipeline_1f1b`` over the pp mesh and report ``tokens_per_s``
    plus both bubble accountings — ``bubble_fraction_priced`` is the
    cost model's analytic ``(p-1)/(m+p-1)``, ``bubble_fraction_observed``
    is measured from wall clock: the per-tick time comes from the
    t(2m)-t(m) slope (same microbatch size, m more steady ticks), so
    ``(t(m) - m*tick)/t(m)`` is the fraction of the step not spent on
    useful ticks.  More microbatches shrink the bubble but each tick
    moves less, so the sweep has a real peak; the summary's
    ``microbatches_at_peak`` is what ``HVDT_AUTOTUNE_PIPELINE_SEED``
    reads to seed the autotuner's pipeline dimension."""
    _force_cpu_sim(8)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import numpy as np

    from horovod_tpu.analysis import costmodel as _cm
    from horovod_tpu.autotune import ParameterManager
    from horovod_tpu.parallel.pipeline import pipeline_1f1b

    devs = jax.devices()
    p = 4 if len(devs) >= 4 else len(devs)
    mesh = Mesh(np.asarray(devs[:p], dtype=object), ("pp",))
    shard_map = jax.shard_map
    dim = 64
    total = 128     # total rows per step, split into m microbatches
    w = jax.random.normal(jax.random.PRNGKey(1), (p, dim, dim),
                          jnp.float32) * 0.1

    def stage_fn(params, xb):
        return jnp.tanh(xb @ params)

    def make_step(m):
        def local(wl, mbs):
            return pipeline_1f1b(stage_fn, wl[0], mbs, axis="pp")

        return jax.jit(shard_map(local, mesh=mesh,
                                 in_specs=(P("pp"), P()),
                                 out_specs=P()))

    iters, warmup = max(3, args.num_iters), max(1, args.num_warmup)

    def time_step(m, mb):
        step = make_step(m)
        mbs = jax.random.normal(jax.random.PRNGKey(2), (m, mb, dim),
                                jnp.float32)

        def run_and_wait():
            float(jnp.sum(step(w, mbs)[..., :1]))

        for _ in range(warmup):
            run_and_wait()
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run_and_wait()
            times.append(time.perf_counter() - t0)
        return min(times)

    model = _cm.CostModel(_cm.Calibration())
    rows = []
    for lg in ParameterManager.PIPELINE_LOG2_MICROBATCH_CANDIDATES:
        m = int(round(2 ** lg))
        mb = max(1, total // m)
        t_m = time_step(m, mb)
        t_2m = time_step(2 * m, mb)
        tick = max(0.0, (t_2m - t_m) / m)
        observed = (t_m - m * tick) / t_m if t_m > 0 else 0.0
        observed = min(1.0, max(0.0, observed))
        priced = model.pipeline_bubble_fraction(p, m)
        rows.append({
            "microbatches": m,
            "microbatch_rows": mb,
            "seconds": t_m,
            "tokens_per_s": round(m * mb / t_m, 1),
            "tick_seconds": tick,
            "bubble_fraction_priced": round(priced, 4),
            "bubble_fraction_observed": round(observed, 4),
        })
        print(f"microbatches {m:>3}  {t_m*1e3:>8.2f}ms  "
              f"{rows[-1]['tokens_per_s']:>10.1f} rows/s  "
              f"bubble priced {priced:.3f} observed {observed:.3f}",
              file=sys.stderr)

    peak = max(rows, key=lambda r: r["tokens_per_s"])
    summary = {
        "metric": "pipeline_microbatch_sweep",
        "value": peak["tokens_per_s"],
        "unit": "tokens_per_s",
        "n_devices": len(devs),
        "stages": p,
        "microbatches_at_peak": peak["microbatches"],
        "bubble_fraction_priced": peak["bubble_fraction_priced"],
        "bubble_fraction_observed": peak["bubble_fraction_observed"],
        "rows": rows,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))


def _run_serve_child(args) -> None:
    """Serving micro-bench (child process): in-process ModelServer over
    the example MLP, N concurrent HTTP clients firing mixed-size batches
    for --serve-duration seconds.  Prints one JSON line with the serving
    SLO metrics (p50/p99 latency, throughput, steady-state compiles)."""
    import http.client
    import threading

    import jax
    import numpy as np

    from horovod_tpu.models.mlp import mlp_apply, mlp_init
    from horovod_tpu.serve import InferenceEngine, ModelServer

    dev = _device(args)
    print(f"serve bench on {dev.platform}:{dev.device_kind}",
          file=sys.stderr)
    sizes = (784, 256, 128, 10)
    buckets = (1, 8, 32)
    params = mlp_init(jax.random.PRNGKey(0), sizes)
    engine = InferenceEngine(mlp_apply, params, buckets=buckets)
    server = ModelServer(engine, host="127.0.0.1", port=0,
                         max_delay_ms=2.0, max_queue_depth=4096)
    port = server.start()
    engine.warmup((sizes[0],))
    warm_compiles = engine.compile_count()

    stop = threading.Event()
    counts = [0] * args.serve_threads
    errors = [0] * args.serve_threads

    def client(i):
        rng = np.random.default_rng(i)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        while not stop.is_set():
            rows = 1 + (i + counts[i]) % 4
            x = rng.normal(size=(rows, sizes[0])).astype(np.float32)
            try:
                conn.request("POST", "/predict",
                             json.dumps({"inputs": x.tolist()}),
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                r.read()
                if r.status == 200:
                    counts[i] += 1
                else:
                    errors[i] += 1
            except Exception:
                errors[i] += 1
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=30)
        conn.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.serve_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(args.serve_duration)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    dt = time.perf_counter() - t0
    lat = server.metrics.summary("serve_request_latency_ms_predict")
    pct = lat.percentiles()
    ok = sum(counts)
    server.stop()
    print(json.dumps({
        "metric": "serve_throughput_rps",
        "value": round(ok / dt, 2),
        "unit": "req/s",
        "throughput_rps": round(ok / dt, 2),
        "latency_p50_ms": (round(pct[0.5], 3)
                           if pct[0.5] is not None else None),
        "latency_p99_ms": (round(pct[0.99], 3)
                           if pct[0.99] is not None else None),
        "requests_ok": ok,
        "requests_failed": sum(errors),
        "clients": args.serve_threads,
        "duration_s": round(dt, 2),
        "buckets": list(buckets),
        "steady_state_compiles": engine.compile_count() - warm_compiles,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


def _run_serve_llm_child(args) -> None:
    """LLM engine comparison (child process): static bucket engine vs
    continuous paged-KV engine on the SAME greedy-decode workload —
    mixed prompt lengths, one token per step.  The static path pays what
    it actually pays in production (a full padded forward per emitted
    token); the continuous path runs the paged decode step.  Prints one
    JSON line with tokens/s for both and the multiple."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                transformer_apply,
                                                transformer_init)
    from horovod_tpu.serve import InferenceEngine
    from horovod_tpu.serve.llm import ContinuousLLMEngine

    dev = _device(args)
    print(f"serve-llm bench on {dev.platform}:{dev.device_kind}",
          file=sys.stderr)
    seq_len = 128
    cfg = TransformerConfig(vocab=512, layers=2, d_model=128, heads=4,
                            kv_heads=4, d_ff=256, max_seq=seq_len,
                            dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    n_req = int(args.serve_llm_requests)
    max_new = int(args.serve_llm_new_tokens)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in
                rng.integers(1, cfg.vocab, size=int(rng.integers(4, 48)))]
               for _ in range(n_req)]
    total_tokens = n_req * max_new

    # -- static baseline: greedy decode through the bucket engine -------
    apply_fn = lambda p, x: transformer_apply(p, x, cfg)   # noqa: E731
    static = InferenceEngine(apply_fn, params, buckets=(n_req,))
    static.warmup((seq_len,), dtype=np.int32)
    seqs = [list(p) for p in prompts]
    t0 = time.perf_counter()
    for _ in range(max_new):
        x = np.zeros((n_req, seq_len), np.int32)
        for i, s in enumerate(seqs):
            x[i, :len(s)] = s[-seq_len:]
        y = static.infer(x)
        for i, s in enumerate(seqs):
            s.append(int(np.argmax(y[i, len(s) - 1])))
    static_dt = time.perf_counter() - t0
    static_tps = total_tokens / static_dt

    # -- continuous engine ----------------------------------------------
    eng = ContinuousLLMEngine(params, cfg, auto_start=False)
    eng.warmup()
    warm_compiles = eng.compile_count()
    futs = [eng.submit(p, max_new_tokens=max_new,
                       tenant=("batch" if i % 3 == 0 else "interactive"))
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    while not all(f.done() for f in futs):
        eng.step()
    cont_dt = time.perf_counter() - t0
    for f in futs:
        f.result(timeout=1)
    cont_tps = total_tokens / cont_dt
    eng.alloc.check()
    print(json.dumps({
        "metric": "serve_llm_speedup",
        "value": round(cont_tps / static_tps, 3),
        "unit": "x",
        "static_tokens_per_sec": round(static_tps, 2),
        "continuous_tokens_per_sec": round(cont_tps, 2),
        "requests": n_req,
        "new_tokens_per_request": max_new,
        "steady_state_compiles": eng.compile_count() - warm_compiles,
        "preemptions": eng.sched.preemptions,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


def _run_child(args) -> None:
    """Measurement process: import JAX, run the benchmark, print JSON."""
    import jax
    import jax.numpy as jnp
    import optax
    import functools
    import numpy as np

    from horovod_tpu.models import ResNetConfig, resnet50_init, resnet_loss
    from horovod_tpu.step_pipeline import (donated_step,
                                           enable_compilation_cache)

    # Persistent XLA compilation cache: JAX_COMPILATION_CACHE_DIR where
    # it is set, else the HVDT_COMPILATION_CACHE knob (=off opts out),
    # else the fixed <checkout>/.xla_cache, so the second invocation of
    # the same program skips the compile.
    cache_dir = enable_compilation_cache(
        default=os.path.join(ROOT, ".xla_cache"))

    if args.overlap:
        # Overlap leg env contract (read lazily by the subsystems):
        # route the exchange through the scheduler, turn telemetry on so
        # the hvdt_overlap_fraction gauge is live, and default the
        # fusion threshold down so the ResNet-50 gradient pytree plans a
        # multi-bucket schedule (bf16 grads ~51 MB would fit one 64 MiB
        # bucket — nothing to overlap).  All setdefault: explicit env
        # wins.
        os.environ.setdefault("HVDT_OVERLAP", "on")
        os.environ.setdefault("HVDT_TELEMETRY", "1")
        os.environ.setdefault("HVDT_FUSION_THRESHOLD",
                              str(8 * 1024 * 1024))
    if args.transport:
        # Transport leg: the policy routes the gradient exchange
        # through the hierarchical allreduce on the two-level mesh
        # below; telemetry on so the per-axis hvdt_wire_bytes_total
        # counters land in the JSON.
        os.environ["HVDT_TRANSPORT"] = args.transport
        os.environ.setdefault("HVDT_TELEMETRY", "1")
        os.environ.setdefault("HVDT_FUSION_THRESHOLD",
                              str(8 * 1024 * 1024))
    if args.zero:
        # ZeRO leg: route the gradient exchange + optimizer update
        # through the reduce-scatter wire / sharded state (ops/zero.py)
        # on the mesh-bound dp axis below; telemetry on so the memory
        # gauges (hvdt_optimizer_state_bytes) feed the JSON.
        os.environ["HVDT_ZERO"] = args.zero
        os.environ.setdefault("HVDT_TELEMETRY", "1")
        os.environ.setdefault("HVDT_FUSION_THRESHOLD",
                              str(8 * 1024 * 1024))
    if args.remat:
        os.environ.setdefault("HVDT_REMAT", args.remat)
    if args.fp8:
        # fp8 leg: flip the compute gate for anything matmul-shaped in
        # the step (quant/fp8.py; the ResNet conv stack itself is
        # unaffected — the leg's JSON carries the gate/probe state and
        # a standalone convert-dot microbench as the evidence).
        os.environ["HVDT_FP8"] = "matmul"
        os.environ.setdefault("HVDT_TELEMETRY", "1")

    dev = _device(args)
    print(f"benchmarking on {dev.platform}:{dev.device_kind}"
          + (f" (compile cache: {cache_dir})" if cache_dir else ""),
          file=sys.stderr)

    cfg = ResNetConfig(num_classes=1000, dtype=jnp.bfloat16)
    params, stats = resnet50_init(jax.random.PRNGKey(0), cfg)
    loss_fn = resnet_loss
    if args.remat and args.remat != "none":
        # Activation rematerialization leg: trade recompute FLOPs for
        # activation HBM (the complement of --zero's state sharding).
        from horovod_tpu.models import checkpoint_policy

        _pol = checkpoint_policy(args.remat)
        if _pol == "full":
            loss_fn = jax.checkpoint(resnet_loss, static_argnums=(4,))
        elif _pol is not None:
            loss_fn = jax.checkpoint(resnet_loss, policy=_pol,
                                     static_argnums=(4,))
    if args.fused_optimizer or args.zero in ("states", "params"):
        # ZeRO states/params shard the update itself, so the optimizer
        # family must be known (the fused_sgd hyperparameter tag).
        from horovod_tpu.ops.optim_kernels import fused_sgd

        opt = fused_sgd(0.01, momentum=0.9)
    else:
        opt = optax.sgd(0.01, momentum=0.9)
    opt_state = opt.init(params)

    images = jax.random.normal(
        jax.random.PRNGKey(1),
        (args.batch_size, args.image_size, args.image_size, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (args.batch_size,),
                                0, 1000)

    def one_step(params, stats, opt_state, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, stats, images, labels, cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, loss

    zero_tx = None
    if args.overlap or args.transport or args.zero:
        # Overlap / transport A/B legs: run the step inside a mesh-bound
        # shard_map so the gradient exchange actually exists (single-chip
        # runs bind a 1-device axis; the schedule, barriers and
        # accounting are the same program that runs multi-chip).  The
        # transport leg splits the devices into a two-level
        # ('dcn', 'ici') mesh so the policy resolves hierarchically; a
        # smaller default fusion threshold guarantees a multi-bucket
        # schedule on the ~100 MB ResNet-50 gradient pytree.
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from horovod_tpu import optimizer as hvd_opt
        from horovod_tpu.common.types import ReduceOp
        from horovod_tpu.ops import device as hvd_dev
        from horovod_tpu.ops import overlap as hvd_ovl

        hvd_ovl.enable_latency_hiding()
        ndev = len(jax.devices())
        if ndev < 1 or args.batch_size % ndev:
            ndev = 1    # batch must split evenly over the dp axis
        if args.transport and ndev >= 4 and ndev % 2 == 0:
            mesh = Mesh(np.asarray(jax.devices()[:ndev],
                                   dtype=object).reshape(2, ndev // 2),
                        ("dcn", "ici"))
            grad_axis = ("dcn", "ici")
            print(f"transport leg: 2x{ndev // 2} ('dcn','ici') mesh, "
                  f"HVDT_TRANSPORT={os.environ.get('HVDT_TRANSPORT')!r}",
                  file=sys.stderr)
        else:
            mesh = Mesh(np.asarray(jax.devices()[:ndev], dtype=object),
                        ("dp",))
            grad_axis = "dp"
            print(f"overlap leg: dp mesh over {ndev} device(s), "
                  f"HVDT_OVERLAP={os.environ.get('HVDT_OVERLAP')!r} "
                  f"HVDT_TRANSPORT="
                  f"{os.environ.get('HVDT_TRANSPORT')!r}",
                  file=sys.stderr)
        batch_spec = P(grad_axis)

        param_template = params
        if args.zero:
            from horovod_tpu.ops import zero as hvd_zero

            zero_tx = hvd_opt.DistributedOptimizer(
                opt, axis=grad_axis,
                zero=hvd_zero.ZeroSpec(
                    args.zero, axis=grad_axis, num_shards=ndev)
                if args.zero in ("states", "params") else "grads")
            opt_state = zero_tx.init(params)
            if args.zero == "params":
                # Params live sharded between steps; the step gathers
                # them on demand (here: once per step — per-layer
                # on-demand gathering is the GSPMD/fsdp path,
                # parallel/sharding.fsdp_shardings).
                params = zero_tx.shard_params(param_template)

        def _sharded_step(params, stats, opt_state, images, labels):
            def body(params, stats, opt_state, images, labels):
                if args.zero == "params":
                    full = zero_tx.gather_params(params, param_template)
                else:
                    full = params
                (loss, new_stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(full, stats, images,
                                           labels, cfg)
                new_stats = hvd_dev.allreduce(new_stats, grad_axis,
                                              ReduceOp.AVERAGE)
                loss = hvd_dev.allreduce(loss, grad_axis,
                                         ReduceOp.AVERAGE)
                if zero_tx is not None:
                    # ZeRO leg: the transform owns both the exchange
                    # (reduce-scatter wire) and — for states/params —
                    # the shard-local fused update.
                    updates, opt_state = zero_tx.update(
                        grads, opt_state,
                        params=(params if args.zero == "params"
                                else full))
                    if args.zero == "params":
                        new_params = jax.tree.map(jnp.add, params,
                                                  updates)
                    else:
                        new_params = optax.apply_updates(full, updates)
                    return new_params, new_stats, opt_state, loss
                grads = hvd_opt.allreduce_gradients(grads, axis=grad_axis)
                updates, opt_state = opt.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), new_stats,
                        opt_state, loss)

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(), batch_spec, batch_spec),
                out_specs=(P(), P(), P(), P()), check_vma=False)(
                    params, stats, opt_state, images, labels)

        one_step = _sharded_step

    if args.steps_per_call > 1:
        from jax import lax

        def step_fn(params, stats, opt_state, images, labels):
            def body(_, carry):
                p, s, o, _loss = carry
                p, s, o, loss = one_step(p, s, o, images, labels)
                return p, s, o, loss.astype(jnp.float32)

            init = (params, stats, opt_state,
                    jnp.zeros((), jnp.float32))
            return lax.fori_loop(0, args.steps_per_call, body, init)
    else:
        step_fn = one_step
    step = donated_step(step_fn, donate_argnums=(0, 1, 2))

    t0 = time.perf_counter()
    compiled = step.lower(params, stats, opt_state, images, labels).compile()
    compile_s = time.perf_counter() - t0
    print(f"compile: {compile_s:.1f}s", file=sys.stderr)
    # XLA cost analysis counts a while/fori_loop BODY ONCE (trip count is
    # not multiplied), so the N-steps-per-call program reports ~one step's
    # flops/bytes already — do NOT divide by steps_per_call (measured:
    # dividing made the probe's MFU exactly 10x low at
    # --steps-per-call 10, tools/ab_results.json resnet_steps_per_call10).
    # That body-counted-once behavior is undocumented XLA internals, so
    # sanity-check it against the analytic step count (~3x forward FLOPs
    # for training ResNet-50): if a future XLA starts multiplying by trip
    # count, the reported flops jump ~steps_per_call-fold and we rescale
    # rather than inflate MFU.  A missing cost analysis fails the run —
    # MFU from a hard-coded constant is not a measurement.
    cost = compiled.cost_analysis()
    flops_per_step = flops_pre_rescale = float(cost["flops"])
    bytes_per_step = float(cost["bytes accessed"])
    analytic_flops = 3 * 4.1e9 * args.batch_size
    if args.steps_per_call > 1 and flops_per_step > 2 * analytic_flops:
        rescaled = flops_per_step / args.steps_per_call
        if rescaled <= 2 * analytic_flops:
            print(f"cost_analysis flops {flops_per_step:.3e} looks "
                  f"trip-count-multiplied; using /steps_per_call = "
                  f"{rescaled:.3e}", file=sys.stderr)
            flops_per_step = rescaled

    # Telemetry mode (HVDT_TELEMETRY=1): hvd.init() starts the /metrics
    # exporter, a StepTimer publishes step-time percentiles / examples/s
    # / MFU (from the cost-analysis flops above), the goodput ledger
    # books the compile, and the straggler monitor's periodic eager
    # allgather probe exercises the instrumented collective path — so a
    # scrape mid-run shows nonzero bytes-on-wire counters.  The
    # accounting happens OUTSIDE the timed regions.
    telemetry_timer = telemetry_ledger = None
    from horovod_tpu.telemetry import instrument as _tinst

    if _tinst.enabled():
        import horovod_tpu as hvd
        from horovod_tpu import telemetry as _tele

        hvd.init()
        telemetry_ledger = _tele.GoodputLedger(already_elapsed=compile_s)
        telemetry_ledger.charge("recompile", compile_s)
        telemetry_timer = _tele.StepTimer(
            examples_per_step=args.batch_size,
            flops_per_step=flops_per_step,
            device_kind=dev.device_kind,
            straggler=_tele.StragglerMonitor())
        exp = _tele.get_exporter()
        if exp is not None:
            print(f"telemetry /metrics on port {exp.port}",
                  file=sys.stderr)

    # Timing contract: end every timed region with a HOST FETCH of a scalar
    # that data-depends on the last step (float(loss)).  Successive step
    # calls chain through donated buffers and pipeline asynchronously, so
    # each timed iter pays one fetch, amortized over num_batches_per_iter
    # real steps.
    t0 = time.perf_counter()
    for _ in range(args.num_warmup):
        params, stats, opt_state, loss = compiled(params, stats, opt_state,
                                                  images, labels)
    float(loss)
    print(f"warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    # Chaos-audit mode: with HVDT_FAULT_PLAN set, the step loop carries
    # the 'step' injection point and a preemption guard, and the output
    # JSON reports how many injected faults the loop absorbed — so
    # resilience overhead and recovery behavior are auditable straight
    # from bench output.  Without a plan this is a no-op (inj is None).
    from horovod_tpu.resilience import faults as _faults
    from horovod_tpu.resilience.preempt import PreemptionGuard

    inj = _faults.get_injector()
    recovered_faults = 0
    guard = PreemptionGuard().install() if inj is not None else None

    rates = []
    step_idx = 0
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            if inj is not None:
                step_idx += 1
                try:
                    inj.fire("step", step=step_idx)
                except _faults.InjectedFault as e:
                    print(f"bench: recovered injected fault: {e}",
                          file=sys.stderr)
                    recovered_faults += 1
                guard.check(step=step_idx)
            params, stats, opt_state, loss = compiled(
                params, stats, opt_state, images, labels)
        float(loss)
        dt = time.perf_counter() - t0
        rates.append(args.batch_size * args.num_batches_per_iter
                     * args.steps_per_call / dt)
        if telemetry_timer is not None:
            steps_this_iter = args.num_batches_per_iter * args.steps_per_call
            per_step = dt / steps_this_iter
            for _ in range(steps_this_iter):
                telemetry_timer.observe(per_step)

    value = float(np.mean(rates))
    peak, peak_bw = _peak_for(dev.device_kind)
    steps_per_s = value / args.batch_size
    mfu = steps_per_s * flops_per_step / peak if peak else None
    assert mfu is None or mfu <= 1.0, (
        f"measured MFU {mfu:.2f} > 1 is physically impossible — timing did "
        "not actually wait for device completion")
    # Roofline diagnosis: HBM bandwidth fraction (why MFU stops where it
    # does — see docs/performance.md).  Two numbers, both labelled by
    # method:
    #   * hbm_util — XPlane-profiled: per-op bytes capped at what the
    #     op's duration could physically move (compute-bound ops
    #     contribute their real bytes, bandwidth-bound ops at most
    #     peak*dur), summed over a 3-step trace.  XLA's raw "bytes
    #     accessed" is an operand-bytes UPPER BOUND (VMEM reuse isn't
    #     subtracted); the per-op duration cap removes its worst
    #     overcount instead of clamping the aggregate to 1.0.
    #   * hbm_util_est_upper — the uncapped cost-analysis aggregate, for
    #     reference (may exceed 1.0 by construction).
    # The profile either gives hbm_util or fails the run; with
    # HVDT_BENCH_PROFILE=0 the field is absent and the line says so.
    hbm_util = hbm_method = None
    est_upper = (steps_per_s * bytes_per_step / peak_bw
                 if peak_bw else None)
    profile_on = os.environ.get("HVDT_BENCH_PROFILE", "1") not in (
        "0", "false", "off")
    if peak_bw and args.steps_per_call == 1 and profile_on:
        # Capped at 1.0: the per-op duration cap makes >1 possible
        # only when profiler overhead inflates traced durations
        # relative to the untraced timing loop — unphysical, clamp.
        hbm_util = min(1.0, _profiled_hbm_util(
            compiled, params, stats, opt_state, images,
            labels, steps_per_s, peak_bw))
        hbm_method = "xplane_per_op_bw_capped"
    else:
        print("hbm_util: not measured (no profile taken)", file=sys.stderr)
    print(f"img/sec per iter: {[round(r, 1) for r in rates]} "
          f"(+-{float(np.std(rates)):.1f}); final loss {float(loss):.3f}; "
          f"flops/step {flops_per_step:.3e}", file=sys.stderr)
    telemetry_doc = None
    if telemetry_timer is not None:
        from horovod_tpu.telemetry import exporter as _texp
        from horovod_tpu.telemetry import flight_recorder as _tfr
        from horovod_tpu.telemetry import trace as _ttrace

        telemetry_doc = _texp.snapshot_dict()
        telemetry_doc["goodput_fraction"] = round(
            telemetry_ledger.fraction(), 4)
        exp = _texp.get_exporter()
        if exp is not None:
            telemetry_doc["metrics_port"] = exp.port
        # Forensics layer: where the span dump landed and how much the
        # flight recorder holds — the two handles an operator needs
        # after a bad run.
        if _ttrace.get_tracer() is not None:
            telemetry_doc["trace_file"] = _ttrace.flush(publish=False)
        fr = _tfr.get_flight_recorder()
        if fr is not None:
            telemetry_doc["flight_recorder_events"] = len(fr.events())
        # Predicted-vs-observed attribution (HVDT_EXPECTED_SCHEDULE):
        # the cost model's exposed-comm prediction, the observed
        # comm-exposed step time, the deviation ratio, and per-kind
        # anomaly counts.
        evo = _tele.expected_vs_observed_doc()
        if evo is not None:
            telemetry_doc["expected_vs_observed"] = evo
        if args.report and os.environ.get("HVDT_EVENT_LOG"):
            from horovod_tpu.analysis.report import render_report

            print(render_report(os.environ["HVDT_EVENT_LOG"]),
                  file=sys.stderr)
    print(json.dumps({
        "metric": METRIC,
        "value": round(value, 2),
        "unit": UNIT,
        "vs_baseline": round(value / BASELINE_IMG_S_PER_DEVICE, 3),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "hbm_util": round(hbm_util, 4) if hbm_util is not None else None,
        "hbm_util_method": hbm_method,
        "hbm_util_est_upper": (round(est_upper, 4)
                               if est_upper is not None else None),
        "batch_size": args.batch_size,
        "compile_s": round(compile_s, 2),
        # Auditability of the trip-count rescale heuristic (ADVICE r5):
        # the raw cost-analysis flops ride along, so a wrong rescale is
        # visible from the results file, not just stderr.
        "flops_per_step": flops_per_step,
        "flops_pre_rescale": flops_pre_rescale,
        **({"compile_cache": cache_dir} if cache_dir else {}),
        **(_overlap_doc() if args.overlap else {}),
        **(_transport_doc(args.transport) if args.transport else {}),
        **(_zero_doc(args, zero_tx, params, opt_state) if args.zero
           else {}),
        **({"remat": args.remat} if args.remat else {}),
        **(_fp8_doc() if args.fp8 else {}),
        **(_ckpt_stall_doc(params) if args.ckpt_stall else {}),
        **({"fused_optimizer": True} if args.fused_optimizer else {}),
        **({"steps_per_call": args.steps_per_call}
           if args.steps_per_call != 1 else {}),
        **({"fault_plan": os.environ.get("HVDT_FAULT_PLAN", ""),
            "recovered_faults": recovered_faults,
            "injected_faults": inj.fired_total(),
            "emergency_checkpoints": PreemptionGuard.emergency_checkpoints}
           if inj is not None else {}),
        **({"telemetry": telemetry_doc} if telemetry_doc else {}),
    }))


def _ckpt_stall_doc(tree) -> dict:
    """The --ckpt-stall leg: how long does the step loop stall for one
    commit of the trained state, synchronous save vs ``save_async``
    (submit-side only; the async write itself is drained before the
    temp dirs are removed)?"""
    import shutil as _shutil
    import tempfile

    from horovod_tpu.checkpoint import CheckpointManager

    out = {}
    root = tempfile.mkdtemp(prefix="hvdt-ckpt-stall-")
    prev = os.environ.pop("HVDT_ASYNC_CKPT", None)
    try:
        mgr = CheckpointManager(os.path.join(root, "sync"))
        t0 = time.perf_counter()
        mgr.save(1, tree, force=True)
        out["sync"] = round((time.perf_counter() - t0) * 1e3, 2)
        os.environ["HVDT_ASYNC_CKPT"] = "1"
        amgr = CheckpointManager(os.path.join(root, "async"))
        t0 = time.perf_counter()
        amgr.save_async(1, tree, force=True)
        out["async"] = round((time.perf_counter() - t0) * 1e3, 2)
        amgr.wait_for_async(120)
        amgr.close()
    except Exception as e:   # the probe must never sink the bench
        print(f"ckpt-stall probe failed: {e!r}", file=sys.stderr)
        return {}
    finally:
        if prev is None:
            os.environ.pop("HVDT_ASYNC_CKPT", None)
        else:
            os.environ["HVDT_ASYNC_CKPT"] = prev
        _shutil.rmtree(root, ignore_errors=True)
    return {"checkpoint_stall_ms": out}


def _overlap_doc() -> dict:
    """The --overlap leg's JSON fields: the telemetry gauge value (the
    acceptance handle — `overlap_fraction > 0` proves the schedule
    actually traced hidden collectives) and the last bucket plan."""
    from horovod_tpu.ops import overlap as _ovl
    from horovod_tpu.telemetry.instrument import get_recorder

    fraction = None
    rec = get_recorder()
    if rec is not None:
        try:
            v = float(rec.registry.gauge("hvdt_overlap_fraction").value())
            if v > 0:       # 0.0 is the never-set default — fall through
                fraction = round(v, 4)
        except Exception:
            fraction = None
    if fraction is None and _ovl.overlap_fraction() is not None:
        fraction = round(_ovl.overlap_fraction(), 4)
    return {"overlap": True,
            "overlap_fraction": fraction,
            "overlap_schedule": _ovl.last_schedule()}


def _transport_doc(spec: str) -> dict:
    """The --transport leg's JSON fields: the resolved policy and the
    per-axis wire-byte counters (the hierarchical-savings evidence)."""
    from horovod_tpu.telemetry.instrument import get_recorder
    from horovod_tpu.transport import get_policy

    pol = get_policy()
    doc = {"transport": spec,
           "transport_policy": pol.describe() if pol else None}
    rec = get_recorder()
    if rec is not None:
        try:
            wb = rec.registry.get("hvdt_wire_bytes_total")
            if wb is not None:
                doc["wire_bytes_by_axis"] = {
                    ",".join(f"{k}={v}" for k, v in key): val
                    for key, val in sorted(wb._values.items())}
        except Exception:
            pass
    return doc


def _fp8_doc() -> dict:
    """The --fp8 leg's JSON fields: the gate/probe state, whether the
    lowered HLO really carries the f8e4m3 convert-dot, and a matmul
    microbench (fp8 vs plain bf16) — the compute-side analog of the
    wire-byte evidence.  Also snapshots the per-axis wire-byte counters
    when telemetry ran (fp8 legs usually ride a transport config)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.quant import fp8 as _f8
    from horovod_tpu.telemetry.instrument import get_recorder

    doc = {"fp8": {"mode": _f8.fp8_mode(),
                   "available": _f8.fp8_available(),
                   "engaged": _f8.matmul_enabled()}}
    try:
        k = 1024
        x = jnp.ones((k, k), jnp.bfloat16)
        w = jnp.ones((k, k), jnp.float32)
        f_fp8 = jax.jit(lambda a, b: _f8.fp8_matmul(a, b))
        f_ref = jax.jit(lambda a, b: a @ b.astype(a.dtype))
        doc["fp8"]["hlo_has_f8"] = (
            "f8e4m3" in f_fp8.lower(x, w).compile().as_text())
        for f, key in ((f_fp8, "fp8_matmul_us"),
                       (f_ref, "bf16_matmul_us")):
            jax.block_until_ready(f(x, w))
            t0 = time.perf_counter()
            out = None
            for _ in range(10):
                out = f(x, w)
            jax.block_until_ready(out)
            doc["fp8"][key] = round(
                (time.perf_counter() - t0) / 10 * 1e6, 1)
    except Exception as e:  # the probe must never sink the bench
        print(f"fp8 microbench failed: {e!r}", file=sys.stderr)
    rec = get_recorder()
    if rec is not None:
        try:
            wb = rec.registry.get("hvdt_wire_bytes_total")
            if wb is not None:
                doc["wire_bytes_by_axis"] = {
                    ",".join(f"{k}={v}" for k, v in key): val
                    for key, val in sorted(wb._values.items())}
        except Exception:
            pass
    return doc


def _zero_doc(args, zero_tx, params, opt_state) -> dict:
    """The --zero leg's JSON fields: the stage and the per-rank
    post-sharding memory accounting (the ZeRO evidence —
    optimizer_state_bytes shrinks ~n× at stages states/params).  Also
    feeds the hvdt_param_bytes / hvdt_optimizer_state_bytes telemetry
    gauges."""
    from horovod_tpu.telemetry.step_stats import (record_memory_accounting,
                                                  tree_bytes)

    n = int(getattr(getattr(zero_tx, "spec", None), "num_shards", 0)
            or 1)
    opt_bytes = tree_bytes(opt_state)
    param_bytes = tree_bytes(params)
    if args.zero in ("states", "params"):
        # State stacks are [n, shard_len]; a rank holds one row.
        opt_bytes //= max(1, n)
    if args.zero == "params":
        param_bytes //= max(1, n)
    record_memory_accounting(param_bytes=param_bytes,
                             optimizer_state_bytes=opt_bytes,
                             zero_stage=args.zero)
    return {"zero_stage": args.zero,
            "zero_num_shards": n,
            "optimizer_state_bytes": int(opt_bytes),
            "param_bytes": int(param_bytes)}


def _profiled_hbm_util(compiled, params, stats, opt_state, images,
                       labels, steps_per_s, peak_bw) -> float:
    """Capture a 3-step XPlane trace and estimate achieved HBM
    bandwidth utilization: sum over ops of min(cost-analysis bytes,
    duration * peak_bw), normalized by measured step time * peak_bw."""
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from profile_step import aggregate, capture

    n = 3
    state = [params, stats, opt_state]

    def one():
        p, s, o, loss = compiled(state[0], state[1], state[2], images,
                                 labels)
        state[0], state[1], state[2] = p, s, o
        float(loss)

    path = capture(one, n, tempfile.mkdtemp(prefix="hvdt_bench_prof_"))
    per_op, _cat, _busy, _span = aggregate(path)
    moved = 0.0
    for rec in per_op.values():
        if rec["bytes_accessed"]:
            moved += min(float(rec["bytes_accessed"]),
                         rec["dur_ps"] / 1e12 * peak_bw)
    bytes_per_step = moved / n
    return bytes_per_step * steps_per_s / peak_bw


def _spawn(child_args, timeout_s):
    """Run this script once in child mode; return (json_line_or_None,
    note).  The line is returned only when the child exited 0 AND ran on
    a TPU: with ``JAX_PLATFORMS`` unset a failed TPU init falls back to
    the CPU with a warning, and a number from there must not be printed
    under a device metric's name."""
    cmd = [sys.executable, os.path.abspath(__file__), "--_child",
           "--_measure"] + child_args
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=ROOT)
    except subprocess.TimeoutExpired:
        return None, f"child timed out after {timeout_s}s"
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "")[-600:]
        return None, f"child rc={proc.returncode}: {tail}"
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            platform = json.loads(line).get("platform")
        except ValueError:
            continue
        if platform != "tpu":
            return None, (f"child ran on platform {platform!r}, not a "
                          "TPU — not a measurement")
        return line, ""
    return None, "child printed no JSON line"


def _measure(child_args, timeout_s) -> int:
    """One attempt.  Prints the metric line and returns 0, or prints why
    not on stderr and returns 1 — never a metric without the chip."""
    line, note = _spawn(child_args, timeout_s)
    if line is None:
        print(f"bench: no measurement: {note}", file=sys.stderr)
        return 1
    print(line)
    return 0


def main() -> int:
    args = _parse_args()
    if args._child:
        if args.serve_llm:
            _run_serve_llm_child(args)
        elif args.serve:
            _run_serve_child(args)
        else:
            _run_child(args)
        return 0

    # The four legs below are in-process and never need the chip: the
    # controller storm and the fleet replay are pure host Python; --moe
    # and --pipeline pin the 8-device CPU sim before anything imports jax.
    if args.controller:
        _run_controller_bench(args)
        return 0
    if args.fleet:
        _run_fleet_bench(args)
        return 0
    if args.moe:
        _run_moe_bench(args)
        return 0
    if args.pipeline:
        _run_pipeline_bench(args)
        return 0

    if args.serve_llm:
        return _measure(
            ["--serve-llm",
             "--serve-llm-requests", str(args.serve_llm_requests),
             "--serve-llm-new-tokens", str(args.serve_llm_new_tokens)],
            SERVE_TIMEOUT_S)
    if args.serve:
        return _measure(
            ["--serve", "--serve-duration", str(args.serve_duration),
             "--serve-threads", str(args.serve_threads)],
            SERVE_TIMEOUT_S)

    return _measure(
        ["--batch-size", str(args.batch_size),
         "--image-size", str(args.image_size),
         "--num-iters", str(args.num_iters),
         "--num-batches-per-iter", str(args.num_batches_per_iter),
         "--num-warmup", str(args.num_warmup),
         "--steps-per-call", str(args.steps_per_call)]
        + (["--fused-optimizer"] if args.fused_optimizer else [])
        + (["--overlap"] if args.overlap else [])
        + (["--transport", args.transport] if args.transport else [])
        + (["--zero", args.zero] if args.zero else [])
        + (["--remat", args.remat] if args.remat else [])
        + (["--fp8"] if args.fp8 else [])
        + (["--ckpt-stall"] if args.ckpt_stall else [])
        + (["--report"] if args.report else []),
        TRAIN_TIMEOUT_S)


if __name__ == "__main__":
    sys.exit(main())
