"""Env-var knob registry — single source of truth for runtime configuration.

TPU-native analog of the reference's env registry (ref: common/common.h:107-141,
parsed in operations.cc:436-607 and utils/env_parser.cc).  Precedence follows
the reference (runner/common/util/config_parser.py): CLI > env > config file >
built-in default; the launcher translates CLI flags into these env vars.

All knobs use the ``HVDT_`` prefix (Horovod-TPU).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional

from ..analysis.topology import NOMINAL_SIM_PEAK_FLOPS

__all__ = ["Knob", "KNOBS", "CONTRACT_VARS", "get", "get_bool", "get_int",
           "get_float", "get_str", "registry_doc"]


def _parse_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    default: Any
    parser: Callable[[str], Any]
    doc: str

    def read(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        try:
            return self.parser(raw)
        except (ValueError, TypeError):
            return self.default


def _k(name: str, default: Any, parser: Callable[[str], Any], doc: str) -> Knob:
    return Knob(name, default, parser, doc)


# Registry.  Reference analogs noted per knob (common.h line refs).
KNOBS: Dict[str, Knob] = {
    k.name: k
    for k in [
        # --- fusion / cycle (ref: HOROVOD_FUSION_THRESHOLD common.h:112,
        #     HOROVOD_CYCLE_TIME :113) ---
        _k("HVDT_FUSION_THRESHOLD", 64 * 1024 * 1024, int,
           "Tensor-fusion bucket size in bytes for fused collectives: "
           "how many leaves one collective carries, not a buffer's size "
           "(on the exact wire a bucket is one psum over its leaves' own "
           "shapes, which XLA combines into a variadic all-reduce; only "
           "the quantized, hierarchical and Adasum wires pack it flat). "
           "64 MiB default (TPU HBM-friendly; ref default 128 MiB)."),
        _k("HVDT_CYCLE_TIME", 0.0, float,
           "Background-loop cycle time in ms for the eager path. 0 = run "
           "as fast as possible (XLA mode forces 0 in the reference, "
           "operations.cc:500-506)."),
        _k("HVDT_BATCH_COLLECTIVES", True, _parse_bool,
           "Pack multiple same-dtype tensors into one fused collective."),
        # --- overlap scheduling (ops/overlap.py: dependency-ordered
        #     bucket schedule, async collectives, pipelined int8 wire,
        #     fused-update latency hiding) ---
        _k("HVDT_OVERLAP", "", str,
           "Overlapped gradient exchange: 'on' routes bucketed gradient "
           "collectives through the reverse-topological, barrier-pinned "
           "overlap schedule (ops/overlap.py) so each bucket's allreduce "
           "is issued as soon as its grads exist; unset/'off' (default) "
           "keeps the monolithic fused_allreduce path — the EXACT "
           "pre-existing code objects (overlap.get_scheduler() is None, "
           "zero wrappers)."),
        _k("HVDT_XLA_LATENCY_HIDING", "auto", str,
           "XLA latency-hiding scheduler / async collective fusion "
           "flags (ridden via LIBTPU_INIT_ARGS, read once at TPU "
           "backend init; inert off-TPU): auto (skip when JAX_PLATFORMS "
           "pins a non-TPU backend), on, off.  Engaged by hvd.init() "
           "— this is what turns the overlap "
           "schedule's dependency freedom into overlapped execution on "
           "hardware."),
        _k("HVDT_AUTOTUNE_OVERLAP", False, _parse_bool,
           "Add an overlap-schedule on/off dimension to the autotune "
           "search space; the step builder is rebuilt with overlap=... "
           "at each knob change (autotune.AutotunedStep), hot-swappable "
           "because both legs keep one optimizer state tree (the "
           "schedule changes lowering, never state).  Starting point "
           "comes from HVDT_OVERLAP."),
        # --- transport policies (horovod_tpu/transport: per-mesh-axis
        #     algorithm / wire dtype / fusion threshold + the two-level
        #     hierarchical allreduce) ---
        _k("HVDT_TRANSPORT", "", str,
           "Per-mesh-axis transport policy: comma entries "
           "axis:algorithm:wire[:threshold] with axis in "
           "{ici,dcn,dp,pp,fsdp,ep,sp,tp}, algorithm in "
           "{ring,tree,2d_ring}, wire in {f32,bf16,fp16,int8}, "
           "threshold like 64M — e.g. 'ici:ring:f32:64M,dcn:tree:int8:"
           "8M'; 'auto' derives the topology default (innermost axis = "
           "ICI ring f32, outer = DCN tree f32 8M).  Multi-axis reduce "
           "groups then run the hierarchical allreduce (fast-axis "
           "reduce-scatter -> slow-axis shard exchange -> allgather).  "
           "Unset (default) keeps the flat path as the identical code "
           "objects (transport.get_policy() is None, zero wrappers); "
           "unknown vocabulary fails hvd.init() with the valid lists."),
        _k("HVDT_AUTOTUNE_TRANSPORT", False, _parse_bool,
           "Add a flat-vs-hierarchical transport dimension (0/1) to the "
           "autotune search space; the step builder is rebuilt with "
           "transport=... at each knob change (autotune.AutotunedStep), "
           "hot-swappable because both legs keep one optimizer state "
           "tree (the policy changes lowering, never state).  Starting "
           "point: HVDT_TRANSPORT set, or the measured "
           "HVDT_AUTOTUNE_TRANSPORT_SEED verdict."),
        _k("HVDT_AUTOTUNE_TRANSPORT_SEED", "", str,
           "Path to a bench_allreduce.py --json-out file; when its "
           "measured hierarchical_speedup_vs_flat_at_peak exceeds 1.0 "
           "the autotuner's transport dimension STARTS on the "
           "hierarchical leg — policies are seeded from measurements, "
           "not guesses."),
        # --- ZeRO-sharded gradient exchange / optimizer state
        #     (ops/zero.py: reduce-scatter wire, shard-local fused
        #     updates, allgather-on-demand parameters) ---
        _k("HVDT_ZERO", "", str,
           "ZeRO-style state-sharding stage: 'grads' swaps the fused "
           "allreduce for an explicit reduce-scatter + invariant-"
           "allgather split (same wire bytes, deferrable allgather; any "
           "optax optimizer); 'states' reduce-scatters gradients and "
           "runs the single-HBM-pass optimizer update on each rank's "
           "1/n shard of the moments, allgathering only the parameter "
           "deltas (optimizer HBM shrinks ~n x; requires fused_adam/"
           "fused_sgd); 'params' additionally keeps the parameters "
           "sharded between steps (allgather-on-demand via the fsdp "
           "sharding rules).  Unset/'off' (default) keeps the "
           "replicated path as the identical code objects "
           "(zero.get_zero() is None, zero wrappers); unknown stages "
           "fail hvd.init() with the valid list."),
        _k("HVDT_AUTOTUNE_ZERO", False, _parse_bool,
           "Add a replicated-vs-ZeRO-sharded dimension (0/1) to the "
           "autotune search space; the step builder is rebuilt with "
           "zero=... at each knob change (autotune.AutotunedStep), "
           "hot-swappable because both legs keep ONE sharded state "
           "tree (the replicated leg exchanges via allreduce and "
           "slices its shard — same layout, different wire).  Starting "
           "point: HVDT_ZERO set, or the measured "
           "HVDT_AUTOTUNE_ZERO_SEED verdict."),
        _k("HVDT_AUTOTUNE_ZERO_SEED", "", str,
           "Path to a bench_allreduce.py --reduce-scatter --json-out "
           "file; when its measured rs_ag_speedup_vs_allreduce_at_peak "
           "exceeds 1.0 the autotuner's zero dimension STARTS on the "
           "sharded leg — seeded from measurements, not guesses "
           "(mirrors HVDT_AUTOTUNE_TRANSPORT_SEED)."),
        # --- 4D parallelism (horovod_tpu/parallel: expert axis +
        #     1F1B pipeline as first-class mesh axes) ---
        _k("HVDT_PP", 1, int,
           "Pipeline-parallel extent of the pod mesh "
           "(parallel.mesh.pod_mesh_spec): carves whole pod groups "
           "into 1F1B stages — the pp axis rides the DCN tier, its "
           "ppermute ticks cross pods.  Must divide the pod count; 1 "
           "(default) keeps the classic (dcn, ici) 2-axis mesh."),
        _k("HVDT_EP", 1, int,
           "Expert-parallel extent of the pod mesh "
           "(parallel.mesh.pod_mesh_spec): carves chips inside each "
           "pod into expert ranks — the ep axis rides the ICI tier, "
           "the MoE dispatch/combine a2a stays on-pod.  Must divide "
           "the pod size; 1 (default) keeps the classic 2-axis mesh."),
        _k("HVDT_MOE_CAPACITY_FACTOR", 1.25, float,
           "Default expert capacity factor for "
           "parallel.moe.moe_dispatch_combine: per-expert slots = "
           "ceil(tokens * top_k / experts * factor).  Tokens over "
           "capacity are dropped (residual passthrough); "
           "hvdt_moe_dropped_fraction reports the realized drop rate."),
        _k("HVDT_MOE_TOPK", 1, int,
           "Default experts-per-token for "
           "parallel.moe.moe_dispatch_combine (gates renormalized "
           "over the chosen k; 1 = switch routing).  Primary choices "
           "claim capacity before secondary ones."),
        _k("HVDT_PEAK_FLOPS", NOMINAL_SIM_PEAK_FLOPS, float,
           "Nominal peak FLOP/s for parallel.pipeline."
           "report_pipeline_mfu (per-chip peak x chips).  On the CPU "
           "sim any consistent value works — MFU is a ratio; the "
           "hvdt_pipeline_mfu gauge carries the result."),
        _k("HVDT_PIPELINE_MICROBATCHES", 8, int,
           "Default 1F1B microbatch count (the pipeline autotune "
           "dimension's starting point). "
           "More microbatches shrink the bubble fraction "
           "(p-1)/(m+p-1) at the cost of smaller per-tick payloads."),
        _k("HVDT_AUTOTUNE_MOE", False, _parse_bool,
           "Add an expert capacity-factor dimension to the autotune "
           "search space; the step builder is rebuilt with "
           "capacity_factor=... at each knob change "
           "(autotune.AutotunedStep), hot-swappable because capacity "
           "changes the dispatch layout, never optimizer state.  "
           "Starting point: HVDT_MOE_CAPACITY_FACTOR set explicitly, "
           "or the cost model's a2a-wire ordering "
           "(HVDT_AUTOTUNE_MODEL_SEED)."),
        _k("HVDT_AUTOTUNE_PIPELINE", False, _parse_bool,
           "Add a 1F1B microbatch-count dimension to the autotune "
           "search space; the step builder is rebuilt with "
           "microbatches=... at each knob change "
           "(autotune.AutotunedStep), hot-swappable because the "
           "microbatch clock changes lowering, never state.  Starting "
           "point: HVDT_PIPELINE_MICROBATCHES set explicitly, or the "
           "cost model's ppermute ordering (HVDT_AUTOTUNE_MODEL_SEED)."),
        # --- activation rematerialization (models/: jax.checkpoint
        #     policy on the transformer block — the second half of the
        #     memory-for-MFU trade next to HVDT_ZERO) ---
        _k("HVDT_REMAT", "", str,
           "Activation rematerialization for the transformer block: "
           "'none'/'' (default) saves all activations; 'full' saves "
           "only block inputs (min HBM, +1/3 FLOPs); 'dots' uses "
           "jax.checkpoint_policies.dots_with_no_batch_dims_saveable "
           "(save matmul outputs, recompute elementwise+attention — "
           "falls back to 'full' with a warning on jax builds without "
           "the policy).  Consumed by models.remat_from_env; "
           "unknown values raise with the valid list."),
        # --- cache (ref: HOROVOD_CACHE_CAPACITY common.h:114) ---
        _k("HVDT_CACHE_CAPACITY", 1024, int,
           "Response-cache capacity (negotiated-collective descriptors)."),
        # --- autotune (ref: HOROVOD_AUTOTUNE* common.h:132-137) ---
        _k("HVDT_AUTOTUNE", False, _parse_bool,
           "Enable Bayesian autotuning of fusion threshold / cycle time."),
        _k("HVDT_AUTOTUNE_LOG", "", str, "CSV log file for autotune samples."),
        _k("HVDT_AUTOTUNE_WARMUP_SAMPLES", 3, int, "Autotune warmup discard count."),
        _k("HVDT_AUTOTUNE_STEPS_PER_SAMPLE", 10, int, "Steps per autotune sample."),
        _k("HVDT_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20, int, "Max BO samples."),
        _k("HVDT_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8, float, "GP noise alpha."),
        _k("HVDT_AUTOTUNE_FUSED_OPTIMIZER", False, _parse_bool,
           "Add a fused-vs-unfused optimizer dimension (0/1) to the "
           "autotune search space; the step builder is then rebuilt "
           "with fused=... at each knob change (autotune.AutotunedStep). "
           "Starting point comes from HVDT_FUSED_OPTIMIZER."),
        # --- telemetry (horovod_tpu/telemetry: metrics registry,
        #     per-collective instrumentation, straggler detection,
        #     per-worker /metrics exporter — no reference analog beyond
        #     the Timeline; the observability subsystem) ---
        _k("HVDT_TELEMETRY", False, _parse_bool,
           "Enable the unified telemetry subsystem: per-collective "
           "bytes/latency metrics, step stats (examples/s, MFU, goodput),"
           " straggler detection, and the per-worker /metrics HTTP "
           "exporter (started by hvd.init()).  Off (default) installs "
           "ZERO wrapper objects on the hot paths "
           "(telemetry.instrument.get_recorder() is None)."),
        _k("HVDT_METRICS_PORT", 9090, int,
           "Base port for the per-worker /metrics + /healthz exporter; "
           "each worker binds base + local_rank (0 = ephemeral port).  "
           "A taken slot falls back to ephemeral with a logged warning."),
        _k("HVDT_STRAGGLER_WINDOW", 64, int,
           "Steps between cross-rank step-duration allgathers for "
           "straggler detection (telemetry/straggler.py).  0 disables "
           "the cross-rank check."),
        _k("HVDT_STRAGGLER_THRESHOLD", 2.0, float,
           "A rank is flagged as a straggler when its mean step time "
           "over the last window exceeds this multiple of the median."),
        _k("HVDT_TELEMETRY_PUBLISH_S", 30.0, float,
           "Seconds between worker snapshot publishes to the rendezvous "
           "KV (/telemetry/<rank>) for driver-side aggregation; only "
           "active under the elastic launcher.  0 disables publishing."),
        # --- live perf attribution (telemetry/history.py +
        #     telemetry/anomaly.py: per-metric time series, windowed
        #     anomaly detectors, predicted-vs-observed pricing) ---
        _k("HVDT_HISTORY", False, _parse_bool,
           "Keep bounded per-metric time series (ring buffers of "
           "(wall_ts, step, value) samples: step time, examples/s, MFU, "
           "goodput fraction, per-axis wire bytes, perf-deviation "
           "ratio), served as /timeseries on the per-worker exporter, "
           "published in the KV telemetry snapshot for driver-side "
           "step-aligned roll-ups, and fed to the windowed anomaly "
           "detectors.  Requires HVDT_TELEMETRY.  Off (default) = zero "
           "overhead (telemetry.history.get_history() is None)."),
        _k("HVDT_HISTORY_WINDOW", 512, int,
           "Max samples retained per time series (ring buffer; the "
           "recent window is what detectors and `hvdtrun top` read, "
           "memory stays flat)."),
        _k("HVDT_HISTORY_SAMPLE_S", 1.0, float,
           "Minimum seconds between time-series samples (the recording "
           "cadence; steps arriving faster are coalesced into one "
           "sample carrying their mean step time).  0 = sample every "
           "observed step (tests, short runs)."),
        _k("HVDT_EVENT_LOG", "", str,
           "Path of the structured JSONL anomaly event log: each "
           "detector firing (step_time_shift, goodput_drop, "
           "mfu_regression, wire_drift, straggler_onset, "
           "perf_deviation) appends one JSON line with kind / step / "
           "rank / pod / value / baseline / ratio / message; the "
           "elastic driver writes cluster-scoped events (a pod-wide "
           "shift is ONE event) to the same format.  Empty (default) = "
           "off (telemetry.anomaly.get_event_log() is None); "
           "hvdt_anomaly_total{kind} counters ride the registry either "
           "way when detectors run."),
        _k("HVDT_EVENT_LOG_MAX_BYTES", 0, int,
           "Size bound for the HVDT_EVENT_LOG JSONL file: when an "
           "append would push it past this many bytes the file rotates "
           "to <path>.1 (keep-1 — the previous .1 is replaced) and a "
           "fresh file starts, so a long run with a chatty online "
           "controller cannot grow the log unboundedly.  0 (default) = "
           "unbounded (the pre-rotation behavior)."),
        # --- online policy controller (horovod_tpu/control: the
        #     driver-side loop that prices anomaly events with the cost
        #     model and acts at step boundaries) ---
        _k("HVDT_CONTROLLER", "", str,
           "Engage the online policy controller on the elastic driver: "
           "anomaly events from the HVDT_EVENT_LOG sensor plane are "
           "mapped to candidate actions (flip a transport leg, retune "
           "the bucket threshold, toggle the overlap/ZeRO legs, evict "
           "a straggler pod, resize the world, scale serve replicas), "
           "priced OFFLINE with the analytical cost model, and the "
           "best candidate clearing the guardrails is applied at a "
           "step boundary through the no-recompile autotune leg "
           "machinery, then verified against "
           "hvdt_perf_deviation_ratio with a never-worse rollback.  "
           "Values: empty/0 (default) = off "
           "(control.get_controller() is None, zero overhead); 1/on = "
           "act; observe = decide + log but never apply (dry run).  "
           "Decisions append controller_decision / controller_outcome "
           "records to the event JSONL — auditable and replayable."),
        _k("HVDT_CONTROLLER_COOLDOWN_S", 60.0, float,
           "Per-action-kind cooldown: after the controller applies an "
           "action, the same kind is ineligible for this many seconds "
           "(doubled after each never-worse rollback of that kind) so "
           "one bad actuator cannot thrash the run."),
        _k("HVDT_CONTROLLER_ENTER_RATIO", 1.2, float,
           "Hysteresis ENTER band: a triggering event's slowdown ratio "
           "must be at least this factor before the controller acts "
           "(events below it are recorded as suppressed:hysteresis)."),
        _k("HVDT_CONTROLLER_EXIT_RATIO", 1.05, float,
           "Hysteresis EXIT band: hvdt_perf_deviation_ratio must fall "
           "back under this factor for an applied action to count as "
           "recovered and for its trigger to re-arm — the enter/exit "
           "split is what prevents flapping on an oscillating series."),
        _k("HVDT_CONTROLLER_RECOVERY_WINDOW", 3, int,
           "Controller ticks an applied action gets to bring the "
           "deviation ratio under the exit band before the never-worse "
           "rollback re-applies the inverse leg (one-way actions — "
           "evict/resize/replica-scale — just expire)."),
        _k("HVDT_CONTROLLER_MIN_GAIN_S", 0.0, float,
           "Minimum predicted step-seconds improvement a candidate "
           "must clear (from the offline cost-model pricing) to be "
           "applied; candidates below it are suppressed:no_gain."),
        _k("HVDT_CONTROLLER_MAX_ACTIONS", 0, int,
           "Total actions the controller may apply over one run (0 = "
           "unbounded) — the blast-radius bound for unattended runs."),
        # --- fleet scheduler (horovod_tpu/fleet: one pod inventory,
        #     two workloads — training backfills serving's trough and
        #     drains when router pressure crosses the band) ---
        _k("HVDT_FLEET", "", str,
           "Engage the bin-packing fleet scheduler over the shared pod "
           "inventory: serving pressure (router queue depth per "
           "replica vs HVDT_SERVE_QUEUE_HI, p99 vs the SLO) above the "
           "ENTER band reclaims a training pod for serving (exit-83 "
           "drain; emergency commit + peer-RAM restore make it cheap), "
           "and a deep trough backfills a serve pod to training — "
           "every move priced offline (cost model at the candidate "
           "world size vs predicted SLO headroom) and wrapped in the "
           "controller guardrail battery.  Values: empty/0 (default) "
           "= off (fleet.get_scheduler() is None, zero overhead); "
           "1/on = act; observe = decide + log but never move a pod.  "
           "Decisions append fleet_decision / fleet_outcome records "
           "to the event JSONL.  When active it owns the "
           "/serve/target_replicas key via a seq-guarded doc; the "
           "controller's scale_replicas action becomes a hint routed "
           "through it; raw-int KV / --target-file overrides still "
           "win."),
        _k("HVDT_FLEET_COOLDOWN_S", 60.0, float,
           "Per-move-kind cooldown: after the fleet scheduler applies "
           "a reclaim or backfill, the same kind is ineligible for "
           "this many seconds (doubled after each never-worse "
           "rollback) so one workload cannot thrash the other."),
        _k("HVDT_FLEET_ENTER_RATIO", 1.2, float,
           "Hysteresis ENTER band on the serving pressure ratio "
           "(queue/HVDT_SERVE_QUEUE_HI or p99/SLO, whichever is "
           "worse): pressure must reach this factor before a reclaim "
           "fires (below it -> suppressed:hysteresis)."),
        _k("HVDT_FLEET_EXIT_RATIO", 1.05, float,
           "Hysteresis EXIT band: pressure must fall back under this "
           "factor for an applied reclaim to count as recovered and "
           "for the pressure trigger to re-arm — the enter/exit split "
           "that keeps a flappy traffic series from ping-ponging "
           "pods."),
        _k("HVDT_FLEET_BACKFILL_RATIO", 0.5, float,
           "Trough band: serving pressure at/below this fraction of "
           "threshold marks a trough, releasing one serve pod back to "
           "training (never below the serve floor, and charged the "
           "predicted pressure increase before commit)."),
        _k("HVDT_FLEET_RECOVERY_WINDOW", 3, int,
           "Scheduler ticks an applied move gets to prove itself: a "
           "reclaim must bring pressure under the exit band before "
           "the window expires or the never-worse rollback backfills "
           "the pod home; a backfill that pushes pressure over the "
           "ENTER band inside the window is reclaimed back."),
        _k("HVDT_FLEET_MIN_GAIN", 0.0, float,
           "Minimum predicted gain (dimensionless: serving relief "
           "minus training throughput cost) a candidate move must "
           "clear; candidates below it are suppressed:no_gain."),
        _k("HVDT_FLEET_MAX_MOVES", 0, int,
           "Total moves the fleet scheduler may apply over one run "
           "(0 = unbounded) — the blast-radius bound."),
        _k("HVDT_FLEET_MIN_TRAIN_PODS", 1, int,
           "Floor on pods leased to training: reclaims never shrink "
           "the training world below this many pods (the elastic "
           "min_np analog at fleet granularity)."),
        _k("HVDT_PERF_DEVIATION_RATIO", 2.0, float,
           "Fire a perf_deviation anomaly event when "
           "hvdt_perf_deviation_ratio (observed EWMA step seconds vs "
           "the cost-model-predicted step seconds: predicted exposed "
           "comm + compute anchor) exceeds this factor — the runtime "
           "mirror of the CI --perf ratchet.  Needs "
           "HVDT_EXPECTED_SCHEDULE (or an in-process traced "
           "fingerprint) so hvd.init() can price the schedule."),
        # --- distributed tracing + flight recorder (telemetry/trace.py,
        #     telemetry/flight_recorder.py — cross-rank forensics) ---
        _k("HVDT_TRACE_DIR", "", str,
           "Enable distributed span tracing and write per-rank Chrome-"
           "trace dumps (trace_rank<N>.json) plus desync reports into "
           "this directory; under the elastic launcher the driver also "
           "merges per-rank dumps from the rendezvous KV into "
           "trace_merged.json (rank as pid).  Empty (default) = off, "
           "zero overhead (telemetry.trace.get_tracer() is None)."),
        _k("HVDT_TRACE_BUFFER", 65536, int,
           "Max spans retained per rank by the trace buffer (ring; "
           "forensics wants the recent window, memory stays flat)."),
        _k("HVDT_FLIGHT_RECORDER", False, _parse_bool,
           "Enable the collective flight recorder: an always-cheap ring "
           "buffer of the last N collective events per rank (seq, "
           "op/name/dtype/bytes/wire, in-flight vs done), dumped on "
           "stall-abort (with a cross-rank desync report), on "
           "preemption, and on demand via the exporter's /flightrecorder"
           " endpoint.  Off (default) = zero overhead "
           "(telemetry.flight_recorder.get_flight_recorder() is None)."),
        _k("HVDT_FLIGHT_RECORDER_EVENTS", 256, int,
           "Ring capacity (events) of the collective flight recorder."),
        _k("HVDT_EXPECTED_SCHEDULE", "", str,
           "Path to a static collective-schedule fingerprint JSON "
           "(exported by `python -m horovod_tpu.analysis --schedule "
           "OUT.json` or analysis.schedule.ScheduleFingerprint.save). "
           "When set, desync reports gain an `expected_schedule` "
           "section comparing the STATIC expected issue order against "
           "every rank's runtime-observed events and naming the first "
           "deviation — static-expected vs observed forensics instead "
           "of observed-vs-observed.  Empty (default) = off."),
        _k("HVDT_COSTMODEL_CALIBRATION", "", str,
           "Path to the analytical cost model's fitted calibration "
           "JSON (per-(tier, algorithm, wire) alpha-beta constants, "
           "regenerated by tools/fit_costmodel.py from bench_allreduce "
           "--json-out rows).  Empty (default) = the checked-in "
           ".hvdt-costmodel-calibration.json at the repo root; a "
           "missing file degrades to the analysis/topology.py "
           "order-of-magnitude defaults."),
        _k("HVDT_PERF_BASELINE", "", str,
           "Path to the static perf-regression baseline JSON the "
           "`python -m horovod_tpu.analysis --perf` gate ratchets "
           "against (predicted exposed-comm seconds, per-axis wire "
           "bytes, overlap fraction for the reference fingerprints; "
           "regenerated by --update-perf-baseline).  Empty (default) "
           "= the checked-in .hvdt-perf-baseline.json at the repo "
           "root."),
        _k("HVDT_AUTOTUNE_MODEL_SEED", "", str,
           "Let autotune consult the static cost model "
           "(analysis/costmodel.predict_leg_order) to order its "
           "flat-vs-hierarchical / wire-dtype / overlap starting legs "
           "when no measured HVDT_AUTOTUNE_*_SEED sweep is available: "
           "'1' uses the default calibration, a path names a "
           "calibration file.  Unset (default) = off — measured seeds "
           "and explicit env policies always win over the model."),
        # --- timeline (ref: HOROVOD_TIMELINE common.h:110) ---
        _k("HVDT_TIMELINE", "", str,
           "Write per-tensor Chrome-tracing timeline JSON to this path."),
        _k("HVDT_TIMELINE_MARK_CYCLES", False, _parse_bool,
           "Mark background-loop cycles in the timeline."),
        # --- stall detection (ref: HOROVOD_STALL_CHECK_* common.h:116-118) ---
        _k("HVDT_STALL_CHECK_DISABLE", False, _parse_bool, "Disable stall inspector."),
        _k("HVDT_STALL_CHECK_TIME_SECONDS", 60, int,
           "Warn when a tensor is ready on some-but-not-all ranks this long."),
        _k("HVDT_STALL_SHUTDOWN_TIME_SECONDS", 0, int,
           "Abort after this long stalled (0 = never)."),
        _k("HVDT_STALL_ABORT_TIME_SECONDS", 0, int,
           "Stall-escalation abort rung (resilience/escalation.py): past "
           "this age the coordinator aborts the stalled negotiation with "
           "an error response, so waiters raise HorovodInternalError and "
           "the elastic retry loop recovers instead of hanging forever. "
           "0 = disabled (warn-only, the seed behavior)."),
        _k("HVDT_STALL_RESET_TIME_SECONDS", 0, int,
           "Stall-escalation reset rung: past this age a worker "
           "additionally publishes READY to the elastic driver's "
           "registry, requesting a full re-rendezvous.  0 = disabled."),
        # --- resilience: fault injection + failure detection ---
        _k("HVDT_FAULT_PLAN", "", str,
           "Declarative chaos-testing fault plan (resilience/faults.py), "
           "e.g. 'crash@step=12:rank=1,hang@step=30:secs=20,"
           "corrupt_ckpt@step=40,kv_drop@p=0.1'.  Empty (default) "
           "compiles every injection point to a no-op."),
        _k("HVDT_FAULT_SEED", 0, int,
           "RNG seed for probabilistic fault-plan entries (kv_drop@p=...) "
           "so chaos runs are reproducible."),
        _k("HVDT_FAULT_JOURNAL", "", str,
           "Path prefix for the fired-fault journal (per rank: "
           "<path>.rank<N>).  Elastic recovery respawns processes; the "
           "journal carries each fault's fired count across restarts so "
           "'times' bounds fires per JOB, not per process life.  Empty "
           "= per-process counting."),
        _k("HVDT_CONTROL_PLANE_TIMEOUT_S", 300.0, float,
           "Coordination-service gather/broadcast timeout — the failure-"
           "detection latency bound: a dead peer surfaces as this timeout "
           "firing, converted to HorovodInternalError for the elastic "
           "retry loop.  Chaos tests shrink it to recover in seconds."),
        _k("HVDT_ELASTIC_BLACKLIST_COOLDOWN_S", 0.0, float,
           "Blacklist cooldown for failed hosts in elastic discovery: 0 "
           "(default) = permanent blacklist; >0 = the host re-enters "
           "discovery after the cooldown, doubling per repeated failure "
           "(capped 8x).  Set on preemptible fleets where a crash rarely "
           "means a bad machine — and for single-host chaos runs, where "
           "a permanent blacklist would strand the job."),
        _k("HVDT_TCP_CONNECT_RETRIES", 3, int,
           "Socket-mesh bootstrap attempts for the native TCP data plane "
           "(shared exponential backoff between tries): peers of a "
           "restarted rank come up at different times."),
        # --- pod-granular elastic control plane (runner/elastic/pods.py) ---
        _k("HVDT_POD", "", str,
           "Pod (TPU slice) id this worker belongs to.  Set per slot by "
           "the elastic launcher from the discovery script's "
           "'host[:slots][@pod]' column; read by pod-scoped fault-plan "
           "entries (pod_crash/pod_partition) and published in the "
           "telemetry KV snapshot so the driver can aggregate per pod."),
        _k("HVDT_POD_SIZE", 0, int,
           "Slots per pod.  Driver side: chunk undeclared discovery "
           "hosts (in order) into pods of this many slots — the "
           "alternative to the @pod discovery column.  Worker side: the "
           "ici extent of the two-level (dcn, ici) mesh contract "
           "(parallel.mesh.pod_mesh_spec).  0 = per-host pods (the flat "
           "PR-4 semantics)."),
        _k("HVDT_POD_EXIT_WINDOW_S", 10.0, float,
           "Pod exit-correlation window: failure exits of one pod's "
           "ranks within this many seconds collapse into ONE pod-"
           "removal event — one blacklist entry, one cooldown clock — "
           "instead of N independent recovery decisions for what is a "
           "single correlated slice loss."),
        _k("HVDT_POD_DRAIN_GRACE_S", 60.0, float,
           "How long a preemption-drained pod stays excluded from pod "
           "assignment while waiting for the platform to reclaim its "
           "hosts; after the grace it becomes placeable again rather "
           "than stranded (a drain is advisory, not a blacklist)."),
        _k("HVDT_POD_STRAGGLER_EVICT", 0, int,
           "Pod-straggler eviction rung: a pod whose median step time "
           "exceeds HVDT_STRAGGLER_THRESHOLD x the cross-pod median for "
           "this many consecutive telemetry windows is evicted "
           "(cooldown blacklist + pod-granular resize down) instead of "
           "dragging every synchronous step.  0 = disabled.  Needs "
           "HVDT_TELEMETRY on the workers (the driver aggregates their "
           "KV snapshots)."),
        # --- continuous goodput (checkpoint.py / resilience/peer_store.py) ---
        _k("HVDT_ASYNC_CKPT", False, _parse_bool,
           "Asynchronous non-blocking checkpointing: "
           "CheckpointManager.save_async takes a device->host snapshot "
           "at the commit point and hands it to a background writer "
           "thread (queue depth 1, a newer snapshot supersedes a queued "
           "older one); the LAST_GOOD pointer advances only after the "
           "manifest write + fsync completes.  Unset (default): "
           "save_async IS the synchronous save (identity contract)."),
        _k("HVDT_CKPT_SNAPSHOT_BUDGET_S", 1.0, float,
           "Stall budget for the commit-point device->host checkpoint "
           "snapshot (the only part of an async save the step loop "
           "pays).  Snapshots are timed into the "
           "hvdt_ckpt_snapshot_seconds summary; one exceeding the "
           "budget logs a warning and increments "
           "hvdt_ckpt_snapshot_over_budget_total."),
        _k("HVDT_PEER_STORE", False, _parse_bool,
           "In-memory peer-replicated snapshot tier: at every commit "
           "point each rank publishes its committed snapshot over the "
           "rendezvous KV and mirrors peer (rank+1) %% n's newest "
           "snapshot in host RAM, so a single-rank or single-pod loss "
           "restores surviving state over the KV/TCP path without "
           "touching the filesystem (manifest-verified disk remains "
           "the fallback tier).  Needs the elastic rendezvous env "
           "(HVDT_RENDEZVOUS_ADDR) to be active."),
        # --- logging (ref: HOROVOD_LOG_LEVEL) ---
        _k("HVDT_LOG_LEVEL", "warning", str,
           "trace|debug|info|warning|error|fatal"),
        _k("HVDT_LOG_HIDE_TIME", False, _parse_bool, "Hide timestamps in log lines."),
        # --- profiler (ref: HOROVOD_DISABLE_NVTX_RANGES) ---
        _k("HVDT_DISABLE_PROFILER_RANGES", False, _parse_bool,
           "Disable jax.profiler TraceAnnotation ranges around eager ops."),
        # --- kernels ---
        _k("HVDT_FLASH_ATTENTION", "auto", str,
           "Pallas flash-attention kernel: auto (on a TPU, from the "
           "measured crossover: sequences of 512 and longer), on, off."),
        _k("HVDT_FUSED_CONV1X1", False, _parse_bool,
           "Route eligible ResNet 1x1 conv+BN(+ReLU) blocks through the "
           "fused Pallas kernels (ops/conv_fused.py): train mode emits "
           "conv output + batch-stat partials in one pass, eval mode "
           "fuses the folded affine into the matmul epilogue.  Off "
           "until a run of the resnet50_train cell with the variable "
           "set decides it — an unmeasured kernel is not a default.  "
           "Eligibility: "
           "1x1, stride 1, Cin % 128 == 0 AND Cout % 128 == 0 (SyncBN "
           "via psum'd stat partials when bn_axis is set)."),
        _k("HVDT_RING_PALLAS", False, _parse_bool,
           "Run ring attention's per-step block update and backward "
           "through the Pallas kernels (when shapes tile)."),
        _k("HVDT_FUSED_OPTIMIZER", False, _parse_bool,
           "Route optimizer updates through the fused Pallas kernels "
           "(ops/optim_kernels.fused_adam/fused_sgd) where leaves are "
           "tile-eligible; ineligible leaves fall back to the identical "
           "XLA math.  Off until a run of a benchmark cell with the "
           "variable set decides it (the autotuner's fused dimension "
           "reads it as the starting point)."),
        # --- step pipeline ---
        _k("HVDT_COMPILATION_CACHE", "", str,
           "Directory for JAX's persistent XLA compilation cache "
           "(step_pipeline.enable_compilation_cache; engaged inside "
           "hvd.init()).  JAX_COMPILATION_CACHE_DIR, where set, wins "
           "over it; the root scripts (chip_smoke.py, "
           "bench_allreduce.py) default to <checkout>/.xla_cache below "
           "it.  Empty = unset; off = disabled."),
        _k("HVDT_COMPILATION_CACHE_MIN_COMPILE_SECS", 1.0, float,
           "Only persist compilations at least this expensive — keeps "
           "the multi-second train steps, skips trivial helper jits."),
        # --- serving (horovod_tpu/serve: engine, batcher, HTTP front end,
        #     hot reload — no reference analog; the inference workload) ---
        _k("HVDT_SERVE_HOST", "127.0.0.1", str,
           "Bind address for the serving HTTP front end."),
        _k("HVDT_SERVE_PORT", 8000, int,
           "Bind port for the serving HTTP front end (0 = ephemeral)."),
        _k("HVDT_SERVE_BUCKETS", "1,8,32", str,
           "Comma ladder of batch-size shape buckets the engine jits; "
           "requests are padded up to the smallest admitting bucket so "
           "steady-state traffic never recompiles."),
        _k("HVDT_SERVE_MAX_BATCH_SIZE", 32, int,
           "Max rows the dynamic batcher coalesces into one dispatch."),
        _k("HVDT_SERVE_MAX_DELAY_MS", 5.0, float,
           "Max linger (ms) the batcher waits for a fuller batch after "
           "the first request arrives — the batching latency budget."),
        _k("HVDT_SERVE_MAX_QUEUE_DEPTH", 256, int,
           "Admission-control bound (rows queued but not dispatched); "
           "past it /predict sheds load with HTTP 503 instead of "
           "growing the queue into an OOM."),
        _k("HVDT_SERVE_REQUEST_TIMEOUT_S", 30.0, float,
           "Per-request deadline inside the server (504 past it)."),
        _k("HVDT_SERVE_RELOAD_INTERVAL_S", 10.0, float,
           "Seconds between checkpoint-directory polls for hot weight "
           "reload (serve/reload.py CheckpointWatcher)."),
        # --- elastic serving control plane (serve/router.py +
        #     serve/autoscale.py on the pod-aware elastic machinery) ---
        _k("HVDT_SERVE_HEARTBEAT_S", 2.0, float,
           "Replica heartbeat period to the rendezvous KV "
           "(/serve/replicas/<id>); the router treats a replica whose "
           "heartbeat is older than 2x this as dead and routes around "
           "it — the serving analog of the elastic dead-peer bound."),
        _k("HVDT_SERVE_SLO_P99_MS", 0.0, float,
           "p99 latency SLO (ms) for routing and autoscaling: the "
           "router ejects a replica whose reported p99 breaches it, "
           "and the autoscaler scales up while the fleet p99 sits "
           "above it.  0 = no SLO enforcement."),
        _k("HVDT_SERVE_REPLICAS", 1, int,
           "Initial/target replica count for `hvdtrun serve "
           "--replicas` (the elastic serving control plane; 1 = the "
           "single-replica PR-2 path unless --autoscale raises it)."),
        _k("HVDT_SERVE_MAX_REPLICAS", 4, int,
           "Autoscaler ceiling on replica count (and the localhost "
           "slot count of the default serve host discovery)."),
        _k("HVDT_SERVE_AUTOSCALE", False, _parse_bool,
           "Enable the replica autoscaler loop: scale up on queue "
           "depth per replica / p99-over-SLO, scale down on idle "
           "queues, within [1, HVDT_SERVE_MAX_REPLICAS]."),
        _k("HVDT_SERVE_SCALE_COOLDOWN_S", 10.0, float,
           "Minimum seconds between autoscaler scale events — resize "
           "decisions must not flap faster than replicas boot/drain."),
        _k("HVDT_SERVE_QUEUE_HI", 16.0, float,
           "Scale-UP watermark: mean queued rows per live replica "
           "above this adds a replica (queue depth is the leading "
           "indicator; p99 breaches confirm it)."),
        _k("HVDT_SERVE_QUEUE_LO", 2.0, float,
           "Scale-DOWN watermark: mean queued rows per replica below "
           "this (with p99 inside the SLO) drains the newest replica."),
        _k("HVDT_SERVE_ROUTER_PORT", 0, int,
           "Bind port for the serving router front tier (0 = "
           "ephemeral; the router logs the bound port on start)."),
        _k("HVDT_SERVE_EJECT_COOLDOWN_S", 3.0, float,
           "Seconds an ejected replica (failed probe / SLO breach / "
           "dispatch failures) sits out of routing before re-admission "
           "— doubles per repeated ejection like the elastic host "
           "blacklist cooldown."),
        _k("HVDT_SERVE_HEDGE_MS", 0.0, float,
           "Hedge-request threshold (ms): a /predict still unanswered "
           "past it is duplicated to a second replica and the first "
           "response wins.  0 = adaptive (hedge past ~2x the router's "
           "observed p99, floored at 50 ms); negative = hedging off."),
        # --- continuous-batching LLM decode engine (serve/llm: paged KV
        #     cache, per-iteration scheduler, jitted decode loop) ---
        _k("HVDT_SERVE_ENGINE", "static", str,
           "Serving engine: 'static' (the shape-bucket InferenceEngine) "
           "or 'continuous' (the serve/llm continuous-batching decode "
           "engine with a paged KV cache; --model transformer only).  "
           "The router and autoscaler are engine-agnostic."),
        _k("HVDT_KV_BLOCK_SIZE", 16, int,
           "Tokens per paged-KV-cache block.  Smaller blocks waste less "
           "tail capacity per sequence but grow the block tables; the "
           "decode step's gather shape is [slots, blocks_per_seq * "
           "block_size], so block_size * HVDT_KV_SEQ_BLOCKS bounds "
           "context length."),
        _k("HVDT_KV_BLOCKS", 128, int,
           "Total paged-KV-cache block budget per engine (physical "
           "block 0 is the write sink for inactive decode slots and is "
           "never allocated).  The scheduler admits/evicts against this "
           "budget; HBM cost is 2 * layers * blocks * block_size * "
           "kv_heads * head_dim * dtype bytes."),
        _k("HVDT_KV_SEQ_BLOCKS", 8, int,
           "Block-table length per sequence (max context = this * "
           "HVDT_KV_BLOCK_SIZE tokens).  Fixed so the decode step's "
           "gather never changes shape — the zero-recompile contract."),
        _k("HVDT_SERVE_DECODE_SLOTS", 8, int,
           "Decode-slot count of the continuous engine: sequences "
           "decoded per iteration.  Fixed shape — admission/eviction "
           "swaps sequences in and out of slots without recompiling."),
        _k("HVDT_SERVE_PREFILL_CHUNK", 64, int,
           "Prefill chunk length (tokens) of the continuous engine.  "
           "Long prompts stream through in chunks of this size, one "
           "chunk per iteration, so a long prefill never stalls decode "
           "for more than one chunk's worth of compute (decode-p99 "
           "disaggregation)."),
        _k("HVDT_SERVE_MAX_NEW_TOKENS", 32, int,
           "Default generation budget per request for the continuous "
           "engine (a request's max_new_tokens field overrides, capped "
           "by the context bound)."),
        _k("HVDT_SERVE_INT8", False, _parse_bool,
           "Serve transformer weights block-scaled int8 (quant/kernels "
           "quantize_flat) in the continuous engine: eligible matmul "
           "weights are stored int8+scales in HBM and dequantized "
           "inside the jitted step — ~4x weight-HBM density per "
           "replica, unchanged request API."),
        _k("HVDT_SERVE_BATCH_QUOTA", 0.5, float,
           "Ceiling fraction of decode slots the 'batch' tenant class "
           "may hold.  The live quota adapts below this off the "
           "interactive-tenant queue-wait time series (telemetry/"
           "history.Series): sustained interactive waiting shrinks the "
           "batch share, an idle interactive queue restores it."),
        _k("HVDT_SERVE_RING_PREFILL", 0, int,
           "Sequence-parallel degree for long-context prefill in the "
           "continuous engine: prompts spanning at least half the "
           "context ride a shard_map ring_attention island over this "
           "many devices (0/1 = chunked single-device prefill only)."),
        # --- host data plane (ref: HOROVOD_CPU_OPERATIONS common.h:127-128,
        #     LibType selection env_parser.cc) ---
        _k("HVDT_CPU_OPERATIONS", "xla", str,
           "Host-collective data plane: 'xla' (host tensors ride the device "
           "mesh) or 'tcp' (native C++ socket-mesh backend, the Gloo analog)."),
        _k("HVDT_TCP_ADDRS", "", str,
           "Rank-ordered host:port list for the native TCP backend (set by "
           "the launcher when HVDT_CPU_OPERATIONS=tcp; process set k "
           "listens on port + k*HVDT_TCP_SET_PORT_STRIDE)."),
        _k("HVDT_TCP_TIMEOUT_MS", 30000, int,
           "Connect timeout for the native TCP backend mesh bootstrap."),
        _k("HVDT_TCP_SET_PORT_STRIDE", 128, int,
           "Port stride between process sets' socket meshes. All base "
           "ports on one host must live in a contiguous block smaller "
           "than this stride, so per-set listener ports (base + "
           "set_id*stride) never collide with another rank's ports."),
        # --- elastic (ref: HOROVOD_ELASTIC common.h:139) ---
        _k("HVDT_ELASTIC", False, _parse_bool, "Elastic (fault-tolerant) mode."),
        # --- topology / rendezvous (set by the launcher; ref env contract
        #     runner/gloo_run.py:65-76) ---
        _k("HVDT_RANK", -1, int, "Global process rank (set by launcher)."),
        _k("HVDT_SIZE", -1, int, "Global process count (set by launcher)."),
        _k("HVDT_LOCAL_RANK", -1, int, "Rank within the host (set by launcher)."),
        _k("HVDT_LOCAL_SIZE", -1, int, "Processes on this host (set by launcher)."),
        _k("HVDT_CROSS_RANK", -1, int, "Host index (set by launcher)."),
        _k("HVDT_CROSS_SIZE", -1, int, "Number of hosts (set by launcher)."),
        _k("HVDT_HOSTNAME", "", str, "Logical hostname assigned by launcher."),
        _k("HVDT_COORDINATOR_ADDR", "", str,
           "host:port of the JAX coordination service / rendezvous KV."),
        _k("HVDT_RENDEZVOUS_ADDR", "", str, "Rendezvous HTTP KV server address."),
        _k("HVDT_RENDEZVOUS_PORT", 0, int, "Rendezvous HTTP KV server port."),
        _k("HVDT_SECRET_KEY", "", str, "HMAC key for launcher RPC authentication."),
        # --- mesh defaults ---
        _k("HVDT_MESH_AXES", "", str,
           "Comma list of axis=size pairs for the default mesh, e.g. "
           "'dp=4,tp=2'. Empty = 1-D data-parallel mesh over all devices."),
        # --- orchestrators (horovod_tpu/orchestrate: Spark barrier
        #     execution + estimator dataframe sharding) ---
        _k("HVDT_SPARK_START_TIMEOUT", 600.0, float,
           "Seconds the Spark barrier job waits for every executor "
           "slot to check in before aborting the launch (the "
           "--start-timeout analog for orchestrate/spark.run)."),
        _k("HVDT_SPARK_RUN_TIMEOUT", 86400.0, float,
           "Wall-clock bound (seconds) on one orchestrate/spark.run "
           "barrier job; past it the job group is cancelled and the "
           "run raises instead of holding executors forever."),
        _k("HVDT_SPARK_COORD_TIMEOUT", 120.0, float,
           "Seconds a Spark barrier task waits for rank 0's "
           "coordinator address broadcast before giving up."),
        _k("HVDT_DFSHARD_TIMEOUT", 120.0, float,
           "Seconds the estimator's dataframe-shard fetch waits for "
           "each worker's partition to materialize."),
        # --- example harness A/B switches (read by examples/,
        #     documented in docs/performance.md) ---
        _k("HVDT_LM_SINGLE", True, _parse_bool,
           "examples/jax_transformer_lm.py: run the single-island step "
           "layout (default); 0/false re-runs the per-stage island leg "
           "as the A/B comparison documented in docs/performance.md."),
        # --- persistence safety ---
        _k("HVDT_MLPARAMS_ALLOW_PREFIXES", "horovod_tpu.", str,
           "Comma list of module prefixes orchestrate/ml_params.load() "
           "may import classes from (metadata.json 'class' field); a "
           "non-allowlisted class is rejected BEFORE any unpickling. "
           "Extend when persisting your own MLParams subclasses, e.g. "
           "'horovod_tpu.,myproject.models.'."),
        # --- numerics ---
        _k("HVDT_ALLREDUCE_DTYPE", "", str,
           "Force wire dtype for allreduce ('bfloat16' for compression-"
           "on-the-wire; empty = tensor dtype)."),
        # --- quantized wire (horovod_tpu/quant: block-scaled int8
        #     collectives with error feedback) ---
        _k("HVDT_COMPRESSION", "", str,
           "Gradient wire compressor by name: none|bf16|fp16|int8|int4 "
           "(empty = none).  Consumed by hvd.init() and by "
           "DistributedOptimizer wrappers when compression= is unset; "
           "unknown names raise with the valid list.  The launcher "
           "forwards --compression."),
        _k("HVDT_QUANT", False, _parse_bool,
           "Shorthand for HVDT_COMPRESSION=int8 (wins over it): route "
           "gradient collectives over the block-scaled int8 wire "
           "(quant/collectives two-stage quantized allreduce).  Pair "
           "with quant.with_error_feedback for f32-parity convergence."),
        _k("HVDT_QUANT_BLOCK", 256, int,
           "Block size (elements) for int8/int4 wire quantization: one "
           "f32 absmax scale per block.  256 default = 1.6% scale "
           "overhead; must be a multiple of 128 for the int8 Pallas "
           "lowering (256 for the packed-int4 one; other values fall "
           "back to identical-math XLA)."),
        _k("HVDT_QUANT_KERNELS", "auto", str,
           "Quantize/dequantize lowering: auto (Pallas on TPU, XLA "
           "elsewhere), on (force Pallas — interpret mode off-TPU, the "
           "kernel-equivalence test path), off (XLA everywhere).  Both "
           "lowerings share the same block math."),
        _k("HVDT_AUTOTUNE_QUANT", False, _parse_bool,
           "Add a quantized-wire leg dimension (f32/int8/int4) to the "
           "autotune search space; the step builder is rebuilt with "
           "quant=.../quant_leg=... at each knob change "
           "(autotune.AutotunedStep), hot-swappable because all legs "
           "keep one optimizer state tree (see "
           "quant.with_error_feedback(enabled=...), whose residual is "
           "leg-independent f32).  Starting point comes from "
           "HVDT_QUANT / HVDT_COMPRESSION."),
        _k("HVDT_FP8", "off", str,
           "fp8 (e4m3) compute path: off (default) or matmul — route "
           "the transformer MLP/attention-projection matmuls through "
           "quant.fp8.fp8_matmul (per-tensor delayed-max scaling, f32 "
           "accumulation).  A capability probe falls back to the plain "
           "matmul when the installed jax/backend lacks working fp8 "
           "dtypes, so 'matmul' is always safe to set; unknown values "
           "raise with the valid list."),
    ]
}


# Internal env-contract variables: set by the launcher / elastic driver /
# serve control plane for their own child processes — wiring, not
# operator-facing knobs, so they carry no Knob entry (no default, no
# CLI flag).  Declared here so the static analyzer (horovod_tpu/analysis
# lint rule `knob-drift`) can tell wiring from a typo'd or undeclared
# knob; every HVDT_* read anywhere in the tree must appear either in
# KNOBS or here.
CONTRACT_VARS: Dict[str, str] = {
    "HVDT_SECRET": "HMAC secret for the rendezvous KV (launcher -> "
                   "workers; hex).",
    "HVDT_GENERATION": "Elastic cluster generation counter (driver -> "
                       "workers on each re-rendezvous).",
    "HVDT_NICS": "--network-interface allowlist the launcher exports "
                 "to workers.",
    "HVDT_POD_INDEX": "Pod index of this host (launcher topology "
                      "contract).",
    "HVDT_POD_RANK": "Rank within the pod (launcher topology contract).",
    "HVDT_NUM_PODS": "Pod count of the current mesh (elastic driver "
                     "contract).",
    "HVDT_EXEC_ADDR": "Executor-pool KV address (orchestrate/executor "
                      "driver -> workers).",
    "HVDT_EXEC_PORT": "Executor-pool KV port.",
    "HVDT_EXEC_SECRET": "Executor-pool KV HMAC secret (hex).",
    "HVDT_RUNFUNC_ADDR": "runner.run() function-shipping KV address.",
    "HVDT_RUNFUNC_PORT": "runner.run() function-shipping KV port.",
    "HVDT_RUNFUNC_SECRET": "runner.run() function-shipping KV secret "
                           "(hex).",
    "HVDT_SERVE_REPLICA_ID": "Replica id the serve autoscaler assigns "
                             "to each spawned serving process.",
}


def get(name: str) -> Any:
    return KNOBS[name].read()


def get_bool(name: str) -> bool:
    return bool(get(name))


def get_int(name: str) -> int:
    return int(get(name))


def get_float(name: str) -> float:
    return float(get(name))


def get_str(name: str) -> str:
    return str(get(name))


def registry_doc() -> str:
    """Render the knob registry as help text (used by the CLI)."""
    lines = []
    for k in KNOBS.values():
        lines.append(f"{k.name} (default: {k.default!r})\n    {k.doc}")
    return "\n".join(lines)
