"""One run of one cell: set-up, the reference check, the measured window,
the traced window, the result line's contents.

The path under test is the one a user of the framework runs, built as
``chip_smoke.py`` builds it (a copy: later PRs may change the program, not
the yardstick): ``hvd.init()`` -> a ``dp`` mesh over the cell's chips ->
``hvd.DistributedOptimizer`` fed per-rank gradients -> ``hvd.donated_step``.
Everything that differs between cells is data (``benchmark/workloads``,
``benchmark/configs``) or a file found by name (``families``,
``layer_metrics``).  ``run_cell`` takes its devices as an argument so the
tests can drive it at toy size on the CPU; only ``run.py`` decides whether
a result may be printed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import math
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from benchmark import manifest, trace_reduce

GIB = float(2 ** 30)


class CompileLog:
    """What compiled in this process and when (``jax.monitoring``, as
    ``chip_smoke.CompileLog``): every backend compile with its seconds and
    whether the persistent cache served it."""

    def __init__(self):
        from jax import monitoring

        self.compiles: List[tuple] = []     # (seconds, was a cache hit)
        self._hit = False
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self._hit = False
        elif name == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _duration(self, name, secs, **_):
        # Fires for a hit too (the time to load the executable).
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((secs, self._hit))
            self._hit = False

    @property
    def count(self) -> int:
        return len(self.compiles)

    def missed_seconds(self) -> float:
        return sum(s for s, hit in self.compiles if not hit)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may look at."""

    config: Dict[str, Any]
    traffic: Dict[str, Any]
    family: Any
    chips: int
    peaks: Dict[str, Any]
    hlo_text: str                       # the compiled step, as HLO text
    memory: Any                         # its memory_analysis()
    setup_compile_s: float              # cache-miss compile seconds in set-up
    throughput: float                   # units/s/chip over the measured window
    trace: Optional[trace_reduce.Trace] = None

    @functools.cached_property
    def convolutions(self) -> set:
        """Names of the step's instructions that are, or fuse, a
        convolution (every matmul is one on the TPU)."""
        return trace_reduce.instructions_holding(self.hlo_text,
                                                 "convolution")


# ---------------------------------------------------------------------------
# The main path (copy of chip_smoke.build_dp_step).
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


@contextlib.contextmanager
def _env(values: Dict[str, str]):
    """Env knobs for the duration of a trace (the model reads its knobs
    at trace time)."""
    before = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class Training:
    """The step program of ``family`` on ``devices`` with its state: the
    executable compiled ahead of time from shapes, weights and optimizer
    state made on the device from the seed, each under one jitted call."""

    def __init__(self, family, devices, global_batch: int):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        self.family = family
        self.mesh = Mesh(np.asarray(devices, dtype=object), ("dp",))
        self.replicated = NamedSharding(self.mesh, P())
        self.sharded = NamedSharding(self.mesh, P("dp"))
        self.global_batch = global_batch
        key = jax.random.PRNGKey(0)
        batch = jax.eval_shape(
            lambda k: family.make_batch(k, global_batch), key)
        step, self.opt = build_dp_step(self.mesh, family.loss_fn,
                                       family.optimizer, len(batch))
        params = jax.eval_shape(family.init, key)
        shapes = (self._shaped(params, self.replicated),
                  self._shaped(jax.eval_shape(self.opt.init, params),
                               self.replicated),
                  *self._shaped(batch, self.sharded))
        self.compiled = step.lower(*shapes).compile()
        self.params = self.opt_state = None

    @staticmethod
    def _shaped(tree, sharding):
        import jax

        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    def init_params(self, key):
        import jax

        self.params = jax.jit(self.family.init,
                              out_shardings=self.replicated)(key)
        return self.params

    def init_opt_state(self):
        import jax

        self.opt_state = jax.jit(self.opt.init,
                                 out_shardings=self.replicated)(self.params)

    def make_pool(self, key, batches: int):
        """``batches`` seeded global batches resident on the device,
        sharded over dp, from one jitted call."""
        import jax

        def pool(key):
            return tuple(self.family.make_batch(k, self.global_batch)
                         for k in jax.random.split(key, batches))

        return jax.jit(pool, out_shardings=self.sharded)(key)

    @functools.cached_property
    def hlo_text(self) -> str:
        """The compiled step's HLO text (megabytes: rendered once)."""
        return self.compiled.as_text()

    def step(self, batch):
        """Dispatch one step; returns its loss (not fetched)."""
        self.params, self.opt_state, loss = self.compiled(
            self.params, self.opt_state, *batch)
        return loss


# ---------------------------------------------------------------------------
# correct (a): the system's loss and gradients against the plain reference.
# ---------------------------------------------------------------------------


def _leaf(tree, path: str):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def reference_check(family, params, key, device, mosaic: bool
                    ) -> Dict[str, Any]:
    """Loss and gradients of the system's own loss function (the cell's
    dtype; the attention path the step program was seen to take) against
    ``benchmark/reference`` on a seeded sample, on one device."""
    import jax
    import jax.numpy as jnp

    tol = family.tolerances
    params = jax.device_put(params, device)
    batch = jax.jit(lambda k: family.make_batch(k, family.sample_size),
                    out_shardings=jax.sharding.SingleDeviceSharding(device)
                    )(key)
    with _env(family.sample_env(mosaic)):
        loss_s, grad_s = jax.jit(jax.value_and_grad(family.loss_fn))(
            params, *batch)
    loss_r, grad_r = jax.jit(jax.value_and_grad(family.reference_loss))(
        params, *batch)

    @jax.jit
    def compare(gs, gr):
        def dot(a, b):
            return sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32))
                       for x, y in zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b)))

        def cosine(a, b):
            return dot(a, b) / jnp.sqrt(dot(a, a) * dot(b, b))

        return (jnp.sqrt(dot(gs, gs)), jnp.sqrt(dot(gr, gr)),
                {p: cosine(_leaf(gs, p), _leaf(gr, p))
                 for p in tol["leaf_cosine_min"]})

    norm_s, norm_r, cosines = compare(grad_s, grad_r)
    out = {
        "sample": family.sample_size,
        "loss": float(loss_s), "loss_reference": float(loss_r),
        "grad_norm": float(norm_s), "grad_norm_reference": float(norm_r),
        "leaf_cosine": {p: float(c) for p, c in cosines.items()},
    }
    out["loss_rel"] = abs(out["loss"] - out["loss_reference"]) / abs(
        out["loss_reference"])
    out["grad_norm_rel"] = abs(out["grad_norm"] - out["grad_norm_reference"]
                               ) / out["grad_norm_reference"]
    out["ok"] = bool(
        out["loss_rel"] <= tol["loss_rel"]
        and out["grad_norm_rel"] <= tol["grad_norm_rel"]
        and all(out["leaf_cosine"][p] >= least      # a NaN fails this too
                for p, least in tol["leaf_cosine_min"].items()))
    return out


# ---------------------------------------------------------------------------
# correct (d): dp=n against one device.
# ---------------------------------------------------------------------------


def dp_check(family, devices, spec: Dict[str, Any], seed: int
             ) -> Dict[str, Any]:
    """``spec["steps"]`` steps at ``spec["global_batch"]`` on the dp mesh
    over ``devices`` and on its first device alone, same seed: first loss
    equal to ``first_rel``, last to ``last_rel`` (reduction order
    differs, and the difference compounds through the updates)."""
    import jax

    runs = {}
    for label, devs in (("dp", devices), ("one", devices[:1])):
        t = Training(family, devs, spec["global_batch"])
        t.init_params(jax.random.PRNGKey(seed))
        t.init_opt_state()
        batch = t.make_pool(jax.random.PRNGKey(seed + 1), 1)[0]
        runs[label] = [float(t.step(batch)) for _ in range(spec["steps"])]
    rel = [abs(a - b) / abs(b) for a, b in zip(runs["dp"], runs["one"])]
    return {"dp": runs["dp"], "one": runs["one"],
            "ok": bool(rel[0] <= spec["first_rel"]
                       and rel[-1] <= spec["last_rel"]
                       and all(math.isfinite(v) for v in runs["dp"]))}


# ---------------------------------------------------------------------------
# The windows.
# ---------------------------------------------------------------------------


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_window(training: Training, pool, *, seconds: Optional[float] = None,
               steps: Optional[int] = None, first: int = 0):
    """Steps until ``seconds`` have passed (or exactly ``steps``), the
    host at most two steps ahead of the device: the loss of step i-2 is
    fetched before step i is dispatched.  Returns (losses, elapsed s,
    steps started, steps that raised).  The window opens on a drained
    device and closes when the last step started has finished."""
    losses: List[float] = []
    pending = collections.deque()
    started = raised = 0
    # What the imports and the set-up left on the heap is not garbage: out
    # of the collector's sight, so that no full collection stalls the host
    # for longer than the two steps it is ahead.
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    while True:
        if len(pending) >= 2:
            with _span("bench.fetch_loss"):
                losses.append(float(pending.popleft()))
        if steps is not None and started >= steps:
            break
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
        try:
            with _span("bench.dispatch"):
                pending.append(
                    training.step(pool[(first + started) % len(pool)]))
        except Exception:                # counted, reported, run ends
            import traceback

            traceback.print_exc()
            raised += 1
            started += 1
            break
        started += 1
    with _span("bench.drain"):
        losses.extend(float(x) for x in pending)
    return losses, time.perf_counter() - t0, started, raised


def traced_window(training: Training, pool, steps: int, first: int,
                  trace_dir: str) -> trace_reduce.Trace:
    """``steps`` steps under the profiler (host spans from the benchmark's
    own annotations; Python call tracing off, it slows the host)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        run_window(training, pool, steps=steps, first=first)
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))


def peak_hbm_bytes(memory) -> int:
    """Per-device bytes the step executable needs: arguments + outputs +
    temporaries - 2 x aliased.  On this runtime ``temp_size_in_bytes``
    already holds the donated state the outputs alias, so the aliased
    bytes are in all three terms and are counted once.  Settled on the
    chip in PR 22 (PERF.md section 2: the runtime reserves temporaries -
    aliased for the program, and 0.75 GiB of ballast fits beside the
    seq-512 step where 1.25 GiB does not) and never changed."""
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - 2 * memory.alias_size_in_bytes)


def fullest_device_bytes(devices) -> Dict[str, int]:
    """``bytes_in_use``, ``bytes_reserved`` and the process's
    ``peak_bytes_in_use`` so far on the device that has most of the first
    two together, as the runtime accounts them now ({} where the backend
    keeps no such account)."""
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get("bytes_in_use", 0)
                  + s.get("bytes_reserved", 0))
    return {k: fullest[k] for k in ("bytes_in_use", "bytes_reserved",
                                    "peak_bytes_in_use") if k in fullest}


def run_cell(cell: Dict[str, Any], devices, *, seed: int, seconds: float,
             trace: bool, started_at: float, trace_dir: str
             ) -> Dict[str, Any]:
    """Everything between "the devices are there" and the result line's
    contents.  ``started_at`` is the process's ``time.perf_counter()`` at
    start, for ``setup_s``."""
    import jax

    log = CompileLog()
    phases: List[list] = []             # [what, seconds] of the set-up
    last = started_at

    def phase(name: str):
        nonlocal last
        now = time.perf_counter()
        phases.append([name, now - last])
        last = now

    phase("start, imports, hvd.init")
    config, traffic = cell["config_data"], cell["traffic"]
    chips = cell["chips"]
    devices = list(devices)[:chips]
    peaks = manifest.load_peaks(devices[0].device_kind)
    family = manifest.load_family(config["family"]).build(config, traffic)
    readers = {name: manifest.load_layer_metric(name)
               for name in (cell["layer_metrics"] if trace else [])}
    global_batch = traffic["per_chip_batch"] * chips
    training = Training(family, devices, global_batch)
    memory = training.compiled.memory_analysis()
    mosaic = trace_reduce.MOSAIC_TARGET in training.hlo_text
    phase("step program: trace, compile or load")

    key = jax.random.PRNGKey(seed)
    k_init, k_pool, k_sample = jax.random.split(key, 3)
    params = jax.block_until_ready(training.init_params(k_init))
    phase("weights")
    # What the runtime accounts on the fullest device when the reference
    # check starts (the weights; the loaded step program) and once it is
    # over, the peak so far with it: what a larger configuration's check
    # is sized against.
    before_check = fullest_device_bytes(devices)
    checks: Dict[str, Any] = {
        "reference": reference_check(family, params, k_sample, devices[0],
                                     mosaic)}
    checks["reference"]["device_bytes"] = {
        "before": before_check, "after": fullest_device_bytes(devices)}
    phase("reference check")
    if "dp_check" in cell:
        checks["dp"] = dp_check(family, devices, cell["dp_check"], seed)
        phase("dp check")
    training.init_opt_state()
    pool = jax.block_until_ready(
        training.make_pool(k_pool, traffic["pool_batches"]))
    phase("optimizer state, batch pool")
    warm, _, n_warm, _ = run_window(training, pool,
                                    steps=cell["warmup_steps"])
    phase("warm-up steps")
    setup_compile_s = log.missed_seconds()
    compiles_before = log.count

    setup_s = time.perf_counter() - started_at
    losses, elapsed, attempted, raised = run_window(
        training, pool, seconds=seconds, first=n_warm)
    compiles_in_window = log.count - compiles_before

    units_per_step = global_batch * family.units_per_sample
    finite = [v for v in losses if math.isfinite(v)]
    failed = raised + (len(losses) - len(finite))
    completed = attempted - raised
    throughput = completed * units_per_step / elapsed / chips
    falling = (len(losses) >= 8 and len(finite) == len(losses)
               and sum(losses[-4:]) < sum(losses[:4]))
    checks["setup"] = {"setup_s": setup_s, "phases": phases,
                       "compile_s": setup_compile_s,
                       "compiles": compiles_before}
    checks["window"] = {
        "steps": attempted, "elapsed_s": elapsed,
        "loss_first4": losses[:4], "loss_last4": losses[-4:],
        "loss_falls": bool(falling),
        "compiles_in_window": compiles_in_window,
        "warmup_losses": warm, "mosaic_in_step": mosaic,
        "memory_analysis": {
            k: getattr(memory, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(memory, k)},
    }
    correct = bool(checks["reference"]["ok"] and falling and failed == 0
                   and compiles_in_window == 0
                   and checks.get("dp", {"ok": True})["ok"])

    peak = peak_hbm_bytes(memory)
    stats = [d.memory_stats() or {} for d in devices]
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        # What the runtime accounts for on the fullest chip once the
        # window has run: live arrays plus what it holds reserved for the
        # loaded programs.  peak_bytes_in_use alone leaves the programs'
        # temporaries out on this runtime, and adding the two peaks would
        # add what was never there at one time.
        "memory_peak_bytes": max(
            s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
            for s in stats) or peak,
        "bytes_limit": stats[0].get("bytes_limit"),
    }
    values = {f"{family.unit}_per_s_chip": throughput,
              "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed}

    if trace:
        ctx = Context(
            config=config, traffic=traffic, family=family, chips=chips,
            peaks=peaks, hlo_text=training.hlo_text, memory=memory,
            setup_compile_s=setup_compile_s, throughput=throughput,
            trace=traced_window(training, pool, cell["trace_steps"],
                                n_warm + attempted, trace_dir))
        values = {name: read(ctx) for name, read in readers.items()}
        lo, hi = trace_reduce.window(ctx.trace)
        busy = [trace_reduce.busy_seconds(d)
                for d in ctx.trace.devices.values()]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = hi - lo
        worst = min(ctx.trace.devices.values(),
                    key=trace_reduce.busy_seconds)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(
                worst, label=lambda op: "convolution"
                if op.name in ctx.convolutions else ""),
            "idle_gaps": trace_reduce.idle_gaps(
                worst, ctx.trace.host_spans, (lo, hi))}
        names = cell["layer_metrics"]
    else:
        names = cell["end_to_end"]
    result["metrics"] = {
        name: {"value": values[name], "unit": cell["units"][name]}
        for name in names if values.get(name) is not None}
    result["device"] = device
    result["checks"] = checks
    return result
