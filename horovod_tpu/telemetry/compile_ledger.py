"""Set-up seen from inside: what this process traced, lowered, compiled
or loaded from the persistent cache, per program and per kernel site.

JAX names every stage of a program's way to the device through
``jax.monitoring`` (``/jax/core/compile/jaxpr_trace_duration``,
``.../jaxpr_to_mlir_module_duration``, ``.../backend_compile_duration``,
which fires for a cache hit too: the load) with the function's name and
the span's wall-clock start and end.  :class:`CompileLedger` is the
package's one consumer of them.  It keeps

* **per program** (JAX's ``fun_name`` with ``jit(...)`` stripped, so the
  three stages of one program meet under one name): seconds of its
  *outermost* trace spans, of lowering, of backend work split by the
  ``cache_hits`` event that precedes the span into ``cache_load`` (a
  hit: retrieval, deserialisation, the load onto the device) and
  ``compile`` (a miss); how often each happened; what JAX says the cache
  saved; a **role**, ``step`` for what ``step_pipeline.donated_step``
  built (:func:`note_step_program`) and ``other`` for everything else a
  process compiles;
* **per kernel site** (:func:`kernel_scope`): how often the block was
  entered, which is once a trace of the site, and the host seconds spent
  inside it;
* **the start-up phases JAX does not name** (:meth:`note_startup`: the
  package's import, ``hvd.init()``, the backend's first touch);
* a bounded list of the counted spans (every lowering and backend span,
  the outermost trace spans of a millisecond or more) on ``time.time()``,
  the clock JAX stamps them with and ``telemetry/trace.py`` its own.

A trace span that opens while another trace span of the same thread is
open (a jitted function called by a jitted function; every ``jnp``
function is one) is its caller's time already and is counted as a trace
of its program and no more.  JAX reports a span when it closes, so a
caller's span arrives after its callees'; the depth comes from the
scalar JAX records with the same event when a span opens.  An outermost
trace span under a millisecond (``jax.eval_shape`` of an initialiser
traces a ``jnp`` function at a time, thousands a process) is seconds in
the account and no span of its own.  Both kinds are held by their thread,
with no lock and no metric touched, until its next counted span or a
read: a process's six thousand trace spans cost it a few tens of
milliseconds (PERF.md section 6, PR 51).

Outlets, all existing: the default registry (``hvdt_compile_*``,
``hvdt_startup_seconds``, ``hvdt_kernel_trace*``; bumped whether or not
``HVDT_TELEMETRY`` is set, served by the exporter when it is), the span
``Tracer`` (one ``compile.<stage>`` complete event a counted span, with
the span's own start, when ``HVDT_TRACE_DIR`` is set) and the recovery
``GoodputLedger`` (a step program built again charges ``recompile``; the
first build after a restore charges the recovery phase ``compile``).

Always on: the listeners run when JAX traces, lowers, compiles or loads,
never when a step is dispatched.  docs/observability.md, "Reading a
start-up", has what it costs.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Deque, Dict, NamedTuple, Optional

from . import trace as _trace
from .metrics import CATALOG, default_registry

__all__ = ["CompileLedger", "Program", "KernelSite", "Span", "STAGES",
           "SPAN_BOUND", "get_ledger", "install", "note_step_program",
           "kernel_scope"]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

STAGES = ("trace", "lower", "cache_load", "compile")
STARTUP_PHASES = ("import", "init", "backend")
SPAN_BOUND = 4096
# An outermost trace span shorter than this is seconds in the account and
# no span of its own (below).
SHORT_SPAN_S = 1e-3


class Span(NamedTuple):
    stage: str
    program: str
    start: float            # time.time() seconds, as JAX stamped it
    end: float
    hit: bool               # a backend span the cache served


@dataclasses.dataclass
class Program:
    """One program name's account (seconds by stage, counts)."""

    name: str
    role: str = "other"
    trace_s: float = 0.0    # outermost trace spans only
    lower_s: float = 0.0
    cache_load_s: float = 0.0
    compile_s: float = 0.0
    saved_s: float = 0.0    # JAX's compile_time_saved_sec, as reported
    traces: int = 0         # every trace span, nested ones too
    hits: int = 0
    misses: int = 0
    unbuilt_s: float = 0.0  # trace + lower seconds since its last build

    @property
    def builds(self) -> int:
        """Times the backend compiled or loaded it."""
        return self.hits + self.misses

    @property
    def recompiles(self) -> int:
        """Builds after the first: new shapes, or a dropped cache."""
        return max(0, self.builds - 1)

    @property
    def seconds(self) -> float:
        return (self.trace_s + self.lower_s + self.cache_load_s
                + self.compile_s)


@dataclasses.dataclass
class KernelSite:
    traces: int = 0
    seconds: float = 0.0


def program_name(fun_name: str) -> str:
    """``jit(step)`` (lowering, backend) and ``step`` (trace) are one
    program."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _name_of(fn) -> str:
    """What JAX will call ``fn`` (``jax._src.util.fun_name``)."""
    while True:
        name = getattr(fn, "__name__", None)
        if name is not None:
            return name
        if not hasattr(fn, "func"):
            return "<unnamed function>"
        fn = fn.func            # a functools.partial


def _doc(name: str) -> str:
    """A metric's one-line help: the catalog's, kept in one place."""
    return CATALOG[name].doc


class _Outlets:
    """The ledger's metric handles on one registry, made once a registry:
    the listeners then pay an ``inc`` a counted span, not a lookup."""

    def __init__(self, registry):
        self.registry = registry
        self.seconds = registry.counter(
            "hvdt_compile_seconds_total", _doc("hvdt_compile_seconds_total"))
        self.builds = registry.counter(
            "hvdt_compiles_total", _doc("hvdt_compiles_total"))
        self.recompiles = registry.counter(
            "hvdt_recompiles_total", _doc("hvdt_recompiles_total"))
        self.saved = registry.counter(
            "hvdt_compile_cache_saved_seconds_total",
            _doc("hvdt_compile_cache_saved_seconds_total"))
        self.startup = registry.gauge(
            "hvdt_startup_seconds", _doc("hvdt_startup_seconds"))
        self.kernel_traces = registry.counter(
            "hvdt_kernel_traces_total", _doc("hvdt_kernel_traces_total"))
        self.kernel_seconds = registry.counter(
            "hvdt_kernel_trace_seconds_total",
            _doc("hvdt_kernel_trace_seconds_total"))


class CompileLedger:
    """The account (module docstring).  One is process-wide
    (:func:`get_ledger`); tests build their own and feed the four
    ``on_*`` listeners by hand."""

    def __init__(self):
        self._lock = threading.Lock()
        # a thread's own: depth (open trace spans), nested (traces counted
        # under the open outermost span, by name), hit, saved
        self._local = threading.local()
        self._outlets: Optional[_Outlets] = None
        self.programs: Dict[str, Program] = {}
        self.kernels: Dict[str, KernelSite] = {}
        self.startup: Dict[str, float] = {}
        self.spans: Deque[Span] = collections.deque(maxlen=SPAN_BOUND)
        self.requests = 0       # compiles that asked the persistent cache
        self._step_names: set = set()
        self._restore_s = 0.0   # the recovery ledger's, at the last build

    def _out(self) -> _Outlets:
        """The handles on today's default registry (tests swap it)."""
        registry = default_registry()
        if self._outlets is None or self._outlets.registry is not registry:
            self._outlets = _Outlets(registry)
        return self._outlets

    def _program(self, name: str) -> Program:
        program = self.programs.get(name)
        if program is None:
            program = self.programs[name] = Program(
                name, "step" if name in self._step_names else "other")
        return program

    # -- what the package tells it ------------------------------------------
    def note_step_program(self, fn) -> None:
        name = _name_of(fn)
        with self._lock:
            self._step_names.add(name)
            if name in self.programs:
                self.programs[name].role = "step"

    def note_startup(self, phase: str, seconds: float) -> None:
        if phase not in STARTUP_PHASES:
            raise ValueError(f"unknown start-up phase {phase!r}; valid: "
                             f"{', '.join(STARTUP_PHASES)}")
        self.startup[phase] = float(seconds)
        self._out().startup.set(float(seconds), phase=phase)

    def note_kernel_trace(self, kernel: str, seconds: float) -> None:
        with self._lock:
            site = self.kernels.setdefault(kernel, KernelSite())
            site.traces += 1
            site.seconds += seconds
        out = self._out()
        out.kernel_traces.inc(1.0, kernel=kernel)
        out.kernel_seconds.inc(seconds, kernel=kernel)

    # -- jax.monitoring listeners -------------------------------------------
    # JAX emits a start scalar, a duration and a span for every jitted jnp
    # function it traces: thousands a process, most of them nested.  Those
    # take no lock and touch no metric.

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_REQUEST_EVENT:
            with self._lock:
                self.requests += 1
            self._local.hit = False
        elif event == CACHE_HIT_EVENT:
            self._local.hit = True

    def on_scalar(self, event: str, value, **_) -> None:
        # JAX records a span's start as a scalar of the same event.
        if event == TRACE_EVENT:
            local = self._local
            try:
                local.depth += 1
            except AttributeError:      # the thread's first
                local.depth, local.pending = 1, {}

    def on_duration(self, event: str, seconds: float, **_) -> None:
        # Inside the backend span of the hit it belongs to.
        if event == CACHE_SAVED_EVENT:
            self._local.saved = seconds

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_) -> None:
        local = self._local
        seconds = end - start
        hit = False
        if event == TRACE_EVENT:
            try:
                depth = local.depth = max(0, local.depth - 1)
            except AttributeError:      # opened before the ledger listened
                depth, local.depth, local.pending = 0, 0, {}
            if depth or seconds < SHORT_SPAN_S:
                # nested: its caller's time already, a trace of its program
                # and no more.  Outermost and short: its seconds too, held
                # with the count until the thread's next counted span.
                held = local.pending.get(fun_name)
                if held is None:
                    held = local.pending[fun_name] = [0, 0.0]
                held[0] += 1
                if not depth:
                    held[1] += seconds
                return
            stage = "trace"
        elif event == LOWER_EVENT:
            stage = "lower"
        elif event == BACKEND_EVENT:
            hit, local.hit = getattr(local, "hit", False), False
            stage = "cache_load" if hit else "compile"
        else:
            return
        self._fold()
        name = program_name(fun_name)
        built_s = None
        saved = 0.0
        with self._lock:
            program = self._program(name)
            setattr(program, stage + "_s",
                    getattr(program, stage + "_s") + seconds)
            self.spans.append(Span(stage, name, start, end, hit))
            if stage == "trace":
                program.traces += 1
            if stage in ("trace", "lower"):
                program.unbuilt_s += seconds
            else:
                if hit:
                    saved = getattr(local, "saved", 0.0)
                    program.saved_s += saved
                program.hits += hit
                program.misses += not hit
                built_s = program.unbuilt_s + seconds
                program.unbuilt_s = 0.0
            role, recompile = program.role, program.builds > 1
        out = self._out()
        out.seconds.inc(seconds, stage=stage, role=role)
        tracer = _trace.get_tracer()
        if tracer is not None:
            tracer.complete("compile." + stage, seconds, cat="compile",
                            args={"program": name, "role": role},
                            end_ts_us=end * 1e6)
        if built_s is not None:
            local.saved = 0.0
            self._publish_build(out, name, role, hit, saved, built_s,
                                recompile)

    def _fold(self) -> None:
        """Book what the calling thread holds: the traces counted under
        its spans, and its short outermost spans' seconds."""
        pending = getattr(self._local, "pending", None)
        if not pending:
            return
        short = {}
        with self._lock:
            for name, (traces, seconds) in pending.items():
                program = self._program(name)
                program.traces += traces
                program.trace_s += seconds
                short[program.role] = short.get(program.role, 0.0) + seconds
        pending.clear()
        for role, seconds in short.items():
            if seconds:
                self._out().seconds.inc(seconds, stage="trace", role=role)

    def _publish_build(self, out: _Outlets, name: str, role: str, hit: bool,
                       saved: float, built_s: float, recompile: bool
                       ) -> None:
        out.builds.inc(1.0, cache="hit" if hit else "miss", role=role)
        if saved > 0:
            # a counter takes no step down: a load slower than the compile
            # it stands for shows in Program.saved_s alone
            out.saved.inc(saved)
        if role != "step":
            return
        if recompile:
            out.recompiles.inc(1.0, program=name)
        from .step_stats import recovery_ledger

        goodput = recovery_ledger()
        if goodput is None:
            return
        restored = goodput.recovery_seconds("restore")
        if restored > self._restore_s:
            # the first step program after a restore: the recovery's own
            self._restore_s = restored
            goodput.charge_phase("compile", built_s)
        elif recompile:
            goodput.charge("recompile", built_s)

    # -- reading -------------------------------------------------------------
    def seconds(self, stage: Optional[str] = None,
                role: Optional[str] = None) -> float:
        """Seconds of ``stage`` (all four without) over the programs of
        ``role`` (all without)."""
        stages = STAGES if stage is None else (stage,)
        self._fold()
        with self._lock:
            return sum(getattr(p, s + "_s") for p in self.programs.values()
                       if role is None or p.role == role for s in stages)

    def builds(self, hit: Optional[bool] = None) -> int:
        """Backend builds: the cache's hits, its misses, or both."""
        with self._lock:
            return sum((p.hits if hit is not False else 0)
                       + (p.misses if hit is not True else 0)
                       for p in self.programs.values())

    def kernel_seconds(self) -> float:
        with self._lock:
            return sum(k.seconds for k in self.kernels.values())

    def kernel_traces(self) -> int:
        with self._lock:
            return sum(k.traces for k in self.kernels.values())


# ---------------------------------------------------------------------------
# The process's ledger.
# ---------------------------------------------------------------------------

_ledger = CompileLedger()
_install_lock = threading.Lock()
_installed = False


def get_ledger() -> CompileLedger:
    return _ledger


def install() -> CompileLedger:
    """Register the process's ledger with ``jax.monitoring``, once
    (``enable_compilation_cache`` and ``hvd.init()`` both call this;
    nothing compiles before them)."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax import monitoring

            monitoring.register_event_listener(_ledger.on_event)
            monitoring.register_scalar_listener(_ledger.on_scalar)
            monitoring.register_event_duration_secs_listener(
                _ledger.on_duration)
            monitoring.register_event_time_span_listener(_ledger.on_span)
            _installed = True
            import horovod_tpu

            if hasattr(horovod_tpu, "_IMPORT_SECONDS"):
                _ledger.note_startup("import", horovod_tpu._IMPORT_SECONDS)
    return _ledger


def note_step_program(fn) -> None:
    """``donated_step`` names the function it wraps: what a user's own
    step program cost apart from everything else a process compiles."""
    _ledger.note_step_program(fn)


@contextlib.contextmanager
def kernel_scope(name: str):
    """``jax.named_scope("hvdt.kernel." + name)`` around a kernel's call
    site, counted: one trace of the site and the host seconds inside the
    block (the block runs when JAX traces the call, never with the
    program).  Mosaic's lowering comes later, inside the program's
    lowering span, and stays the program's."""
    import jax

    t0 = time.perf_counter()
    try:
        with jax.named_scope("hvdt.kernel." + name):
            yield
    finally:
        _ledger.note_kernel_trace(name, time.perf_counter() - t0)
