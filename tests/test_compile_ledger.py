"""Set-up seen from inside (PR 51): ``telemetry/compile_ledger.py`` on toy
programs, on the CPU.  What JAX traced, lowered, compiled or loaded, per
program; the role ``donated_step`` gives; the three outlets (registry,
span Tracer, recovery GoodputLedger); ``install()`` and the span list's
bound.  Counts and which stage a second is booked under: never a time."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

import horovod_tpu as hvd
from horovod_tpu import step_pipeline
from horovod_tpu.telemetry import compile_ledger as cl
from horovod_tpu.telemetry import instrument as tinst
from horovod_tpu.telemetry import metrics as tmetrics
from horovod_tpu.telemetry import step_stats
from horovod_tpu.telemetry import trace as ttrace


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Registry, recorder, tracer and recovery ledger are process-wide and
    env-gated: every test starts and ends without them."""

    def reset():
        tmetrics.reset_default_registry()
        tinst.reset()
        ttrace.reset()
        step_stats.reset_recovery_ledger()

    for var in ("HVDT_TELEMETRY", "HVDT_TRACE_DIR", "HVDT_RANK"):
        monkeypatch.delenv(var, raising=False)
    reset()
    yield
    reset()


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    """The persistent cache at a directory of the test's own, every
    compile cached; JAX's settings and its cache object put back after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(step_pipeline, "_engaged", None)
    cc.reset_cache()
    yield step_pipeline.enable_compilation_cache(
        str(tmp_path / "xla"), min_compile_secs=0.0)
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def counter(name, **labels):
    return tmetrics.default_registry().counter(name).value(**labels)


def state():
    """Params and optimizer state of the toy steps (donated: new a call)."""
    return jnp.ones(()), jnp.zeros(())


def slow_to_trace():
    """In a toy function's body: its trace span is no short one."""
    time.sleep(2 * cl.SHORT_SPAN_S)


# ---------------------------------------------------------------------------
# A real program through JAX's own events.
# ---------------------------------------------------------------------------


def test_a_step_program_misses_then_loads_and_the_second_is_a_recompile(
        cache):
    def ledger_toy_step(p, s, x):
        slow_to_trace()
        return p + jnp.sin(x).sum(), s, (p * x).sum()

    ledger = cl.get_ledger()
    step = hvd.donated_step(ledger_toy_step)
    step(*state(), jnp.ones(4))
    program = ledger.programs["ledger_toy_step"]
    assert program.role == "step"
    assert (program.misses, program.hits, program.recompiles) == (1, 0, 0)
    assert program.compile_s > 0 and program.cache_load_s == 0
    assert program.trace_s > 0 and program.lower_s > 0
    assert counter("hvdt_compiles_total", cache="miss", role="step") == 1
    assert counter("hvdt_compiles_total", cache="hit", role="step") == 0
    assert counter("hvdt_recompiles_total", program="ledger_toy_step") == 0
    compiled_s = program.compile_s

    jax.clear_caches()
    step(*state(), jnp.ones(4))
    assert (program.misses, program.hits, program.recompiles) == (1, 1, 1)
    assert program.cache_load_s > 0 and program.compile_s == compiled_s
    assert program.traces == 2
    assert counter("hvdt_compiles_total", cache="hit", role="step") == 1
    assert counter("hvdt_recompiles_total", program="ledger_toy_step") == 1
    for stage in cl.STAGES:
        assert counter("hvdt_compile_seconds_total", stage=stage,
                       role="step") == pytest.approx(
                           getattr(program, stage + "_s"))
    assert ledger.seconds(role="step") >= program.seconds
    last = [s for s in ledger.spans if s.program == "ledger_toy_step"][-3:]
    assert [(s.stage, s.hit) for s in last] == [
        ("trace", False), ("lower", False), ("cache_load", True)]
    assert last[0].end <= last[1].start <= last[1].end <= last[2].start


def test_a_jitted_function_inside_another_is_its_callers_time():
    @jax.jit
    def ledger_inner(x):
        return jnp.sin(x) * 2

    def ledger_outer(x):
        slow_to_trace()
        return ledger_inner(x) + jnp.cos(x)

    ledger = cl.get_ledger()
    x = jnp.ones(8)                     # its own small programs: before
    before = ledger.seconds("trace")
    step_before = ledger.seconds("trace", role="step")
    jax.jit(ledger_outer)(x)
    outer, inner = (ledger.programs[n]
                    for n in ("ledger_outer", "ledger_inner"))
    assert (outer.traces, inner.traces) == (1, 1)       # both are programs
    assert inner.trace_s == 0 and inner.builds == 0     # counted once:
    assert outer.trace_s > 0 and outer.builds == 1      # by the outermost
    assert ledger.seconds("trace") - before == pytest.approx(outer.trace_s)
    spans = [s for s in ledger.spans if s.stage == "trace"
             and s.program in ("ledger_outer", "ledger_inner")]
    assert [s.program for s in spans] == ["ledger_outer"]
    # plain jax.jit is not donated_step: role "step" is only what that built
    assert (outer.role, inner.role) == ("other", "other")
    assert ledger.seconds("trace", role="step") == step_before


def test_the_tracer_gets_one_event_a_stage_with_the_spans_own_start(
        monkeypatch, tmp_path):
    def ledger_traced_step(p, s, x):
        slow_to_trace()
        return p + jnp.tanh(x).sum(), s, p

    ledger = cl.get_ledger()
    assert ttrace.get_tracer() is None      # off: counted, no event anywhere
    hvd.donated_step(ledger_traced_step)(*state(), jnp.ones(2))
    assert ledger.programs["ledger_traced_step"].builds == 1

    monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
    hvd.donated_step(ledger_traced_step)(*state(), jnp.ones(3))
    events = [e for e in ttrace.get_tracer().events()
              if e["cat"] == "compile"
              and e["args"]["program"] == "ledger_traced_step"]
    assert [e["name"] for e in events] == [
        "compile.trace", "compile.lower", "compile.compile"]
    spans = [s for s in ledger.spans
             if s.program == "ledger_traced_step"][-3:]
    for event, span in zip(events, spans):
        assert event["ph"] == "X" and event["args"]["role"] == "step"
        assert event["ts"] == pytest.approx(span.start * 1e6, abs=1.0)
        assert event["dur"] == pytest.approx(
            (span.end - span.start) * 1e6, abs=1.0)
    # the nested jnp functions' spans made no event of their own
    assert not [e for e in ttrace.get_tracer().events()
                if e["cat"] == "compile" and e["args"]["program"] == "tanh"]


def test_install_twice_registers_one_set_of_listeners():
    ledger = cl.install()
    assert cl.install() is ledger is cl.get_ledger()
    for listeners, method in (
            (jax_monitoring.get_event_listeners(), ledger.on_event),
            (jax_monitoring.get_scalar_listeners(), ledger.on_scalar),
            (jax_monitoring.get_event_duration_listeners(),
             ledger.on_duration),
            (jax_monitoring.get_event_time_span_listeners(),
             ledger.on_span)):
        assert listeners.count(method) == 1
    assert ledger.startup["import"] == hvd._IMPORT_SECONDS > 0


def test_init_times_itself_and_the_backend_apart():
    hvd.init()
    startup = cl.get_ledger().startup
    assert set(startup) == {"import", "init", "backend"}
    assert all(v >= 0 for v in startup.values())
    gauge = tmetrics.default_registry().gauge("hvdt_startup_seconds")
    cl.get_ledger().note_startup("backend", 2.5)
    assert gauge.value(phase="backend") == 2.5
    cl.get_ledger().note_startup("backend", startup["backend"])
    with pytest.raises(ValueError, match="unknown start-up phase"):
        cl.get_ledger().note_startup("warm-up", 1.0)


# ---------------------------------------------------------------------------
# A ledger of the test's own, fed by hand.
# ---------------------------------------------------------------------------


def feed(ledger, program, *, trace=(), lower=None, backend=None, hit=False,
         saved=None):
    """One program's way to the device as JAX reports it: each trace span
    (start, end, [callee spans]) opens, its callees open and close, it
    closes; then lowering; then the cache's events inside the backend
    span."""

    def traced(name, start, end, callees=()):
        ledger.on_scalar(cl.TRACE_EVENT, start, fun_name=name)
        for callee in callees:
            traced(*callee)
        ledger.on_span(cl.TRACE_EVENT, start, end, fun_name=name)

    for span in trace:
        traced(program, *span)
    if lower:
        ledger.on_span(cl.LOWER_EVENT, *lower, fun_name=f"jit({program})")
    if backend:
        ledger.on_event(cl.CACHE_REQUEST_EVENT)
        if hit:
            ledger.on_event(cl.CACHE_HIT_EVENT)
            ledger.on_duration(cl.CACHE_SAVED_EVENT, saved)
        ledger.on_span(cl.BACKEND_EVENT, *backend,
                       fun_name=f"jit({program})")


def test_spans_fed_by_hand_land_under_their_stage_role_and_program():
    ledger = cl.CompileLedger()

    def train_step():
        pass

    ledger.note_step_program(train_step)
    feed(ledger, "train_step",
         trace=[(10.0, 14.0, [("sin", 11.0, 11.5),
                              ("helper", 12.0, 13.0,
                               [("cos", 12.25, 12.5)])])],
         lower=(14.0, 15.5), backend=(15.5, 20.5))
    feed(ledger, "init", trace=[(21.0, 21.5)], lower=(21.5, 22.0),
         backend=(22.0, 22.25), hit=True, saved=3.0)
    assert ledger.seconds("trace") == 4.5           # 4 + 0.5: no callee
    assert ledger.seconds("trace", role="step") == 4.0
    assert ledger.seconds("lower") == 2.0
    assert ledger.seconds("compile") == 5.0
    assert ledger.seconds("cache_load") == 0.25
    assert ledger.seconds(role="step") == 10.5
    assert ledger.seconds() == 11.75
    assert (ledger.builds(), ledger.builds(hit=True),
            ledger.builds(hit=False), ledger.requests) == (2, 1, 1, 2)
    assert ledger.programs["init"].saved_s == 3.0
    assert ledger.programs["helper"].traces == 1
    assert ledger.programs["helper"].seconds == 0
    assert counter("hvdt_compile_cache_saved_seconds_total") == 3.0
    assert counter("hvdt_compile_seconds_total", stage="trace",
                   role="other") == 0.5
    assert [(s.stage, s.program) for s in ledger.spans] == [
        ("trace", "train_step"), ("lower", "train_step"),
        ("compile", "train_step"), ("trace", "init"), ("lower", "init"),
        ("cache_load", "init")]


def test_a_span_is_nested_only_under_a_span_of_its_own_thread():
    ledger = cl.CompileLedger()
    ledger.on_scalar(cl.TRACE_EVENT, 1.0, fun_name="slow")     # open here
    other = threading.Thread(
        target=feed, args=(ledger, "elsewhere"),
        kwargs={"trace": [(2.0, 3.0)]})
    other.start()
    other.join()
    ledger.on_span(cl.TRACE_EVENT, 1.0, 5.0, fun_name="slow")
    assert ledger.programs["elsewhere"].trace_s == 1.0
    assert ledger.programs["slow"].trace_s == 4.0
    # a span whose opening the ledger never saw is taken as outermost
    ledger.on_span(cl.TRACE_EVENT, 6.0, 6.5, fun_name="late")
    assert ledger.programs["late"].trace_s == 0.5


def test_a_short_outermost_span_is_seconds_and_no_span_of_its_own(
        monkeypatch, tmp_path):
    """``jax.eval_shape`` of an initialiser traces thousands of ``jnp``
    functions outside any span: each is counted and its seconds kept, with
    no lock, metric, span or Tracer event of its own."""
    monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
    ledger = cl.CompileLedger()
    for i in range(3):
        feed(ledger, "add", trace=[(i, i + 0.25 * cl.SHORT_SPAN_S)])
    assert not ledger.programs and not ledger.spans     # held by the thread
    assert not ttrace.get_tracer().events()
    assert ledger.seconds("trace") == pytest.approx(0.75 * cl.SHORT_SPAN_S)
    assert ledger.programs["add"].traces == 3           # booked at a read
    feed(ledger, "add", trace=[(5.0, 5.0 + 0.5 * cl.SHORT_SPAN_S)])
    feed(ledger, "f", trace=[(6.0, 7.0)])               # and at a counted span
    assert ledger.programs["add"].traces == 4
    assert ledger.programs["add"].trace_s == pytest.approx(
        1.25 * cl.SHORT_SPAN_S)
    assert [s.program for s in ledger.spans] == ["f"]
    assert [e["args"]["program"] for e in ttrace.get_tracer().events()] == [
        "f"]
    assert counter("hvdt_compile_seconds_total", stage="trace",
                   role="other") == pytest.approx(
                       1.0 + 1.25 * cl.SHORT_SPAN_S)


def test_a_recompile_charges_goodput_and_a_restore_makes_it_recovery(
        monkeypatch):
    ledger = cl.CompileLedger()

    def train_step():
        pass

    ledger.note_step_program(train_step)
    feed(ledger, "train_step", trace=[(0.0, 1.0)], lower=(1.0, 2.0),
         backend=(2.0, 4.0))
    assert step_stats.recovery_ledger() is None     # telemetry off: counted,
    feed(ledger, "train_step", backend=(4.0, 5.0))  # charged nowhere
    assert counter("hvdt_recompiles_total", program="train_step") == 1

    monkeypatch.setenv("HVDT_TELEMETRY", "1")
    goodput = step_stats.recovery_ledger()
    feed(ledger, "train_step", trace=[(10.0, 11.0)], lower=(11.0, 11.5),
         backend=(11.5, 13.5), hit=True, saved=0.0)
    assert goodput.lost_seconds("recompile") == 3.5
    feed(ledger, "other_program", trace=[(14.0, 15.0)], backend=(15.0, 16.0))
    feed(ledger, "other_program", backend=(16.0, 17.0))
    assert goodput.lost_seconds() == 3.5            # step programs only

    goodput.charge_phase("restore", 0.75)
    feed(ledger, "train_step", trace=[(20.0, 22.0)], lower=(22.0, 23.0),
         backend=(23.0, 27.0))
    assert goodput.recovery_seconds("compile") == 7.0
    assert goodput.lost_seconds("recompile") == 3.5
    feed(ledger, "train_step", backend=(30.0, 30.5))
    assert goodput.lost_seconds("recompile") == 4.0
    assert goodput.recovery_seconds("compile") == 7.0


def test_the_span_list_stops_at_its_bound():
    ledger = cl.CompileLedger()
    for i in range(cl.SPAN_BOUND + 10):
        ledger.on_span(cl.LOWER_EVENT, float(i), i + 0.5, fun_name="jit(f)")
    assert len(ledger.spans) == cl.SPAN_BOUND == 4096
    assert ledger.spans[-1].start == cl.SPAN_BOUND + 9
    assert ledger.seconds("lower") == 0.5 * (cl.SPAN_BOUND + 10)


def test_kernel_scope_is_the_named_scope_and_counts_the_block():
    ledger = cl.get_ledger()
    site = ledger.kernels.setdefault("ledger_toy", cl.KernelSite())
    before = site.traces

    def body(x):
        with cl.kernel_scope("ledger_toy"):
            return x * 2

    text = jax.jit(body).lower(jnp.ones(4)).as_text(debug_info=True)
    assert '"hvdt.kernel.ledger_toy"' in text or \
        "hvdt.kernel.ledger_toy/" in text
    assert site.traces == before + 1 and site.seconds > 0
    assert counter("hvdt_kernel_traces_total", kernel="ledger_toy") == 1
    assert counter("hvdt_kernel_trace_seconds_total",
                   kernel="ledger_toy") == pytest.approx(site.seconds)
    with pytest.raises(RuntimeError), cl.kernel_scope("ledger_toy"):
        raise RuntimeError("a block that raises is still counted")
    assert site.traces == before + 2
    assert ledger.kernel_traces() >= 2
    assert ledger.kernel_seconds() >= site.seconds
