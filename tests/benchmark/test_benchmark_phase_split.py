"""The phase split (``benchmark/phase_split.py``) and the nine readers
over it, on a second small recorded trace with a matching, hand-written
HLO text (``data/phase_trace.json``, ``data/phase_step.hlo.txt``).  CPU
only; no profiler and no device is touched.

Per step of 100 ms on device 0 (device 1 runs the same step in 90): a
forward ``while`` (attention matmul 12, flash kernel 8, MLP matmul 10),
the loss forward 5, a backward ``while`` (attention recompute 10, the
kernel's recompute 8, an attention weight-gradient fusion with no name of
its own 12, a nameless copy 3, MLP backward 8, attention backward 4), the
loss backward 4, the exchange (packing 2, the gradients' all-reduce 6,
the loss's all-reduce 1), an optimizer fusion whose root is the caller's
``apply_updates`` add 4, a bare ``apply_updates`` fusion 1, a nameless
copy in the entry computation 1, idle 1."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, phase_report  # noqa: E402
from benchmark import phase_split as ps  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
READERS = {"fwd_ms": 35.0, "remat_ms": 18.0, "bwd_ms": 31.0,
           "optimizer_ms": 4.0, "exchange_ms": 9.0, "unscoped_ms": 2.0,
           "attention_ms": 57.0, "loss_ms": 9.0, "flash_fwd_named_ms": 16.0}
PHASE_READERS = ["fwd_ms", "remat_ms", "bwd_ms", "optimizer_ms",
                 "exchange_ms", "unscoped_ms"]


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


class Ctx:
    def __init__(self, trace, hlo_text):
        self.trace, self.hlo_text = trace, hlo_text


@pytest.fixture(scope="module")
def ctx():
    return Ctx(tr.trace_from_json(_read("phase_trace.json")),
               _read("phase_step.hlo.txt"))


@pytest.fixture(scope="module")
def names(ctx):
    return ps.op_names(ctx.hlo_text)


J = "jit(local_step)/"
S = "jit(local_step)/shard_map/"
FUSION, ALL_REDUCE = ("fusion", "kOutput"), ("all-reduce", "")


# op_name strings as the CPU (toy size) and the described v5e (full size,
# benchmark/rehearse.py's compile) print them.
@pytest.mark.parametrize("op_name, kind, phase", [
    (J + "jvp()/while/body/closed_call/hvdt.attention/dot_general",
     FUSION, "forward"),
    (S + "jvp(hvdt.loss)/while/body/closed_call/reduce_max", FUSION,
     "forward"),
    (J + "jvp()/conv_general_dilated", FUSION, "forward"),
    # Built once outside differentiation: no wrapper, a model scope.
    (S + "hvdt.attention/jit(tril)/ge", FUSION, "forward"),
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/hvdt.attention/hvdt.kernel.flash_fwd/pallas_call",
     ("custom-call", "tpu_custom_call"), "remat"),
    (S + "transpose(jvp(hvdt.loss))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", FUSION, "remat"),
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/hvdt.mlp/"
     "dot_general", FUSION, "backward"),
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/"
     "hvdt.attention/while/body/closed_call", ("copy", ""), "backward"),
    (S + "transpose(jvp())/while/body/dynamic_update_slice", FUSION,
     "backward"),
    (J + "transpose(jvp())/conv_general_dilated", FUSION, "backward"),
    (S + "hvdt.optimizer/mul", FUSION, "optimizer"),
    (J + "hvdt.optimizer/jit(_where)/select_n", FUSION, "optimizer"),
    (S + "hvdt.exchange/hvdt.fused_allreduce.b4/psum_invariant",
     ALL_REDUCE, "exchange"),
    (S + "hvdt.exchange/concatenate", FUSION, "exchange"),
    (S + "hvdt.exchange/hvdt.fused_allreduce.b0/div", FUSION, "exchange"),
    # The loss's pmean is the caller's, and a collective all the same.
    (S + "psum_invariant", ALL_REDUCE, "exchange"),
    ("", ("all-gather-start", ""), "exchange"),
    (J + "add", FUSION, "unscoped"),
    (S + "broadcast.54", ("broadcast", ""), "unscoped"),
    ("", ("copy-done", ""), "unscoped"),
    # Not a scope: part of a longer segment.
    (J + "my_hvdt.optimizer_test/mul", FUSION, "unscoped"),
])
def test_phase_of_an_op_name(op_name, kind, phase):
    assert ps.phase(tr.Op("x.1", 0.0, 1.0, *kind), op_name) == phase


@pytest.mark.parametrize("op_name, scope, there", [
    (S + "jvp(hvdt.loss)/reduce_sum", "hvdt.loss", True),
    (S + "transpose(jvp(hvdt.loss))/while", "hvdt.loss", True),
    (J + "jvp()/while/body/closed_call/hvdt.attention/mul",
     "hvdt.attention", True),
    ("hvdt.exchange/hvdt.fused_allreduce.b0/psum_invariant",
     "hvdt.exchange", True),
    (S + "hvdt.exchange/hvdt.fused_allreduce.b0/div", "hvdt.fused_allreduce",
     False),
    (S + "hvdt.attention_v2/mul", "hvdt.attention", False),
    ("", "hvdt.loss", False),
])
def test_a_scope_is_a_whole_segment_of_the_path(op_name, scope, there):
    assert ps.has_scope(op_name, scope) is there


@pytest.mark.parametrize("instruction, ends, why", [
    ("fusion.10", "jvp()/while/body/closed_call/hvdt.attention/dot_general",
     "its own and its matmul's"),
    ("fusion.31", "checkpoint/hvdt.attention/dot_general",
     "no name of its own: the matmul it fuses, not the update around it"),
    ("fusion.60", "hvdt.optimizer/mul",
     "the commonest phase inside beats the root's apply_updates add"),
    ("fusion.61", "shard_map/add", "nothing named inside: its own"),
    ("copy.5", "checkpoint/hvdt.attention/dot_general",
     "nameless in a loop body: the body's commonest phase and scope"),
    ("while.2", "checkpoint/hvdt.attention/dot_general",
     "a nameless while: its body's"),
    ("copy-start.1", "", "nameless in the entry computation"),
    ("copy-done.1", "", "its operand is nameless too"),
    ("params", "", "a parameter's op_name is an argument's name"),
])
def test_where_an_instruction_gets_its_op_name(names, instruction, ends,
                                               why):
    assert names[instruction].endswith(ends) and bool(
        names[instruction]) == bool(ends), why


@pytest.mark.parametrize("metric", list(READERS) + [
    "fwd_ms.images", "bwd_ms.images", "optimizer_ms.images",
    "unscoped_ms.images"])
def test_reader_on_the_recorded_trace(ctx, metric):
    # The slowest device's reading (device 1 would give nine tenths).
    got = manifest.load_layer_metric(metric)(ctx)
    assert got == pytest.approx(READERS[metric.split(".")[0]])


@pytest.mark.parametrize("device", [DEV0, DEV1])
def test_the_six_phases_partition_the_leaf_time(ctx, names, device):
    dev = ctx.trace.devices[device]
    split = ps.split(dev, names)
    assert tuple(split) == ps.PHASES
    leaf_ms = 1e3 * sum(o.seconds for o in dev.leaves) / 2
    assert sum(split.values()) == pytest.approx(leaf_ms, rel=1e-9)
    assert leaf_ms == pytest.approx(99.0 if device == DEV0 else 89.1)


def test_the_phase_readers_sum_to_the_slowest_devices_leaf_time(ctx):
    total = sum(manifest.load_layer_metric(m)(ctx) for m in PHASE_READERS)
    assert total == pytest.approx(99.0)


def test_the_named_kernel_reader_agrees_with_the_unnamed_one(ctx):
    # flash_fwd_ms counts every Mosaic call; here the flash forward is
    # the only one, so both read the same events.
    assert manifest.load_layer_metric("flash_fwd_named_ms")(ctx) == \
        pytest.approx(manifest.load_layer_metric("flash_fwd_ms")(ctx))


@pytest.mark.parametrize("metric", list(READERS))
def test_a_program_without_scopes_is_not_read(ctx, metric):
    """A step program served by a compilation cache from before the
    scopes (the cache key ignores metadata) must not be reported as one
    long unscoped phase."""
    stale = Ctx(ctx.trace, ctx.hlo_text.replace("hvdt.", "x."))
    assert "jvp(" in stale.hlo_text
    assert manifest.load_layer_metric(metric)(stale) is None


@pytest.mark.parametrize("metric", list(READERS))
def test_no_trace_nothing_to_read(ctx, metric):
    assert manifest.load_layer_metric(metric)(
        Ctx(None, ctx.hlo_text)) is None


def test_a_scope_with_no_event_under_it_reads_none(ctx):
    dev = ctx.trace.devices[DEV0]
    assert ps.scope_ms(dev, ps.op_names(ctx.hlo_text),
                       "hvdt.kernel.flash_dq") is None


# ---------------------------------------------------------------------------
# The entries a `benchmark` PR appends to BENCHMARK.json, and the report
# that reads them until then.
# ---------------------------------------------------------------------------

MANIFEST = manifest.load_manifest()
with open(os.path.join(REPO, "benchmark", "phase_metrics.json")) as f:
    ENTRIES = json.load(f)["per_layer"]


@pytest.mark.parametrize("entry", ENTRIES, ids=[e["name"] for e in ENTRIES])
def test_a_phase_metric_entry_is_ready_for_the_manifest(entry):
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", entry["name"])
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "ms", "lower", "program_span")
    assert entry["layer"] in {m["layer"] for m in MANIFEST["per_layer"]}
    assert entry["name"] not in {m["name"] for m in MANIFEST["per_layer"]}
    assert callable(manifest.load_layer_metric(entry["name"]))
    assert entry["workloads"]
    for cell in entry["workloads"]:
        # A per-layer metric is reported only where the metric it moves is.
        assert entry["moves"] in manifest.load_cell(cell)["end_to_end"]


@pytest.mark.parametrize("cell, count", [
    ("lm24x1024_s512_b128", 7), ("lm24x1024_s4096_b8", 8),
    ("lm24x1024_s512_dp4", 8), ("resnet50_train", 4)])
def test_the_report_adds_a_cells_phase_metrics_to_its_list(cell, count):
    before = manifest.load_cell(cell)
    after = phase_report.with_phase_metrics(before)
    added = after["layer_metrics"][len(before["layer_metrics"]):]
    assert after["layer_metrics"][:len(before["layer_metrics"])] == \
        before["layer_metrics"]
    assert len(added) == count == len(set(added))
    assert all(after["units"][m] == "ms" for m in added)
    assert before["layer_metrics"] == manifest.load_cell(
        cell)["layer_metrics"]          # the loaded cell is not edited


def test_the_report_prints_no_result_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "phase_report.py"),
         "--workload", "resnet50_train", "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 1
    assert "no TPU" in r.stderr and "{" not in r.stdout
