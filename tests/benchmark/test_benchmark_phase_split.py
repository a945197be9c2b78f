"""The phase split (``benchmark/phase_split.py``) and the readers over it
(the six phases, two model scopes, the two flash kernels with their
roofline shares), on a second small recorded trace with a matching,
hand-written HLO text (``data/phase_trace.json``,
``data/phase_step.hlo.txt``).  CPU only; no profiler and no device is
touched.

Per step of 100 ms on device 0 (device 1 runs the same step in 90): a
forward ``while`` (attention matmul 12, flash forward kernel 8, MLP matmul
10), the loss forward 5, a backward ``while`` (attention recompute 10, the
forward kernel's recompute 8, the flash backward kernel 5, an attention
weight-gradient fusion with no name of its own 7, a nameless copy 3, MLP
backward 8, attention backward 4), the
loss backward 4, the exchange (packing 2, the gradients' all-reduce 6,
the loss's all-reduce 1), an optimizer fusion whose root is the caller's
``apply_updates`` add 4, a bare ``apply_updates`` fusion 1, a nameless
copy in the entry computation 1, idle 1."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark import layer_metrics as lm  # noqa: E402
from benchmark import phase_split as ps  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
# One forward call of the fixture's shape takes 1.6 ms at the fixture's
# peak, one backward call (2.5 x the operations) 4: two forward calls a
# step in 16 ms are at 20% of their roofline, one backward in 5 at 80%.
TRAFFIC = {"seq": 64, "per_chip_batch": 1}
CONFIG = {"heads": 4, "d_model": 64}
PEAKS = {"bf16_flops_per_s": 2 * 4 * 64 * 64 * 16 / 1.6e-3,
         "hbm_bytes_per_s": 1e12}
READERS = {"fwd_ms": 35.0, "remat_ms": 18.0, "bwd_ms": 31.0,
           "optimizer_ms": 4.0, "exchange_ms": 9.0, "unscoped_ms": 2.0,
           "attention_ms": 57.0, "loss_ms": 9.0,
           "flash_fwd_ms": 16.0, "flash_fwd_roofline": 20.0,
           "flash_bwd_ms": 5.0, "flash_bwd_roofline": 80.0}
PHASE_READERS = ["fwd_ms", "remat_ms", "bwd_ms", "optimizer_ms",
                 "exchange_ms", "unscoped_ms"]


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


class Ctx:
    traffic, config, peaks = TRAFFIC, CONFIG, PEAKS

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
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/"
     "hvdt.attention/hvdt.kernel.flash_bwd/pallas_call",
     ("custom-call", "tpu_custom_call"), "backward"),
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
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/hvdt.attention/"
     "hvdt.kernel.flash_bwd/pallas_call", "hvdt.kernel.flash_bwd", True),
    (J + "transpose(jvp())/while/body/closed_call/checkpoint/hvdt.attention/"
     "hvdt.kernel.flash_bwd/pallas_call", "hvdt.kernel.flash_fwd", False),
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
    "fwd_ms.images", "bwd_ms.images", "unscoped_ms.images"])
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


def test_the_kernels_are_told_apart_by_name_not_by_being_mosaic(ctx):
    # Every Mosaic event of the step is one of the two kernels; each
    # reader counts its own calls (PR 27 to 29: flash_fwd_ms counted all).
    every_ms, every = lm.per_step(ctx, tr.is_mosaic)
    fwd_ms, fwd = ps.scope_calls(ctx, "hvdt.kernel.flash_fwd", tr.is_mosaic)
    bwd_ms, bwd = ps.scope_calls(ctx, "hvdt.kernel.flash_bwd", tr.is_mosaic)
    assert (fwd, bwd, every) == (2, 1, 3)
    assert fwd_ms + bwd_ms == pytest.approx(every_ms) == pytest.approx(21.0)
    assert ps.scope_calls(ctx, "hvdt.kernel.flash_dq",
                          tr.is_mosaic) == (None, None)
    # Without the predicate a scope gives everything under it.
    assert ps.scope_calls(ctx, "hvdt.loss") == (pytest.approx(9.0), 2)


def test_a_kernels_roofline_share_is_its_calls_least_time_over_its_own(ctx):
    ops, nbytes = lm.flash_bwd_call_cost(1, 64, 4, 16)
    least, bound = lm.roofline(ops, nbytes, PEAKS)
    assert (bound, least) == ("compute", pytest.approx(4e-3))
    assert lm.flash_roofline_pct(ctx, 5.0, 1, lm.flash_bwd_call_cost) == \
        pytest.approx(80.0)
    assert lm.flash_roofline_pct(ctx, None, None,
                                 lm.flash_bwd_call_cost) is None
    images = Ctx(ctx.trace, ctx.hlo_text)
    images.traffic = {"per_chip_batch": 128}       # no sequence: no share
    assert lm.flash_roofline_pct(images, 5.0, 1,
                                 lm.flash_fwd_call_cost) is None


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
    assert ps.scope_per_step(dev, ps.op_names(ctx.hlo_text),
                             "hvdt.kernel.flash_dq") == (None, None)
    assert ps.scope_per_step(dev, ps.op_names(ctx.hlo_text),
                             "hvdt.loss") == (pytest.approx(9.0), 2)


# ---------------------------------------------------------------------------
# The manifest's entries that read the program's own names: the ones PR 30
# wrote in are looked for by name, among whatever a later PR appends.
# ---------------------------------------------------------------------------

MANIFEST = manifest.load_manifest()
SPANS = {m["name"]: m for m in MANIFEST["per_layer"]
         if m["source"] == "program_span"}
SPLIT = ["fwd_ms", "fwd_ms.images", "remat_ms", "bwd_ms", "bwd_ms.images",
         "attention_ms", "loss_ms", "optimizer_ms", "exchange_ms",
         "unscoped_ms", "unscoped_ms.images"]
KERNELS = ["flash_fwd_ms", "flash_fwd_roofline", "flash_bwd_ms",
           "flash_bwd_roofline"]


def test_the_phase_split_is_in_the_manifest():
    # Every metric that picks trace events by a name the program gives is
    # marked program_span, the kernels by scope too.
    assert set(SPLIT + KERNELS) <= set(SPANS)
    assert not {"flash_fwd_named_ms", "optimizer_ms.images"} & set(SPANS)


@pytest.mark.parametrize("name", SPLIT + KERNELS)
def test_a_phase_metric_entry_is_read_where_it_says(name):
    entry = SPANS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    if name in SPLIT:
        assert (entry["unit"], entry["better"]) == ("ms", "lower")
        assert entry["layer"] in ("models", "optimizer + exchange")
    else:
        assert entry["layer"] == "kernels"
        assert (entry["unit"], entry["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms", "lower"))
    assert name.split(".")[0] in READERS
    assert callable(manifest.load_layer_metric(name))
    assert entry["workloads"]
    for cell in entry["workloads"]:
        loaded = manifest.load_cell(cell)
        assert name in loaded["layer_metrics"]
        # A per-layer metric is reported only where the metric it moves is.
        assert entry["moves"] in loaded["end_to_end"]


@pytest.mark.parametrize("cell, phases", [
    ("lm24x1024_s512_b128", ["fwd_ms", "remat_ms", "bwd_ms", "optimizer_ms",
                             "unscoped_ms"]),
    ("lm24x1024_s4096_b8", ["fwd_ms", "remat_ms", "bwd_ms", "optimizer_ms",
                            "unscoped_ms"]),
    ("lm24x1024_s512_dp4", ["fwd_ms", "remat_ms", "bwd_ms", "optimizer_ms",
                            "exchange_ms", "unscoped_ms"]),
    ("resnet50_train", ["fwd_ms.images", "bwd_ms.images",
                        "unscoped_ms.images"])])
def test_a_cell_reports_the_phases_its_step_has(cell, phases):
    """The phases a cell leaves out read 0 or next to it there (no
    exchange on one chip; no remat in ResNet, and its SGD update fused
    into the weight-gradient convolutions)."""
    assert all(cell in SPANS[phase]["workloads"] for phase in phases)
    assert {p.split(".")[0] for p in phases} <= set(PHASE_READERS)


def test_the_report_is_run_pys_traced_run(monkeypatch):
    # benchmark/phase_report.py stays, as this and no more, until the
    # operator's notes that name it may be corrected.
    from benchmark import phase_report, run
    seen = []
    monkeypatch.setattr(run, "main", lambda argv: seen.append(argv) or 0)
    asked = ["--workload", "resnet50_train", "--seed", "1", "--seconds", "26"]
    assert phase_report.main(asked) == 0
    assert seen == [asked + ["--trace", "1"]]
