"""The readers of the second level of scopes (PR 35: the children of
``hvdt.attention``, ``hvdt.gdn.scan`` and ``hvdt.moe.dispatch``, and
``hvdt.embed``) on a small recorded step with a matching, hand-written HLO
text (``data/scopes_trace.json``, ``data/scopes_step.hlo.txt``), and their
twelve manifest entries.  CPU only; no profiler and no device is touched.

Per step of 76 ms on one device: the embedding's gather 1; a forward
``while`` (the attention pre-norm 0.5, a q/k/v projection fusion that also
holds RoPE's multiplies 6, RoPE 3, a transposing fusion before the kernel
1, the flash forward kernel 8, a nameless copy of its output 0.5, the gate
1.5, the output projection 4; the linear mixer's beta under
``hvdt.gdn.scan`` alone 0.5, its chunk passes 5, the inverse's kernel 0.5,
the state's ``while`` of two trips of 1, ``O`` 2.5; the router 1, tokens
to rows 2, rows to tokens 3, a dense matmul that fuses a copy 9, a
nameless copy of its output 1); then the backward: the two moves'
cotangent rules 2.5 (under ``.tokens``) and 1.5 (under ``.rows``), the
output projection 3, ``delta`` 2, the flash backward kernel 5, a copy
under RoPE 0.5, the q/k/v projections 7, the embedding's scatter-add 2;
a nameless ``copy-start`` / ``copy-done`` pair in the entry computation
0.5."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import test_benchmark_manifest as accepted  # noqa: E402
from benchmark import manifest  # noqa: E402
from benchmark import phase_split as ps  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import attn_copies  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = {"attn_proj_ms": 20.0, "attn_rope_ms": 3.5, "attn_core_ms": 16.5,
           "attn_surround_ms": 3.5, "attn_gate_ms": 1.5,
           "gdn_chunk_ms": 5.5, "gdn_state_ms": 2.0, "gdn_out_ms": 2.5,
           "moe_rows_ms": 3.5, "moe_tokens_ms": 5.5, "embed_ms": 3.0,
           "attn_copies": 3}
# What the accepted readers give on the same step: the parents.
PARENTS = {"attention_ms": 42.0, "gdn_scan_ms": 10.5, "gdn_ms": 10.5,
           "moe_dispatch_ms": 10.0, "flash_fwd_ms": 8.0, "flash_bwd_ms": 5.0,
           "fwd_ms": 52.0, "bwd_ms": 23.5, "unscoped_ms": 0.5}
LM_CELLS = ["lm24x1024_s512_b128", "lm24x1024_s4096_b8",
            "lm24x1024_s512_dp4", "laguna_xs2_s8192", "qwen3_next_s16384"]
CELLS = {name: LM_CELLS for name in (
    "attn_proj_ms", "attn_rope_ms", "attn_core_ms", "attn_surround_ms",
    "embed_ms", "attn_copies")}
CELLS.update(dict.fromkeys(("attn_gate_ms", "moe_rows_ms", "moe_tokens_ms"),
                           LM_CELLS[3:]))
CELLS.update(dict.fromkeys(("gdn_chunk_ms", "gdn_state_ms", "gdn_out_ms"),
                           LM_CELLS[4:]))
LAYERS = {"gdn": "linear mixer", "moe": "expert layer"}


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


class Ctx:
    traffic, config, peaks = {}, {}, {}

    def __init__(self, trace, hlo_text):
        self.trace, self.hlo_text = trace, hlo_text


@pytest.fixture(scope="module")
def ctx():
    return Ctx(tr.trace_from_json(_read("scopes_trace.json")),
               _read("scopes_step.hlo.txt"))


def read(ctx, metric):
    return manifest.load_layer_metric(metric)(ctx)


@pytest.mark.parametrize("metric", list(READERS) + list(PARENTS))
def test_reader_on_the_recorded_step(ctx, metric):
    assert read(ctx, metric) == pytest.approx({**READERS, **PARENTS}[metric])


def test_the_surround_leaves_out_the_mosaic_events_under_core(ctx):
    kernels_ms, kernels = ps.scope_calls(ctx, "hvdt.attention.core",
                                         tr.is_mosaic)
    assert (kernels_ms, kernels) == (pytest.approx(13.0), 2)
    assert read(ctx, "attn_core_ms") - read(ctx, "attn_surround_ms") == \
        pytest.approx(kernels_ms) == pytest.approx(
            read(ctx, "flash_fwd_ms") + read(ctx, "flash_bwd_ms"))
    # the transposing fusion, the nameless copy (it takes its operand's
    # name, the kernel's) and delta: three events a step
    assert ps.scope_calls(ctx, "hvdt.attention.core",
                          lambda op: not tr.is_mosaic(op))[1] == 3


def test_the_projections_are_qkv_plus_out(ctx):
    qkv, out = (ps.scope_metric(ctx, "hvdt.attention.qkv"),
                ps.scope_metric(ctx, "hvdt.attention.out"))
    assert (qkv, out) == (pytest.approx(13.0), pytest.approx(7.0))
    assert read(ctx, "attn_proj_ms") == pytest.approx(qkv + out)
    # RoPE's multiplies fused onto the projection count as projection: a
    # fusion takes its matmul's name (its own op_name says rope).
    assert ps.has_scope(ps.op_names(ctx.hlo_text)["fusion.qkv"],
                        "hvdt.attention.qkv")
    assert "hvdt.attention.rope" in ctx.hlo_text.split(
        "%fusion.qkv = ")[1].splitlines()[0]


def test_the_copies_under_attention_are_counted_once_each(ctx):
    # inside a fusion, nameless after the kernel, bare in the backward;
    # not the one an mlp fusion holds, nor the nameless one after it, nor
    # the copy-start / copy-done pair
    assert attn_copies.relayouts(ctx.hlo_text) == [
        "transpose.7", "copy.3", "copy.8"]
    assert ctx.hlo_text.count(" copy(") == 4
    # a count of the program: no trace is needed
    assert read(Ctx(None, ctx.hlo_text), "attn_copies") == 3
    only_mlp = ctx.hlo_text.replace("hvdt.attention", "hvdt.mlp")
    assert read(Ctx(ctx.trace, only_mlp), "attn_copies") is None


def test_the_children_add_up_to_their_parents(ctx):
    """What a parent holds beside its children is the remainder PERF.md
    accounts for: the pre-norm, the mixer's beta and g."""
    attention = sum(READERS[m] for m in (
        "attn_proj_ms", "attn_rope_ms", "attn_core_ms", "attn_gate_ms"))
    assert read(ctx, "attention_ms") - attention == pytest.approx(0.5)
    scan = sum(READERS[m] for m in ("gdn_chunk_ms", "gdn_state_ms",
                                    "gdn_out_ms"))
    assert read(ctx, "gdn_scan_ms") - scan == pytest.approx(0.5)
    assert READERS["moe_rows_ms"] + READERS["moe_tokens_ms"] == \
        pytest.approx(ps.scope_metric(ctx, "hvdt.moe.dispatch"))
    # the inverse's kernel is under .chunk, the state's trips under .state
    assert ps.scope_calls(ctx, "hvdt.gdn.scan.chunk", tr.is_mosaic) == (
        pytest.approx(0.5), 1)
    assert ps.scope_calls(ctx, "hvdt.gdn.scan.state")[1] == 2


@pytest.mark.parametrize("metric", list(READERS))
def test_a_program_without_scopes_is_not_read(ctx, metric):
    stale = Ctx(ctx.trace, ctx.hlo_text.replace("hvdt.", "x."))
    assert "jvp(" in stale.hlo_text
    assert read(stale, metric) is None


@pytest.mark.parametrize("metric", [m for m in READERS if m.endswith("_ms")])
def test_no_trace_nothing_to_read(ctx, metric):
    assert read(Ctx(None, ctx.hlo_text), metric) is None


@pytest.mark.parametrize("metric", list(READERS))
def test_a_program_from_before_the_children_is_not_read(ctx, metric):
    """The parent commit's program under this PR's benchmark files: the
    first level is there, the second is not."""
    parent = ctx.hlo_text
    for child in ("qkv", "rope", "core", "gate", "out"):
        parent = parent.replace(f"hvdt.attention.{child}/", "")
    for child in ("hvdt.gdn.scan.chunk/", "hvdt.gdn.scan.state/",
                  "hvdt.gdn.scan.out/", "hvdt.moe.dispatch.rows/",
                  "hvdt.moe.dispatch.tokens/"):
        parent = parent.replace(child, "")
    parent = parent.replace("(hvdt.embed)", "()")
    before = Ctx(ctx.trace, parent)
    assert read(before, "attention_ms") == pytest.approx(42.0)
    if metric == "attn_copies":     # the first level is all it needs
        assert read(before, metric) == 3
    else:
        assert read(before, metric) is None


# ---------------------------------------------------------------------------
# The twelve manifest entries: each one entry and one reader, in the cells
# whose program has the scope.
# ---------------------------------------------------------------------------

MANIFEST = manifest.load_manifest()
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}


@pytest.mark.parametrize("name", list(READERS))
def test_a_child_metric_is_one_entry_read_where_it_says(name):
    entry = ENTRIES[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["source"]) == (
        ("instructions", "program_counter") if name == "attn_copies"
        else ("ms", "program_span"))
    assert (entry["better"], entry["moves"]) == ("lower",
                                                 "tokens_per_s_chip")
    # its parent metric's layer
    assert entry["layer"] == LAYERS.get(name.split("_")[0], "models")
    assert entry["workloads"] == CELLS[name]
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    for cell in MANIFEST["workloads"]:
        reported = manifest.load_cell(cell["name"])["layer_metrics"]
        assert (name in reported) == (cell["name"] in CELLS[name])


def test_the_twelve_are_additions_to_what_was_accepted(tmp_path):
    """Without them the tree is a benchmark that keeps to the contract, as
    it was: the twelve entries and the twelve readers are all that this PR
    brings to the metrics, and each cell reports what it did, and these
    among it in the cells listed."""
    root = accepted._copy_of_the_benchmark(tmp_path)
    bench = manifest.load_manifest(root)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in READERS]
    accepted._dump(bench, os.path.join(root, "BENCHMARK.json"))
    for name in READERS:
        os.remove(os.path.join(root, "benchmark", "layer_metrics",
                               name + ".py"))
    accepted.check_everything(root)
    accepted.check_everything(REPO)
    for cell in MANIFEST["workloads"]:
        was = manifest.load_cell(cell["name"], root=root)["layer_metrics"]
        now = manifest.load_cell(cell["name"])["layer_metrics"]
        assert accepted.in_order(was, now)
        assert sorted(set(now) - set(was)) == sorted(
            name for name in READERS if cell["name"] in CELLS[name])
    counts = {cell: sum(cell in cells for cells in CELLS.values())
              for cell in LM_CELLS}
    assert list(counts.values()) == [6, 6, 6, 9, 12]
