"""``moe_sum_rows_calls`` (PR 40): the counter that the experts' rows go
back to their tokens by the Mosaic call ``hvdt.kernel.moe_sum_rows``; its
manifest entry, its reader on a recorded step, and the accepted readers of
the two moves, which keep reading the call's time."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, manifest  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "moe_sum_rows_calls"
CELLS = ["laguna_xs2_s8192", "qwen3_next_s16384", "sdar_30b_s8192"]


def _ctx(cell, trace=None, hlo_text="ENTRY %main () -> f32[] {\n}"):
    cell = manifest.load_cell(cell)
    return harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text=hlo_text, memory=None, setup_compile_s=0.0,
        throughput=1.0, trace=trace)


def test_the_manifest_gives_the_counter_to_the_three_sparse_cells():
    bench = manifest.load_manifest()
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "calls", "better": "lower",
        "source": "program_span", "layer": "expert layer",
        "moves": "tokens_per_s_chip", "workloads": CELLS}
    for cell in bench["workloads"]:
        listed = NAME in manifest.load_cell(cell["name"])["layer_metrics"]
        assert listed == (cell["name"] in CELLS), cell["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_readers_on_a_recorded_step(cell):
    """Two steps of 13.5 ms: the tokens' gather into the buffer 1 ms, the
    grouped products 3, the kernel's tables 0.5 and its call 1.5 under
    ``hvdt.moe.dispatch.tokens``; in the backward the other gather 1, the
    products 5, the call 1.5 under ``hvdt.moe.dispatch.rows``."""
    with open(os.path.join(DATA, "moe_rows_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "moe_rows_step.hlo.txt")) as f:
        ctx = _ctx(cell, trace, f.read())
    read = lambda name: manifest.load_layer_metric(name)(ctx)  # noqa: E731
    assert read(NAME) == 2
    # the accepted readers keep reading the call: each move is its gather
    # and the other's transpose, now the kernel
    assert read("moe_rows_ms") == pytest.approx(2.5)
    assert read("moe_tokens_ms") == pytest.approx(3.0)
    assert read("moe_dispatch_ms") == pytest.approx(5.5)
    assert read("unscoped_ms") == pytest.approx(8.0)


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_finds_nothing_on_a_program_without_the_call(cell):
    """The parent's program, and a run without a trace: None, no error."""
    reader = manifest.load_layer_metric(NAME)
    assert reader(_ctx(cell)) is None
    with open(os.path.join(DATA, "bd_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "bd_step.hlo.txt")) as f:
        assert reader(_ctx(cell, trace, f.read())) is None
