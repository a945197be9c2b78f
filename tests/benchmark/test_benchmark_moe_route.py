"""``moe_route_ms`` and ``moe_route_sorts`` (PR 42): the reader of the
expert layer's route, which ``moe_dispatch_ms`` held unread beside the two
moves, and the counter of the sorts and top-ks under ``hvdt.moe.route`` in
the compiled step: both on a recorded step with the accepted readers beside
them, and the two manifest entries rehearsed on a copy of the benchmark.
``BENCHMARK.json`` does not list them yet: an accepted test pins the last
five names of ``per_layer`` (``test_benchmark_evabyte.py``), so appending is
the next ``benchmark`` PR's (PERF.md section 7, B0).

The recorded step (two of 12 ms on one device) is a layer whose checkpoint
recomputes the route, the program before PR 42: the router's product 1 ms,
the top-k 2 (XLA:TPU's ``sort`` of the whole row), the two sorts of the
picks 0.5 each, tokens to rows 1; the same four of the route again under
``rematted_computation``; in the backward rows to tokens' rule 1 and the
route's cotangent 1.5; the embedding's scatter-add sorts too, 0.5, outside
the route.  A layer that keeps its route is the same step without the four
recomputed instructions."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import test_benchmark_manifest as accepted  # noqa: E402
from benchmark import harness, manifest  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import moe_route_sorts  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = ["laguna_xs2_s8192", "qwen3_next_s16384", "sdar_30b_s8192"]
ENTRIES = {"moe_route_ms": ("ms", "program_span"),
           "moe_route_sorts": ("instructions", "program_counter")}
RECOMPUTED = ("fusion.router.remat", "sort.4", "sort.5", "sort.6")


def _read(name):
    with open(os.path.join(DATA, name)) as f:
        return f.read()


def _ctx(cell, trace=None, hlo_text="ENTRY %main () -> f32[] {\n}"):
    cell = manifest.load_cell(cell)
    return harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text=hlo_text, memory=None, setup_compile_s=0.0,
        throughput=1.0, trace=trace)


def _recorded(cell, keeps_its_route):
    """The recorded step's context; with ``keeps_its_route`` the step
    without what the recompute ran of the route."""
    hlo, trace = _read("moe_route_step.hlo.txt"), json.loads(
        _read("moe_route_trace.json"))
    if keeps_its_route:
        kept, operand = [], {}
        for line in hlo.splitlines():
            name = line.split(" = ")[0].strip().removeprefix("ROOT ")[1:]
            if name in RECOMPUTED:      # its reader reads its operand
                operand[name] = line.split("(%")[1].split(")")[0]
                continue
            for gone, takes in operand.items():
                line = line.replace(f"(%{gone})", f"(%{takes})")
            kept.append(line)
        hlo = "\n".join(kept)
        for dev in trace["devices"].values():
            dev["ops"] = [op for op in dev["ops"] if op[0] not in RECOMPUTED]
    return _ctx(cell, tr.trace_from_json(json.dumps(trace)), hlo)


def _reader(name):
    return manifest.load_layer_metric(name)


def test_the_two_entries_appended_keep_the_manifest_to_its_contract(
        tmp_path):
    """What the ``benchmark`` PR that lists them does, on a copy: two
    entries at the end of ``per_layer``, nothing accepted moved, and the
    three sparse cells report both while no other cell does."""
    root = accepted._copy_of_the_benchmark(tmp_path)
    bench = manifest.load_manifest(root)
    assert not set(ENTRIES) & {m["name"] for m in bench["per_layer"]}
    was = {c["name"]: manifest.load_cell(c["name"], root=root)[
        "layer_metrics"] for c in bench["workloads"]}
    bench["per_layer"] += [{
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "expert layer", "moves": "tokens_per_s_chip",
        "workloads": CELLS} for name, (unit, source) in ENTRIES.items()]
    accepted._dump(bench, os.path.join(root, "BENCHMARK.json"))
    accepted.check_everything(root)
    for cell, before in was.items():
        now = manifest.load_cell(cell, root=root)["layer_metrics"]
        assert now == before + (list(ENTRIES) if cell in CELLS else [])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("keeps_its_route, route_ms, sorts", [
    (False, 9.5, 6), (True, 5.5, 3)], ids=["recomputed", "kept"])
def test_the_readers_on_a_recorded_step(cell, keeps_its_route, route_ms,
                                        sorts):
    ctx = _recorded(cell, keeps_its_route)
    assert _reader("moe_route_ms")(ctx) == pytest.approx(route_ms)
    assert _reader("moe_route_sorts")(ctx) == sorts
    # the route is what moe_dispatch_ms holds beside the two moves, and
    # what leaves the recompute leaves remat_ms
    assert _reader("moe_rows_ms")(ctx) == pytest.approx(1.0)
    assert _reader("moe_tokens_ms")(ctx) == pytest.approx(1.0)
    assert _reader("moe_dispatch_ms")(ctx) == pytest.approx(route_ms + 2.0)
    assert _reader("remat_ms")(ctx) == pytest.approx(
        0.0 if keeps_its_route else 4.0)
    assert _reader("bwd_ms")(ctx) == pytest.approx(3.0)


def test_every_form_of_a_top_k_counts_and_a_sort_elsewhere_does_not():
    hlo = _read("moe_route_step.hlo.txt")
    assert moe_route_sorts.sorts(hlo) == [
        "sort.1", "sort.2", "sort.3", "sort.4", "sort.5", "sort.6"]
    assert hlo.count(" sort(") == 7     # the embedding's is not the route's
    # XLA:CPU's instruction, and the custom call of older lowerings
    as_topk = hlo.replace("sort(%fusion.router), dimensions={1}, "
                          "is_stable=true, to_apply=%compare",
                          "topk(%fusion.router), k=8, largest=true")
    as_call = hlo.replace("sort(%fusion.router), dimensions={1}, "
                          "is_stable=true, to_apply=%compare",
                          "custom-call(%fusion.router), "
                          'custom_call_target="TopK"')
    for text in (as_topk, as_call):
        assert " sort(%fusion.router)" not in text
        assert len(moe_route_sorts.sorts(text)) == 6
    # a count of the program: no trace is needed
    assert _reader("moe_route_sorts")(_ctx(CELLS[0], None, hlo)) == 6
    assert _reader("moe_route_ms")(_ctx(CELLS[0], None, hlo)) is None


@pytest.mark.parametrize("name", list(ENTRIES))
@pytest.mark.parametrize("cell", CELLS)
def test_the_readers_find_nothing_on_a_program_without_the_route(cell, name):
    """No scope, or another program's scopes: None, no error."""
    assert _reader(name)(_ctx(cell)) is None
    dense = _ctx(cell, tr.trace_from_json(_read("bd_trace.json")),
                 _read("bd_step.hlo.txt").replace("hvdt.moe.route",
                                                  "hvdt.moe.other"))
    assert _reader(name)(dense) is None
