"""The nine ``setup_*`` readers of the start-up layer (PR 51): what the
process's compile ledger (``horovod_tpu/telemetry/compile_ledger.py``)
says of set-up from inside, beside ``compile_s``.

``BENCHMARK.json`` does not list them yet.  A new entry goes to the end of
``per_layer``, and the accepted ``test_benchmark_evabyte.py`` holds the last
five names of that list (PERF.md section 7, B0 (r)); so the nine entries,
which belong after ``compile_s`` with the rest of their layer, are rehearsed
here on a copy of the benchmark, which then passes every check of
``test_benchmark_manifest``.  The readers are held against a ledger filled
by hand, and against the harness's own account after a toy run on the CPU
(counts and which second lands where: never a time)."""

import math
import os
import shutil
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import test_benchmark_harness as toy  # noqa: E402
import test_benchmark_manifest as accepted  # noqa: E402
from benchmark import harness, manifest  # noqa: E402
from benchmark.layer_metrics import setup_ledger  # noqa: E402
from horovod_tpu.telemetry import compile_ledger as cl  # noqa: E402

SECONDS = ["setup_import_s", "setup_init_s", "setup_backend_s",
           "setup_trace_s", "setup_lower_s", "setup_cache_load_s",
           "setup_step_s", "setup_kernel_trace_s"]
READERS = SECONDS + ["setup_kernel_traces"]

v5e_peaks = toy.v5e_peaks               # the fixture, for the toy run below


def entries():
    """The entries a benchmark PR adds for the readers (B0 (r)): no
    ``workloads`` list, every cell reports them, as ``compile_s``."""
    return [{"name": name,
             "unit": "s" if name in SECONDS else "traces",
             "better": "lower",
             "source": "program_span" if name in SECONDS
             else "program_counter",
             "layer": "start-up", "moves": "setup_s"}
            for name in READERS]


@pytest.fixture()
def rehearsed(tmp_path):
    """A copy of the benchmark with the nine entries after ``compile_s``."""
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = manifest.load_manifest()
    assert bench["per_layer"][0]["name"] == "compile_s"
    bench["per_layer"][1:1] = entries()
    accepted._dump(bench, tmp_path / "BENCHMARK.json")
    return str(tmp_path)


def test_the_entries_stand_after_compile_s_with_their_layers_fields(
        rehearsed):
    before = manifest.load_manifest()["per_layer"]
    after = manifest.load_manifest(rehearsed)["per_layer"]
    assert [m["name"] for m in after[:10]] == ["compile_s"] + READERS
    assert after[0] == before[0] and after[10:] == before[1:]
    first = after[0]
    for entry in after[1:10]:
        assert "workloads" not in entry
        for key in ("layer", "moves", "better"):
            assert entry[key] == first[key], (entry["name"], key)
        assert set(entry) == set(first)
    assert {m["name"]: (m["unit"], m["source"]) for m in after[1:10]} == {
        **{name: ("s", "program_span") for name in SECONDS},
        "setup_kernel_traces": ("traces", "program_counter")}


def test_every_cell_lists_all_nine_in_order_before_hbm_temp_gib(rehearsed):
    cells = [w["name"] for w in manifest.load_manifest()["workloads"]]
    assert len(cells) >= 10
    for cell in cells:
        listed = manifest.load_cell(cell, root=rehearsed)["layer_metrics"]
        assert listed[:11] == ["compile_s"] + READERS + ["hbm_temp_gib"]
        # the accepted readers, unchanged and in their order
        assert [m for m in listed if m not in READERS] == \
            manifest.load_cell(cell)["layer_metrics"]
        assert all(manifest.load_cell(cell, root=rehearsed)["units"][m]
                   for m in READERS)


def test_the_rehearsed_manifest_passes_every_accepted_check(rehearsed):
    accepted.check_everything(rehearsed)
    for name in READERS:
        assert callable(manifest.load_layer_metric(name))


def test_the_tree_does_not_list_them_yet():
    """Until a ``benchmark`` PR turns the pinned tail into containment
    (B0 (r)) the manifest has none of the nine, in any cell."""
    names = {m["name"] for m in manifest.load_manifest()["per_layer"]}
    assert not names & set(READERS)


# ---------------------------------------------------------------------------
# The readers on a ledger filled by hand.
# ---------------------------------------------------------------------------


def span(ledger, event, program, start, end):
    ledger.on_span(event, start, end, fun_name=program)


@pytest.fixture()
def by_hand(monkeypatch):
    """A step program with a nested trace span, built once by a miss; a
    second program the cache served; two kernel sites, one entered twice;
    the three start-up phases."""
    ledger = cl.CompileLedger()

    def local_step():
        pass

    ledger.note_step_program(local_step)
    ledger.on_scalar(cl.TRACE_EVENT, 100.0, fun_name="local_step")
    ledger.on_scalar(cl.TRACE_EVENT, 101.0, fun_name="softmax")
    span(ledger, cl.TRACE_EVENT, "softmax", 101.0, 102.5)       # nested
    span(ledger, cl.TRACE_EVENT, "local_step", 100.0, 106.0)
    span(ledger, cl.LOWER_EVENT, "jit(local_step)", 106.0, 108.0)
    ledger.on_event(cl.CACHE_REQUEST_EVENT)                     # a miss
    span(ledger, cl.BACKEND_EVENT, "jit(local_step)", 108.0, 118.0)
    ledger.on_scalar(cl.TRACE_EVENT, 120.0, fun_name="init")
    span(ledger, cl.TRACE_EVENT, "init", 120.0, 120.5)
    span(ledger, cl.LOWER_EVENT, "jit(init)", 120.5, 120.75)
    ledger.on_event(cl.CACHE_REQUEST_EVENT)
    ledger.on_event(cl.CACHE_HIT_EVENT)                         # a hit
    ledger.on_duration(cl.CACHE_SAVED_EVENT, 4.0)
    span(ledger, cl.BACKEND_EVENT, "jit(init)", 120.75, 122.0)
    ledger.note_kernel_trace("flash_fwd", 0.75)
    ledger.note_kernel_trace("flash_fwd", 0.5)
    ledger.note_kernel_trace("rope", 0.25)
    ledger.note_startup("import", 3.0)
    ledger.note_startup("backend", 7.5)
    ledger.note_startup("init", 0.25)
    monkeypatch.setattr(cl, "_ledger", ledger)
    return ledger


@pytest.mark.parametrize("name, reading", [
    ("setup_import_s", 3.0), ("setup_init_s", 0.25),
    ("setup_backend_s", 7.5),
    ("setup_trace_s", 6.5),             # 6 + 0.5: the nested 1.5 is in the 6
    ("setup_lower_s", 2.25),
    ("setup_cache_load_s", 1.25),       # the hit alone; the miss's 10 s
    ("setup_step_s", 18.0),             # are compile_s's: 6 + 2 + 10
    ("setup_kernel_trace_s", 1.5), ("setup_kernel_traces", 3)])
def test_a_reader_returns_the_number_worked_out_by_hand(by_hand, name,
                                                        reading):
    assert manifest.load_layer_metric(name)(None) == reading
    assert by_hand.seconds("compile") == 10.0


def test_a_program_without_the_ledger_leaves_the_metrics_out(monkeypatch):
    """The parent of PR 51 has no ``telemetry/compile_ledger``: each
    reader returns None there and raises nothing."""
    import horovod_tpu.telemetry

    monkeypatch.delattr(horovod_tpu.telemetry, "compile_ledger")
    monkeypatch.setitem(sys.modules, "horovod_tpu.telemetry.compile_ledger",
                        None)           # the import raises ImportError
    assert setup_ledger.process_ledger() is None
    for name in READERS:
        assert manifest.load_layer_metric(name)(None) is None


# ---------------------------------------------------------------------------
# The readers after a toy run, beside the harness's own account.
# ---------------------------------------------------------------------------


def test_the_readers_after_a_toy_run_agree_with_the_harness(
        hvd, devices, v5e_peaks, monkeypatch, tmp_path):
    """The seq-512 cell at toy size with the flash kernels pinned on (in
    interpret mode here), so that the step enters kernel sites.  The
    process's ledger has seen other tests' programs too: what is held is
    what the run added."""
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    cell = toy.toy_cell("lm24x1024_s512_b128")
    cell["traffic"]["seq"] = 128
    readers = {name: manifest.load_layer_metric(name) for name in READERS}
    ledger = cl.get_ledger()
    before = {name: read(None) for name, read in readers.items()}
    missed = ledger.seconds("compile")
    builds = ledger.builds()
    result = harness.run_cell(
        cell, devices, seed=3, seconds=1.0, trace=False,
        started_at=time.perf_counter(), trace_dir=str(tmp_path))
    assert result["correct"], result["checks"]
    after = {name: read(None) for name, read in readers.items()}
    assert all(math.isfinite(v) and v >= 0 for v in after.values())
    added = {name: after[name] - before[name] for name in READERS}
    setup = result["checks"]["setup"]
    # no persistent cache here: every build is a miss, and the harness's
    # compile_s is the ledger's miss seconds
    assert added["setup_cache_load_s"] == 0
    assert ledger.seconds("compile") - missed == pytest.approx(
        setup["compile_s"], rel=1e-6)
    assert ledger.builds() - builds == setup["compiles"]
    assert added["setup_trace_s"] > 0 and added["setup_lower_s"] > 0
    assert added["setup_step_s"] > 0
    assert ledger.programs["local_step"].role == "step"
    assert added["setup_kernel_traces"] >= 2        # flash forward, backward
    assert 0 < added["setup_kernel_trace_s"] <= added["setup_trace_s"]
    stages = (added["setup_trace_s"] + added["setup_lower_s"]
              + added["setup_cache_load_s"] + setup["compile_s"])
    assert added["setup_step_s"] <= stages < setup["setup_s"]
    # the start-up phases are the process's, set once by hvd.init()
    for name in ("setup_import_s", "setup_init_s", "setup_backend_s"):
        assert added[name] == 0 and after[name] >= 0
