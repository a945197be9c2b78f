"""BENCHMARK.json against the contract it was written to, and the promise
that what a later PR brings is data: every name resolves to a file; a
fifth cell of a known family is one new file and one new entry; a
configuration cut to a chip's share is two new files and two new entries;
a new per-layer metric of an accepted cell is one new entry (and its
reader).  What is accepted is held by containment, never by equality with
today's lists, and the last test runs this directory's manifest tests
whole on a copy that has such additions: a list pinned anywhere in them
fails there, before it stops a later PR.  CPU only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


# ---------------------------------------------------------------------------
# The invariants, each a function of the root of a tree that holds
# BENCHMARK.json and benchmark/: this repo, or a copy a later PR's
# addition was rehearsed in.
# ---------------------------------------------------------------------------


def check_top_level(root):
    bench = manifest.load_manifest(root)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128


def check_names_units_and_whys(root):
    bench = manifest.load_manifest(root)
    entries = (bench["configs"] + bench["workloads"]
               + bench["end_to_end"] + bench["per_layer"])
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        texts = [e[k] for k in ("why", "layer") if k in e]
        if e in bench["configs"]:
            texts.append(e["source"])
        for text in texts:
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text, e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["per_layer"]:
        assert {"name", "unit", "better", "source", "layer", "moves"} \
            <= set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def check_end_to_end(root):
    bench = manifest.load_manifest(root)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def check_four_chip_rule(root):
    """At most a quarter of the cells, rounded down, may ask for four
    chips, and one always may."""
    cells = manifest.load_manifest(root)["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4), four


def check_configs(root):
    bench = manifest.load_manifest(root)
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(root, c["file"])) as f:
            manifest.check_config(c, json.load(f))


def check_cell(root, cell):
    bench = manifest.load_manifest(root)
    loaded = manifest.load_cell(cell, root=root)
    assert "setup_s" in loaded["end_to_end"]
    assert len(loaded["end_to_end"]) >= 2 and loaded["layer_metrics"]
    manifest.load_family(loaded["config_data"]["family"])
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in loaded["layer_metrics"]:
        assert callable(manifest.load_layer_metric(name))
        # A per-layer metric is reported only where the metric it moves is.
        assert declared[name]["moves"] in loaded["end_to_end"], name
    assert len(loaded["why"]) <= 200
    # One source of truth: what a cell reports is the manifest's to say.
    with open(os.path.join(root, "benchmark", "workloads",
                           cell + ".json")) as f:
        assert "layer_metrics" not in json.load(f)


def check_every_metric_is_reported(root):
    bench = manifest.load_manifest(root)
    cells = [w["name"] for w in bench["workloads"]]
    for group, key in (("end_to_end", "end_to_end"),
                       ("per_layer", "layer_metrics")):
        reported = set()
        for cell in cells:
            reported |= set(manifest.load_cell(cell, root=root)[key])
        assert reported == {m["name"] for m in bench[group]}
        for m in bench[group]:
            assert set(m.get("workloads", cells)) <= set(cells), m


# What was accepted before this file held it (the driver's record: PR 21 to
# PR 29), and what PR 30 appended.  A later PR appends too: each of these
# is looked for in the tree, in this order, among whatever else is there.
LM_CELLS = ["lm24x1024_s512_b128", "lm24x1024_s4096_b8",
            "lm24x1024_s512_dp4"]
ACCEPTED_CELLS = [("lm24x1024_s512_b128", "lm24x1024", "s512_b128", 1),
                  ("lm24x1024_s4096_b8", "lm24x1024", "s4096_b8", 1),
                  ("lm24x1024_s512_dp4", "lm24x1024", "s512_b32_dp4", 4),
                  ("resnet50_train", "resnet50", "b128_224", 1)]
ACCEPTED_CONFIGS = [("lm24x1024", "https://arxiv.org/abs/1810.04805", []),
                    ("resnet50", "https://arxiv.org/abs/1512.03385", [])]
ACCEPTED_END_TO_END = [("tokens_per_s_chip", 0.01, LM_CELLS),
                       ("images_per_s_chip", 0.01, ["resnet50_train"]),
                       ("peak_hbm_gib", 0.01, [c[0] for c in ACCEPTED_CELLS]),
                       ("setup_s", 0.1, [c[0] for c in ACCEPTED_CELLS])]
RETIRED = {"flash_fwd_named_ms", "optimizer_ms.images"}
COMMON = ["compile_s", "hbm_temp_gib"]
LM = COMMON + ["host_gap_ms", "mfu_pct", "step_device_ms", "device_idle_pct"]
LM_SPLIT = ["fwd_ms", "remat_ms", "bwd_ms", "attention_ms", "loss_ms",
            "optimizer_ms"]
ACCEPTED_LAYER_METRICS = {
    "lm24x1024_s512_b128": LM + LM_SPLIT + ["unscoped_ms"],
    "lm24x1024_s4096_b8": LM + [
        "flash_fwd_ms", "flash_fwd_roofline", "flash_bwd_ms",
        "flash_bwd_roofline"] + LM_SPLIT + ["unscoped_ms"],
    "lm24x1024_s512_dp4": LM + ["allreduce_bytes", "exchange_exposed_ms"]
    + LM_SPLIT + ["exchange_ms", "unscoped_ms"],
    "resnet50_train": COMMON + [
        "host_gap_ms.images", "mfu_pct.images", "step_device_ms.images",
        "device_idle_pct.images", "conv_ms", "conv_roofline",
        "fwd_ms.images", "bwd_ms.images", "unscoped_ms.images"]}


def in_order(part, whole):
    """Whether ``part`` is in ``whole``, in its order, among whatever
    else ``whole`` has."""
    rest = iter(whole)
    return all(item in rest for item in part)


def check_what_was_accepted_is_there(root):
    """The accepted cells, configurations, bounds and window, each with
    the values it was accepted with; more of each may stand beside them."""
    bench = manifest.load_manifest(root)
    assert bench["run_seconds"] == 26
    assert in_order(ACCEPTED_CELLS,
                    [(w["name"], w["config"], w["traffic"], w["chips"])
                     for w in bench["workloads"]])
    assert in_order(ACCEPTED_CONFIGS,
                    [(c["name"], c["source"], c["reduced"])
                     for c in bench["configs"]])
    every = [w["name"] for w in bench["workloads"]]
    assert in_order([m[:2] for m in ACCEPTED_END_TO_END],
                    [(m["name"], m["bound"]) for m in bench["end_to_end"]])
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    for name, _, cells in ACCEPTED_END_TO_END:
        assert in_order(cells, end_to_end[name].get("workloads", every)), name
    assert not RETIRED & {m["name"] for m in bench["per_layer"]}
    for cell, metrics in ACCEPTED_LAYER_METRICS.items():
        check_a_cells_metrics(root, cell, metrics)


def check_a_cells_metrics(root, cell, metrics):
    """``metrics`` are among what ``cell`` reports, and what it reports is
    in the manifest's order, each with a unit."""
    loaded = manifest.load_cell(cell, root=root)
    assert in_order(metrics, loaded["layer_metrics"]), loaded["layer_metrics"]
    order = [m["name"] for m in manifest.load_manifest(root)["per_layer"]]
    assert loaded["layer_metrics"] == sorted(loaded["layer_metrics"],
                                             key=order.index)
    assert all(loaded["units"][m] for m in loaded["layer_metrics"])


ROOT_CHECKS = [check_top_level, check_names_units_and_whys, check_end_to_end,
               check_four_chip_rule, check_configs,
               check_every_metric_is_reported,
               check_what_was_accepted_is_there]


def check_everything(root):
    for check in ROOT_CHECKS:
        check(root)
    for w in manifest.load_manifest(root)["workloads"]:
        check_cell(root, w["name"])


@pytest.mark.parametrize("check", ROOT_CHECKS,
                         ids=[c.__name__[len("check_"):] for c in ROOT_CHECKS])
def test_the_manifest_keeps_to_its_contract(check):
    check(REPO)


def test_the_paths_are_directories_of_the_repo():
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_a_cell_points_at_resolves(cell):
    check_cell(REPO, cell)


@pytest.mark.parametrize("cell", list(ACCEPTED_LAYER_METRICS))
def test_a_cells_per_layer_metrics_are_derived_from_the_manifest(cell):
    # What the cell reported before its list became the manifest's to give
    # (PR 30), and the entries that PR appended, are among what it reports.
    check_a_cells_metrics(REPO, cell, ACCEPTED_LAYER_METRICS[cell])
    with open(os.path.join(REPO, "benchmark", "workloads",
                           cell + ".json")) as f:
        assert "layer_metrics" not in json.load(f)


def test_what_was_accepted_is_looked_for_not_pinned(tmp_path):
    """The check above passes with more of everything beside what was
    accepted, and fails where an accepted value moved."""
    root = _copy_of_the_benchmark(tmp_path)
    _add_a_configuration_cut_in_depth(root)
    bench = manifest.load_manifest(root)
    bench["per_layer"].insert(3, {
        "name": "loss_ms.head", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "models",
        "moves": "tokens_per_s_chip", "workloads": ["lm24x1024_s4096_b8"]})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    check_what_was_accepted_is_there(root)
    for change in (lambda b: b["end_to_end"][0].update(bound=0.02),
                   lambda b: b["workloads"][1].update(chips=4),
                   lambda b: b["end_to_end"][0]["workloads"].remove(
                       "lm24x1024_s4096_b8"),
                   lambda b: b.update(per_layer=[
                       m for m in b["per_layer"] if m["name"] != "mfu_pct"]),
                   lambda b: b.update(run_seconds=27)):
        moved = manifest.load_manifest(root)
        change(moved)
        _dump(moved, os.path.join(root, "BENCHMARK.json"))
        with pytest.raises(AssertionError):
            check_what_was_accepted_is_there(root)
        _dump(bench, os.path.join(root, "BENCHMARK.json"))


def _copy_of_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(tmp_path)


def _files(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[path] = fh.read()
    return out


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(data, path):
    with open(path, "w") as f:
        json.dump(data, f)


def _add_cell(root, entry, cell_file, like):
    """What a later PR does to add a cell: its file, its entry, and its
    name on every ``workloads`` list that holds the cell ``like``."""
    _dump(cell_file, os.path.join(root, "benchmark", "workloads",
                                  entry["name"] + ".json"))
    bench = manifest.load_manifest(root)
    bench["workloads"].append(entry)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(entry["name"])
    _dump(bench, os.path.join(root, "BENCHMARK.json"))


def test_a_fifth_cell_is_one_new_file_and_one_new_entry(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = _files(root)
    cell = _load(os.path.join(root, "benchmark", "workloads",
                              "lm24x1024_s512_b128.json"))
    cell["traffic"].update(name="s1024_b64", seq=1024, per_chip_batch=64)
    cell["why"] = "a later PR's cell: seq 1024 x 64"
    _add_cell(root, {"name": "lm24x1024_s1024_b64", "config": "lm24x1024",
                     "traffic": "s1024_b64", "chips": 1,
                     "why": cell["why"]}, cell, like="lm24x1024_s512_b128")

    loaded = manifest.load_cell("lm24x1024_s1024_b64", root=root)
    assert loaded["traffic"]["seq"] == 1024
    assert loaded["end_to_end"] == ["tokens_per_s_chip", "peak_hbm_gib",
                                    "setup_s"]
    assert loaded["layer_metrics"] == manifest.load_cell(
        "lm24x1024_s512_b128")["layer_metrics"]
    family = manifest.load_family(loaded["config_data"]["family"]).build(
        loaded["config_data"], loaded["traffic"])
    assert family.units_per_sample == 1024 and family.flops_per_unit > 0
    check_everything(root)
    changed = [p for p, data in _files(root).items()
               if before.get(p) != data and p in before]
    assert changed == [os.path.join(root, "BENCHMARK.json")]


def _add_a_configuration_cut_in_depth(root, layers=8, **changes):
    """A later `model_config` PR, rehearsed: a configuration of a known
    family with ``layers`` of the source's 24 layers, its cell, their two
    entries.  ``changes`` overwrite keys of the configuration's file (None
    takes the key out)."""
    name = f"lm{layers}x1024"
    config = _load(os.path.join(root, "benchmark", "configs",
                                "lm24x1024.json"))
    config.update(
        layers=layers, reduced=["layers"], published={"layers": 24},
        deployment=f"{24 // layers} pipeline stages of {layers} layers; "
                   "this chip holds one stage, no layer is divided")
    config.update(changes)
    config = {k: v for k, v in config.items() if v is not None}
    _dump(config, os.path.join(root, "benchmark", "configs", name + ".json"))
    bench = manifest.load_manifest(root)
    bench["configs"].append({
        "name": name, "source": config["source"],
        "file": f"benchmark/configs/{name}.json", "reduced": ["layers"],
        "why": "a later PR's configuration: one pipeline stage's layers"})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    cell = _load(os.path.join(root, "benchmark", "workloads",
                              "lm24x1024_s512_b128.json"))
    cell.update(config=name, why="a later PR's cell: a part of the depth "
                                 "at seq 512 x 128")
    _add_cell(root, {"name": name + "_s512_b128", "config": name,
                     "traffic": "s512_b128", "chips": 1,
                     "why": cell["why"]}, cell, like="lm24x1024_s512_b128")


def test_a_cut_configuration_and_its_cell_are_additions(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = _files(root)
    _add_a_configuration_cut_in_depth(root)

    check_everything(root)
    loaded = manifest.load_cell("lm8x1024_s512_b128", root=root)
    data = loaded["config_data"]
    assert (data["layers"], data["published"], data["reduced"]) == (
        8, {"layers": 24}, ["layers"])
    assert loaded["layer_metrics"] == manifest.load_cell(
        "lm24x1024_s512_b128")["layer_metrics"]
    family = manifest.load_family(data["family"]).build(data,
                                                       loaded["traffic"])
    whole = manifest.load_cell("lm24x1024_s512_b128")
    full = manifest.load_family("transformer_lm").build(
        whole["config_data"], whole["traffic"])
    # Every width as published: only the depth's share of the work went.
    assert family.units_per_sample == full.units_per_sample == 512
    assert 1 / 3 < family.flops_per_unit / full.flops_per_unit < 0.4
    after = _files(root)
    assert sorted(set(after) - set(before)) == [
        os.path.join(root, "benchmark", "configs", "lm8x1024.json"),
        os.path.join(root, "benchmark", "workloads",
                     "lm8x1024_s512_b128.json")]
    assert [p for p in before if after[p] != before[p]] == [
        os.path.join(root, "BENCHMARK.json")]


@pytest.mark.parametrize("changes, refusal", [
    (dict(reduced=["layers", "vocab"],
          published={"layers": 24, "vocab": 30528}),
     r"reduced is \['layers', 'vocab'\] in its file and \['layers'\] in "
     r"BENCHMARK\.json"),
    (dict(published={}), r"published must give the source's value of "
                         r"exactly the keys under reduced \(\['layers'\]\)"),
    (dict(deployment=None), "a cut configuration states its deployment"),
], ids=["reduced_differs", "no_published_value", "no_deployment"])
def test_a_cut_that_is_not_written_down_is_refused(tmp_path, changes,
                                                   refusal):
    root = _copy_of_the_benchmark(tmp_path)
    _add_a_configuration_cut_in_depth(root, **changes)
    with pytest.raises(manifest.ManifestError, match=refusal):
        check_configs(root)
    with pytest.raises(manifest.ManifestError, match=refusal):
        manifest.load_cell("lm8x1024_s512_b128", root=root)
    manifest.load_cell("lm24x1024_s512_b128", root=root)    # the others run


def test_a_new_per_layer_metric_of_an_accepted_cell_is_one_entry(tmp_path):
    # The reader is there already (a second entry of one reader, told
    # apart by its qualifier); a new one is layer_metrics/<reader>.py.
    root = _copy_of_the_benchmark(tmp_path)
    before = _files(root)
    bench = manifest.load_manifest(root)
    bench["per_layer"].append({
        "name": "loss_ms.head", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "models",
        "moves": "tokens_per_s_chip", "workloads": ["lm24x1024_s4096_b8"]})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    was = manifest.load_cell("lm24x1024_s4096_b8")["layer_metrics"]
    assert manifest.load_cell("lm24x1024_s4096_b8", root=root)[
        "layer_metrics"] == was + ["loss_ms.head"]
    assert manifest.load_cell("lm24x1024_s512_b128", root=root)[
        "layer_metrics"] == manifest.load_cell(
            "lm24x1024_s512_b128")["layer_metrics"]
    check_everything(root)
    after = _files(root)
    assert [p for p in before if after[p] != before[p]] == [
        os.path.join(root, "BENCHMARK.json")] and set(after) == set(before)


def test_a_cell_whose_file_disagrees_with_the_manifest_is_refused(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "workloads", "resnet50_train.json")
    _dump(dict(_load(path), chips=4), path)
    with pytest.raises(manifest.ManifestError, match="chips is 4 in its file"):
        manifest.load_cell("resnet50_train", root=root)
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("no_such_cell", root=root)


def test_an_unknown_family_or_metric_names_the_file_to_add():
    with pytest.raises(manifest.ManifestError,
                       match=r"benchmark/families/mamba\.py"):
        manifest.load_family("mamba")
    with pytest.raises(manifest.ManifestError,
                       match=r"benchmark/layer_metrics/router_ms\.py"):
        manifest.load_layer_metric("router_ms.tokens")


def test_the_manifest_tests_pass_whole_on_a_tree_with_later_additions(
        tmp_path):
    """What the reviewer of PR 30 did by hand: a later `model_config` PR's
    configuration and cell and a later `tracing` PR's reader and entry,
    added to a copy of the benchmark AND of these tests, which then run
    whole there (they find their tree from their own path).  A test that
    pins today's lists passes in the repo and fails here."""
    root = _copy_of_the_benchmark(tmp_path)
    tests = os.path.join(root, "tests", "benchmark")
    shutil.copytree(os.path.join(REPO, "tests", "benchmark"), tests,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    _add_a_configuration_cut_in_depth(root, layers=6)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "mlp_ms.py"), "w") as f:
        f.write("from benchmark.phase_split import scope_metric\n\n\n"
                "def read(ctx):\n"
                "    return scope_metric(ctx, 'hvdt.mlp')\n")
    bench = manifest.load_manifest(root)
    bench["per_layer"].append({
        "name": "mlp_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "models",
        "moves": "tokens_per_s_chip",
        "workloads": ["lm24x1024_s4096_b8", "lm6x1024_s512_b128"]})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    after = _files(root)
    assert [p for p in before if after[p] != before[p]] == [
        os.path.join(root, "BENCHMARK.json")]

    modules = ["test_benchmark_manifest.py", "test_benchmark_phase_split.py",
               "test_benchmark_counts.py", "test_benchmark_trace_reduce.py"]
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         "-k", "not pass_whole_on_a_tree"]
        + [os.path.join(tests, m) for m in modules],
        cwd=root, capture_output=True, text=True, timeout=200,
        # horovod_tpu (the families import it) comes from this checkout;
        # `benchmark` from the copy, which the tests put first.
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep))))
    assert run.returncode == 0, run.stdout[-4000:] + run.stderr[-2000:]
    assert " passed" in run.stdout and "failed" not in run.stdout
