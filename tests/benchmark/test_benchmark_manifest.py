"""BENCHMARK.json against the contract it was written to, and the promise
that a later cell is data: every name resolves to a file, and a fifth cell
of a known family is one new file and one new entry.  CPU only."""

import json
import os
import re
import shutil
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


def test_top_level_keys_and_run_seconds():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))


def test_names_units_and_whys_are_in_the_allowed_characters():
    entries = (MANIFEST["configs"] + MANIFEST["workloads"]
               + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        texts = [e[k] for k in ("why", "layer") if k in e]
        if e in MANIFEST["configs"]:
            texts.append(e["source"])
        for text in texts:
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text, e["name"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_metrics_keep_the_contracts_limits():
    by_name = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in by_name
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_exactly_one_cell_asks_for_four_chips():
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four == ["lm24x1024_s512_dp4"]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_every_config_is_used_and_its_file_is_under_paths():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"] == []
        assert "assumed" in data and "family" in data


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_a_cell_points_at_resolves(cell):
    loaded = manifest.load_cell(cell)
    assert "setup_s" in loaded["end_to_end"]
    assert len(loaded["end_to_end"]) >= 2 and loaded["layer_metrics"]
    manifest.load_family(loaded["config_data"]["family"])
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in loaded["layer_metrics"]:
        assert callable(manifest.load_layer_metric(name))
        # A per-layer metric is reported only where the metric it moves is.
        assert declared[name]["moves"] in loaded["end_to_end"], name
    assert len(loaded["why"]) <= 200


def test_every_per_layer_metric_is_reported_by_some_cell():
    reported = set()
    for cell in CELLS:
        reported |= set(manifest.load_cell(cell)["layer_metrics"])
    assert reported == {m["name"] for m in MANIFEST["per_layer"]}


def _copy_of_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(tmp_path)


def test_a_fifth_cell_is_one_new_file_and_one_new_entry(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            path = os.path.join(dirpath, f)
            before[path] = open(path, "rb").read()

    cell = json.load(open(os.path.join(
        root, "benchmark", "workloads", "lm24x1024_s512_b128.json")))
    cell["traffic"].update(name="s1024_b64", seq=1024, per_chip_batch=64)
    cell["why"] = "a later PR's cell: seq 1024 x 64"
    new_file = os.path.join(root, "benchmark", "workloads",
                            "lm24x1024_s1024_b64.json")
    with open(new_file, "w") as f:
        json.dump(cell, f)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({
        "name": "lm24x1024_s1024_b64", "config": "lm24x1024",
        "traffic": "s1024_b64", "chips": 1, "why": cell["why"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lm24x1024_s512_b128" in m.get("workloads", []):
            m["workloads"].append("lm24x1024_s1024_b64")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    loaded = manifest.load_cell("lm24x1024_s1024_b64", root=root)
    assert loaded["traffic"]["seq"] == 1024
    assert loaded["end_to_end"] == ["tokens_per_s_chip", "peak_hbm_gib",
                                    "setup_s"]
    assert loaded["layer_metrics"] == manifest.load_cell(
        "lm24x1024_s512_b128")["layer_metrics"]
    family = manifest.load_family(loaded["config_data"]["family"]).build(
        loaded["config_data"], loaded["traffic"])
    assert family.units_per_sample == 1024 and family.flops_per_unit > 0
    changed = [p for p, data in before.items()
               if open(p, "rb").read() != data]
    assert changed == [os.path.join(root, "BENCHMARK.json")]


def test_a_cell_whose_file_disagrees_with_the_manifest_is_refused(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "workloads", "resnet50_train.json")
    cell = json.load(open(path))
    cell["chips"] = 4
    json.dump(cell, open(path, "w"))
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
