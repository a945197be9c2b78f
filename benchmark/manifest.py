"""Where the benchmark's data lives and how a name in ``BENCHMARK.json``
becomes a file.  Imports no JAX: tests and ``run.py``'s argument handling
use it before any device is touched.

    BENCHMARK.json                      workloads[], configs[], metrics
    benchmark/workloads/<cell>.json     one cell: chips, traffic, warm-up,
                                        traced steps
    <configs[].file>                    one configuration: family + sizes,
                                        what was cut from the source
    benchmark/families/<family>.py      build(config, traffic) -> Family
    benchmark/layer_metrics/<reader>.py read(ctx) -> number | None
    benchmark/peaks.json                device_kind -> published peaks

A later PR adds files and entries; it edits none of these.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(Exception):
    """A name in the manifest or a cell file that resolves to nothing."""


def _read_json(path: str, what: str):
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"), "the manifest")


def check_config(entry: dict, data: dict) -> None:
    """The configuration contract (``benchmark/README.md``), between a
    ``configs`` entry of the manifest and its file's contents: one
    ``source``; one ``reduced``, a list of distinct keys of the file;
    where something is cut, the source's value of each cut key under
    ``published`` (those keys and no others) and the deployment the cut
    stands for, in one line, under ``deployment``."""
    name = entry["name"]

    def refuse(what: str):
        raise ManifestError(f"config {name!r} ({entry['file']}): {what}")

    if data.get("source") != entry["source"]:
        refuse(f"source is {data.get('source')!r} in its file and "
               f"{entry['source']!r} in BENCHMARK.json")
    reduced = data.get("reduced")
    if reduced != entry["reduced"]:
        refuse(f"reduced is {reduced!r} in its file and "
               f"{entry['reduced']!r} in BENCHMARK.json")
    if len(set(reduced)) != len(reduced):
        refuse(f"reduced names a key twice: {reduced}")
    for key in ("family", "assumed"):
        if key not in data:
            refuse(f"no {key!r} in its file")
    absent = [k for k in reduced if k not in data]
    if absent:
        refuse(f"reduced names keys the file does not have: {absent}")
    if not reduced:
        return
    published = data.get("published", {})
    if sorted(published) != sorted(reduced):
        refuse("published must give the source's value of exactly the "
               f"keys under reduced ({sorted(reduced)}); it has "
               f"{sorted(published)}")
    deployment = data.get("deployment", "")
    if not (isinstance(deployment, str) and 1 <= len(deployment) <= 200
            and "\n" not in deployment and "\t" not in deployment):
        refuse("a cut configuration states its deployment (over how many "
               "chips each layer is divided, and how) in one line of at "
               "most 200 characters")


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name``: its manifest entry merged over its own file,
    with its configuration under ``config_data``, and what it reports, in
    the manifest's order: the end-to-end metrics under ``end_to_end`` and
    the per-layer metrics under ``layer_metrics`` (names: the entries
    whose ``workloads`` holds the cell, or that have no ``workloads``),
    every metric's unit under ``units``."""
    manifest = load_manifest(root)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                            f"(known: {known})")
    entry = entries[0]
    cell = _read_json(
        os.path.join(root, "benchmark", "workloads", name + ".json"),
        f"workload {name!r}")
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise ManifestError(
                f"workload {name!r}: {key} is {cell[key]!r} in its file and "
                f"{entry[key]!r} in BENCHMARK.json")
    if cell["traffic"]["name"] != entry["traffic"]:
        raise ManifestError(
            f"workload {name!r}: traffic is {cell['traffic']['name']!r} in "
            f"its file and {entry['traffic']!r} in BENCHMARK.json")
    configs = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    if not configs:
        raise ManifestError(f"workload {name!r}: no config "
                            f"{cell['config']!r} in BENCHMARK.json")
    cell = dict(cell, name=name)
    cell["config_data"] = _read_json(
        os.path.join(root, configs[0]["file"]),
        f"config {cell['config']!r}")
    check_config(configs[0], cell["config_data"])

    def reported(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m["name"] for m in manifest["end_to_end"]
                          if reported(m)]
    cell["layer_metrics"] = [m["name"] for m in manifest["per_layer"]
                             if reported(m)]
    cell["units"] = {m["name"]: m["unit"] for m in
                     manifest["end_to_end"] + manifest["per_layer"]}
    return cell


def load_family(family: str):
    """``benchmark.families.<family>``; an unknown family is an error that
    names the file to add.  Code always comes from the checkout that is
    running (``ROOT``); only data follows ``load_cell``'s ``root``."""
    path = os.path.join(ROOT, "benchmark", "families", family + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"unknown family {family!r}: add {path} with "
            "build(config, traffic) -> Family")
    return importlib.import_module(f"benchmark.families.{family}")


def load_layer_metric(name: str):
    """The ``read(ctx)`` function of the reader of per-layer metric
    ``name``: ``benchmark/layer_metrics/<reader>.py``, where a name is
    ``<reader>`` or ``<reader>.<qualifier>``.  The qualifier only tells
    apart manifest entries that share a reader and move different
    end-to-end metrics (``mfu_pct`` moves tokens/s, ``mfu_pct.images``
    images/s)."""
    reader = name.split(".", 1)[0]
    path = os.path.join(ROOT, "benchmark", "layer_metrics", reader + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"unknown per-layer metric {name!r}: add {path} with read(ctx)")
    return importlib.import_module(f"benchmark.layer_metrics.{reader}").read


def load_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``.  A device that is not in the
    table is an error, not a default."""
    table = _read_json(os.path.join(ROOT, "benchmark", "peaks.json"),
                       "the table of peaks")
    if device_kind not in table:
        raise ManifestError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {', '.join(table)})")
    return table[device_kind]
