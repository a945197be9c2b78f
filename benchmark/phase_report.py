"""python3 benchmark/phase_report.py --workload <cell> --seed <n> --seconds <s>

``run.py``'s traced run of one cell, with the phase-split metrics of
``benchmark/phase_metrics.json`` added to the per-layer metrics the cell
lists: forward / recompute / backward / optimizer / exchange / unscoped,
attention, loss and the flash forward by name, in milliseconds per step.
Same process, same checks, same result line; without a TPU nothing is
printed.

Why this file exists: a cell's per-layer metrics are the ``layer_metrics``
list of its ``benchmark/workloads/<cell>.json``, and PR 24 (`tracing`) may
add files to the benchmark but edit none.  A `benchmark` PR appends the
entries of ``phase_metrics.json`` to ``BENCHMARK.json`` and their names to
the four lists; this file and that one then go.

The step program must have been compiled with the scopes in it: a
``.xla_cache`` from before they were added serves the old names (the cache
key ignores metadata) and the readers then report nothing.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, run  # noqa: E402


def phase_metrics(cell_name: str) -> dict:
    """{metric name: unit} of the phase-split metrics meant for a cell."""
    with open(os.path.join(ROOT, "benchmark", "phase_metrics.json")) as f:
        entries = json.load(f)["per_layer"]
    return {m["name"]: m["unit"] for m in entries
            if cell_name in m["workloads"]}


def with_phase_metrics(cell: dict) -> dict:
    """``cell`` (of ``manifest.load_cell``) with the phase-split metrics
    meant for it appended to its per-layer metrics."""
    extra = phase_metrics(cell["name"])
    return dict(cell,
                layer_metrics=cell["layer_metrics"] + [
                    m for m in extra if m not in cell["layer_metrics"]],
                units=dict(cell["units"], **extra))


def main(argv=None) -> int:
    load = manifest.load_cell
    manifest.load_cell = lambda *a, **kw: with_phase_metrics(load(*a, **kw))
    try:
        return run.main(list(sys.argv[1:] if argv is None else argv)
                        + ["--trace", "1"])
    finally:
        manifest.load_cell = load


if __name__ == "__main__":
    sys.exit(main())
