"""python3 benchmark/phase_report.py --workload <cell> --seed <n> --seconds <s>

``run.py --trace 1`` under the name the operator's notes still give it
(``.claude/skills/verify/SKILL.md``, "Phase split of a step").  Since PR 30
the phase split (forward / recompute / backward / optimizer / exchange /
unscoped, attention, loss, the two flash kernels) is among every cell's
per-layer metrics in ``BENCHMARK.json``, so this adds nothing to the traced
run.  The file stays only because a `benchmark` PR may not edit those notes;
the PR that corrects them deletes it (PERF.md, Open questions).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def main(argv=None) -> int:
    return run.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
