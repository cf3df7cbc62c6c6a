"""``bench/run.py`` with the per-layer metrics of ``bench/program_metrics.json``
added to their cells, for the builder: same arguments, same result line.

    python3 bench/run_program_metrics.py --workload <cell> --seed <n> --seconds <s> --trace 1

Those metrics read spans, scopes and launch counts of the program that a
program from before PR 24 does not have. ``run.py`` calls a traced run not
correct where a reader finds nothing, so they cannot enter ``BENCHMARK.json``
in a PR whose parent lacks what they read (PERF.md, Open questions); the
driver runs ``run.py``, never this. The entries are kept as they would be
appended to ``per_layer``, and their readers are ``bench/metrics/<name>.py``
like any other's."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import loader  # noqa: E402


def main(argv=None):
    cell_of = loader.cell

    def cell(name, root=None):
        out = cell_of(name, root=root)
        extra = loader.load_json("program_metrics.json", root=root)["per_layer"]
        out["per_layer"] = out["per_layer"] + [m for m in extra if name in m["workloads"]]
        return out

    loader.cell = cell
    try:
        return bench_run.main(argv)
    finally:
        loader.cell = cell_of


if __name__ == "__main__":
    sys.exit(main())
