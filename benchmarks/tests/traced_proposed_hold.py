#!/usr/bin/env python3
"""``traced_proposed.py`` for the consumer's hold and the pulse: one traced
run of a cell with the entries of ``proposed_per_layer_hold.json`` beside the
cell's own. They read the histograms ``device_hold_us``,
``device_hold_excess_us`` and ``pulse_py_late_us``, which the program has
from PR 36 on, and so may not enter ``BENCHMARK.json`` with the PR that adds
them: the parent runs the cell they are listed for, finds nothing to read,
and ``run.py`` refuses its own line (PERF.md section 7). ``--workload`` may
name any cell: the entries are listed for it in this run.

Prints one JSON line: the result line and ``validate`` (what
``result_line.validate`` says of it under the extended spec).

    python3 benchmarks/tests/traced_proposed_hold.py --workload <name> \\
        --seed <n> [--seconds 20]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402
from harness import cells, result_line  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    spec = cells.load_spec()
    spec["per_layer"] = spec["per_layer"] + [
        dict(m, workloads=[args.workload])
        for m in cells.load_json("tests", "proposed_per_layer_hold.json")]
    line = run.run_cell(args.workload, args.seed, args.seconds, True,
                        spec=spec)
    try:
        result_line.validate(line, spec, args.workload, True)
        line["validate"] = "passes"
    except result_line.LineError as e:
        line["validate"] = f"refused: {e}"
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
