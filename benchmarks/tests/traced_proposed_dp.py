#!/usr/bin/env python3
"""``traced_proposed.py`` for a cell of several chips: one traced run with
the entries of ``proposed_per_layer_dp.json`` (which read the counter
``model_step_allreduce_bytes_total`` and so may not enter ``BENCHMARK.json``
with the PR that adds the counter) beside the cell's own, and the same report
of the step's device time by named scope, first chip.

    python3 benchmarks/tests/traced_proposed_dp.py --workload <name> \\
        --seed <n> [--seconds 20]
"""

from __future__ import annotations

import argparse
import json
import sys

import traced_proposed  # puts benchmarks/ and the checkout on sys.path
import run
from harness import cells, result_line, trace
from readers import _xplane


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    spec = cells.load_spec()
    spec["per_layer"] = spec["per_layer"] + cells.load_json(
        "tests", "proposed_per_layer_dp.json")
    line = run.run_cell(args.workload, args.seed, args.seconds, True,
                        spec=spec)
    try:
        result_line.validate(line, spec, args.workload, True)
        line["validate"] = "passes"
    except result_line.LineError as e:
        line["validate"] = f"refused: {e}"
    doc = _xplane.load(trace.find_xplane(
        cells.cache_dir(args.workload, "trace")))
    line["report"] = traced_proposed.report(doc, "sharded_step")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
