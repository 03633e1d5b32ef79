#!/usr/bin/env python3
"""The readings of the cells that read a part over S3, at the cell's own
size: for each seed the whole cell (``run.run_cell``, a short window that
still holds an epoch's end), the control (the reference in bfloat16 in the
program's place) and each planted fault of ``faults_s3.py``, every one with
the numbers compared beside their limits. One process: every origin after
the first listens where the first did (``runners/fm_s3.py``).

    python3 benchmarks/tests/chip_readings_s3.py --workload <name> \\
        --seeds 1,2 [--fault-seeds 1] [--seconds 9] [--out file.jsonl]
"""

from __future__ import annotations

import argparse
import json
import sys

import chip_readings  # noqa: F401  puts benchmarks/ and the checkout on sys.path
import run
from faults_s3 import FAULTS
from harness import cells, check


def _numbers(line):
    return {"correct": line["correct"],
            **{k: v["value"] for k, v in line["checks"].items()}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=9.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal: skip the look for a chip")
    args = ap.parse_args()
    spec = cells.load_spec()
    cell = cells.load_cell(spec, args.workload)
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        line = run.run_cell(args.workload, seed, args.seconds, False,
                            require_chip=not args.cpu, spec=spec)
        row = {"workload": args.workload, "seed": seed,
               "part": line["notes"]["setup"]["part"],
               "program": _numbers(line),
               "rows_per_s": line["metrics"]["rows_per_s"]["value"],
               "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
        runner = cells.load_module("runners", cell["config_file"]["runner"])
        s = runner.Session(cell, seed, int(cell["chips"]))
        s.write_data(8)
        try:
            reference = s.reference_readings()
            row["control_bfloat16"] = check.judge(
                check.gaps(s.reference_readings("bfloat16"), reference),
                cell["config_file"]["limits"])
        finally:
            s.close()
        if i < args.fault_seeds:
            for name, fault in FAULTS.items():
                row[name] = _numbers(run.run_cell(
                    args.workload, seed, args.seconds, False,
                    require_chip=not args.cpu, spec=spec, faults=fault))
        text = json.dumps(row)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
