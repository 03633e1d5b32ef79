#!/usr/bin/env python3
"""``chip_readings.py`` for the faults of ``faults_criteo.py``, which that
script does not name: for each seed the gaps of the program, of the control
(the reference in bfloat16 in the program's place) and of each planted fault
of the lane, at the cell's own size, each with the cell's exact numbers
beside it (``epoch_nnz_gap`` reads a lost or an invented entry at once).

    python3 benchmarks/tests/chip_readings_criteo.py --workload <name> \\
        --seeds 1,2,3 [--fault-seeds 1] [--out file.jsonl]
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import chip_readings  # puts benchmarks/ and the checkout on sys.path
import run
from faults_criteo import FAULTS
from harness import cells, check


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal: skip the look for a chip")
    args = ap.parse_args()
    cell = copy.deepcopy(cells.load_cell(cells.load_spec(), args.workload))
    cell["traffic_file"]["epoch_batches"] = \
        int(cell["traffic_file"].get("check_steps", 3)) + 1
    devs = run.find_chip(int(cell["chips"]), not args.cpu)
    runner = run.open_program(cell["config_file"]["runner"])
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        s, program = chip_readings.program_readings(runner, cell, seed,
                                                    len(devs))
        reference = s.reference_readings()
        row = {"workload": args.workload, "seed": seed,
               "reference": reference._asdict(),
               "program": dict(check.gaps(program, reference),
                               **s.exact_numbers()),
               "control_bfloat16": check.gaps(
                   s.reference_readings("bfloat16"), reference)}
        if i < args.fault_seeds:
            for name, fault in FAULTS.items():
                b, broken = chip_readings.program_readings(
                    runner, cell, seed, len(devs), fault)
                row[name] = dict(check.gaps(broken, reference),
                                 **b.exact_numbers())
        row["memory_peak_bytes"] = run.memory_peak(devs)
        text = json.dumps(row)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
