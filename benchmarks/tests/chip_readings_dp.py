#!/usr/bin/env python3
"""``chip_readings.py`` for the fault of ``faults_dp.py``, which that script
does not name: for each seed the gaps of the program with one shard's part
left out of the all-reduce, at the cell's own size, and ``replica_gap`` beside
them (0: the replicas agree with each other on the wrong update).

    python3 benchmarks/tests/chip_readings_dp.py --workload <name> \\
        --seeds 1,2,3 [--out file.jsonl]
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import chip_readings  # puts benchmarks/ and the checkout on sys.path
import run
from faults_dp import FAULTS
from harness import cells, check


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
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
    for seed in (int(x) for x in args.seeds.split(",")):
        row = {"workload": args.workload, "seed": seed}
        for name, fault in FAULTS.items():
            s, broken = chip_readings.program_readings(
                runner, cell, seed, len(devs), fault)
            row[name] = dict(check.gaps(broken, s.reference_readings()),
                             **s.exact_numbers())
        row["memory_peak_bytes"] = run.memory_peak(devs)
        text = json.dumps(row)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
