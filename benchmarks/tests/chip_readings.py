#!/usr/bin/env python3
"""Reads, on the chip and at a cell's own size, what the limits are set from:
for each seed the gaps of the program (lower readings), of the control (the
reference in bfloat16 in the program's place) and of each planted fault
(upper readings). One process, short files (the check's batches and one
more): training's readings need no measured window.

    python3 benchmarks/tests/chip_readings.py --workload <name> --seeds 1,2,3 \\
        [--fault-seeds 3] [--out file.jsonl]
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402
from faults import FAULTS  # noqa: E402
from harness import cells, check  # noqa: E402


def program_readings(runner, cell, seed, chips, fault=None):
    s = runner.Session(cell, seed, chips)
    fault = fault or {}
    fault.get("before_data", lambda _: None)(s)
    s.write_data(8)
    s.build()
    fault.get("after_build", lambda _: None)(s)
    got = s.first_steps()
    s.free()
    return s, got


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal: skip the look for a chip")
    args = ap.parse_args()
    spec = cells.load_spec()
    cell = copy.deepcopy(cells.load_cell(spec, args.workload))
    cell["traffic_file"]["epoch_batches"] = \
        int(cell["traffic_file"].get("check_steps", 3)) + 1
    devs = run.find_chip(int(cell["chips"]), not args.cpu)
    runner = run.open_program(cell["config_file"]["runner"])
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        s, program = program_readings(runner, cell, seed, len(devs))
        reference = s.reference_readings()
        row = {"workload": args.workload, "seed": seed,
               "reference": reference._asdict(),
               "program": check.gaps(program, reference),
               "control_bfloat16": check.gaps(
                   s.reference_readings("bfloat16"), reference)}
        if i < args.fault_seeds:
            for name in ("half_batch", "drop_last_token"):
                _, broken = program_readings(runner, cell, seed, len(devs),
                                             FAULTS[name])
                row[name] = check.gaps(broken, reference)
        text = json.dumps(row)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
