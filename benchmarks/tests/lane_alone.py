#!/usr/bin/env python3
"""The input lane of a cell alone: the cell's file from the seed, drained
through the session's own ``DeviceRowBlockIter`` (read, parse, assemble with
the dedupe, ``device_put``) with no step between batches, epoch after epoch.
Rows a second of the lane against the cell's ``rows_per_s`` is the margin by
which the host path is hidden behind the step.

    python3 benchmarks/tests/lane_alone.py --workload <name> --seed <n> \\
        [--epochs 4]

Prints one JSON line an epoch (the first pays the threads' start) and one of
the lane's telemetry over the later epochs: parse time a thousand rows and,
where the format counts them, a cell; stage and put a batch; the fill share
and the distinct share of the batches sent.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402
from harness import cells  # noqa: E402


def _total(snap, kind, names, field):
    return sum(m[field] for m in snap[kind] if m["name"] in names)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal: skip the look for a chip")
    args = ap.parse_args()
    cell = cells.load_cell(cells.load_spec(), args.workload)
    devs = run.find_chip(int(cell["chips"]), not args.cpu)
    runner = run.open_program(cell["config_file"]["runner"])
    import jax
    from dmlc_core_tpu import telemetry
    s = runner.Session(cell, args.seed, len(devs))
    s.write_data(max(1, min(8, (os.cpu_count() or 2) - 1)))
    s.build()
    snaps = []
    for epoch in range(args.epochs):
        snaps.append(telemetry.snapshot(native=True))
        t0 = time.perf_counter()
        rows = batches = 0
        for batch in s.it:
            jax.block_until_ready(batch.tree())
            rows += batch.total_rows
            batches += 1
        dt = time.perf_counter() - t0
        s.it.before_first()
        print(json.dumps({"epoch": epoch, "rows": rows, "batches": batches,
                          "seconds": dt, "rows_per_s": rows / dt}),
              flush=True)
    a, b = snaps[1], telemetry.snapshot(native=True)

    def rise(kind, names, field):
        return _total(b, kind, names, field) - _total(a, kind, names, field)
    parse_us = rise("histograms", {"parse_stage_scan_us",
                                   "parse_stage_parse_us"}, "sum")
    later = (args.epochs - 1) * s.file_rows
    cells_ = rise("counters", {"parse_cells_total"}, "value")
    missing = rise("counters", {"parse_cells_missing_total"}, "value")
    present = cells_ - missing
    out = {"parse.us_per_krow": parse_us / (later / 1e3),
           "parse.ns_per_cell": 1e3 * parse_us / present if present else None,
           "missing_share": missing / cells_ if cells_ else None}
    real = rise("counters", {"device_nnz_real_total"}, "value")
    out["fill_share"] = real / max(
        rise("counters", {"device_nnz_sent_total"}, "value"), 1)
    out["distinct_share"] = rise(
        "counters", {"device_cols_distinct_total"}, "value") / max(real, 1)
    for name, hist in (("stage.us_per_batch", "device_stage_us"),
                       ("put.us_per_batch", "device_transfer_us")):
        out[name] = rise("histograms", {hist}, "sum") / max(
            rise("histograms", {hist}, "count"), 1)
    print(json.dumps(out), flush=True)
    s.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
