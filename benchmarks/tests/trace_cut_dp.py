#!/usr/bin/env python3
"""Cuts ``recorded_dp4.json`` from a traced run of a four-chip cell: a few
whole steps from the middle of the traced window, in both forms the readers
take. ``trace_dump.py --cut`` looks for an epoch turnover and keeps the first
chip alone; the four-chip cell's traced window (25 steps of an epoch's 56)
may hold none, and ``module_roofline_dp`` and ``trace.busy`` want every
chip's plane.

    python3 benchmarks/tests/trace_cut_dp.py <trace_dir or .xplane.pb> \\
        recorded_dp4.json [--steps 3]

    {"scopes": the scopes form of readers/_xplane.py (first chip),
     "planes": the events form of harness/trace.py: each chip's ``XLA Ops``
               and ``XLA Modules`` and the window's annotation}

The window is narrowed to start 40% into the step before the first kept and
to end 25% into the step after the last, so an operation and a module event
straddle each edge; operation names are cut at their `` = ``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace  # noqa: E402
from readers import _xplane  # noqa: E402

MODULE = "sharded_step"


def cut(path: str, steps: int) -> dict:
    doc = _xplane.load(path)
    events = trace.load_xplane(path)
    mods = sorted(_xplane.step_modules(doc, MODULE), key=lambda m: m[1])
    first = (len(mods) - steps) // 2
    before, after = mods[first - 1], mods[first + steps]
    lo = before[1] + 0.4 * before[2]
    hi = after[1] + 0.25 * after[2]

    def touching(evs):
        return [e for e in evs if e[1] + e[2] > lo and e[1] < hi]

    def short(evs):
        return [[e[0].split(" = ")[0]] + list(e[1:]) for e in evs]

    planes = {p: {line: short(touching(events["planes"][p].get(line, [])))
                  for line in (trace.OPS_LINE, trace.MODULES_LINE)}
              for p in trace.device_planes(events)}
    planes[trace.HOST_PLANE] = {"python3": [[trace.WINDOW_EVENT, lo,
                                             hi - lo]]}
    return {
        "scopes": {
            "window": [lo, hi],
            "ops": short(touching(doc["ops"])),
            "modules": touching(doc["modules"]),
            "host": {i: kept for i, evs in doc["host"].items()
                     for kept in [[e for e in touching(evs)
                                   if e[0] != trace.WINDOW_EVENT]] if kept}},
        "planes": planes}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    xplane = args.trace if args.trace.endswith(".pb") \
        else trace.find_xplane(args.trace)
    with open(args.out, "w") as f:
        json.dump(cut(xplane, args.steps), f, separators=(",", ":"))
