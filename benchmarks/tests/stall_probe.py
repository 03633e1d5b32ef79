#!/usr/bin/env python3
"""Looks at stray stalls with the program's own spans: one run of a cell
wholly under the profiler (``python_tracer_level`` 0), not a measurement of
the cell. For each step that took over 1.5x the median it says what the
device plane shows in it: a step module that ran long (and the operation
with the largest excess over its own median), or an idle gap (and which
``dmlc.``/``bench.`` span each host thread was in, by time covered).

    python3 benchmarks/tests/stall_probe.py --workload kdd2012-fm.libfm \\
        --seed <n> [--seconds 20] [--factor 1.5]

A step is the time between the ends of consecutive ``bench.loss_sync``
annotations, on the profiler's clock.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402
from harness import cells, trace  # noqa: E402
from readers import _xplane  # noqa: E402

MODULE = "sharded_step"


def covering(doc, lo, hi):
    """line index -> {span name: ms of [lo, hi] covered}."""
    out = {}
    for i, evs in doc["host"].items():
        cover = {}
        for name, s, d in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a and name != trace.WINDOW_EVENT:
                cover[name] = cover.get(name, 0.0) + (b - a) / 1e6
        if cover:
            out[i] = {k: round(v, 3) for k, v in sorted(
                cover.items(), key=lambda kv: -kv[1])}
    return out


def host_events_in(path: str, lo: float, hi: float, n: int = 12) -> list:
    """Every host-plane event that overlaps [lo, hi], the runtime's own
    annotations included: [[line index, name, ms covered], ...], longest
    first."""
    from jax.profiler import ProfileData
    cover = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                a = max(e.start_ns, lo)
                b = min(e.start_ns + e.duration_ns, hi)
                if b > a:
                    key = (i, e.name[:80])
                    cover[key] = cover.get(key, 0.0) + (b - a) / 1e6
    return [[i, name, round(ms, 3)] for (i, name), ms in sorted(
        cover.items(), key=lambda kv: -kv[1])[:n]]


def stalls(doc, factor: float) -> dict:
    lo, hi = doc["window"]
    ends = sorted(s + d for evs in doc["host"].values() for n, s, d in evs
                  if n == "bench.loss_sync" and lo <= s and s + d <= hi)
    steps = list(zip(ends, ends[1:]))
    durs = [b - a for a, b in steps]
    med = statistics.median(durs)
    mods = _xplane.step_modules(doc, MODULE)
    mod_med = statistics.median(m[2] for m in mods)
    op_durs = {}
    for name, _, d, _ in doc["ops"]:
        op_durs.setdefault(name.split(" = ")[0], []).append(d)
    op_med = {k: statistics.median(v) for k, v in op_durs.items()}
    gaps = _xplane.idle_gaps(doc)
    found = []
    for k, (a, b) in enumerate(steps):
        if b - a <= factor * med:
            continue
        inside = [m for m in mods if m[1] < b and m[1] + m[2] > a]
        entry = {"step": k + 1, "ms": (b - a) / 1e6,
                 "module_ms": [m[2] / 1e6 for m in inside]}
        if any(m[2] > 1.2 * mod_med for m in inside):
            excess = sorted(
                ((d - op_med[n.split(" = ")[0]], n.split(" = ")[0], scope)
                 for n, s, d, scope in doc["ops"] if a <= s < b),
                reverse=True)[:3]
            entry["device"] = "long operation"
            entry["ops_over_their_median_ms"] = [
                [n, x / 1e6, scope] for x, n, scope in excess]
        else:
            g = max(((min(y, b) - max(x, a), max(x, a), min(y, b))
                     for x, y in gaps if min(y, b) > max(x, a)),
                    default=(0.0, a, a))
            entry["device"] = "gap"
            entry["longest_gap_ms"] = g[0] / 1e6
            entry["gap_ns"] = [g[1], g[2]]
            entry["threads_in_gap"] = covering(doc, g[1], g[2])
        found.append(entry)
    return {"steps": len(steps), "median_step_ms": med / 1e6,
            "median_module_ms": mod_med / 1e6,
            "over": factor, "stalled": found,
            "idle_pct": 100.0 * sum(y - x for x, y in gaps) / (hi - lo)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--factor", type=float, default=1.5)
    args = ap.parse_args(argv)
    cell = cells.load_cell(cells.load_spec(), args.workload)
    devs = run.find_chip(int(cell["chips"]), True)
    runner = run.open_program(cell["config_file"]["runner"])
    s = runner.Session(cell, args.seed, len(devs))
    s.write_data(max(1, min(8, (os.cpu_count() or 2) - 1)))
    s.build()
    s.first_steps()
    tdir = cells.cache_dir(cell["name"], "stall_probe")
    sec = runner.traced_drive(s, args.seconds, tdir)
    s.free()
    xplane = trace.find_xplane(tdir)
    out = stalls(_xplane.load(xplane), args.factor)
    for entry in out["stalled"]:
        if "gap_ns" in entry:
            entry["host_events_in_gap"] = host_events_in(
                xplane, *entry.pop("gap_ns"))
    out["rows_per_s_under_the_profiler"] = sec.rows / sec.seconds
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
