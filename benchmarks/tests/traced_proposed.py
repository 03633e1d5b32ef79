#!/usr/bin/env python3
"""One traced run of a cell with the per-layer metrics of
``proposed_per_layer.json`` added to the cell's own: the harness unchanged
(``run.run_cell`` takes the spec as an argument), BENCHMARK.json untouched.

The seven metrics wait there because ``run.py`` refuses its own line when a
listed metric is missing, and on the parent commit (no scopes, no ``dmlc.``
spans, no new histograms) their readers rightly find nothing: a PR that
listed them would make the parent's traced run exit 4 (PERF.md section 7).

Prints one JSON line: the result line, ``validate`` (what
``result_line.validate`` says of it under the extended spec) and ``report``:
device time a step by scope, the share of the step module under no scope,
the longest unscoped operations, and the host lines that hold ``dmlc.``
events.

    python3 benchmarks/tests/traced_proposed.py --workload <name> --seed <n> \\
        [--seconds 20]
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
from harness import cells, result_line, trace  # noqa: E402
from readers import _xplane  # noqa: E402

# leaves of the scope path that say what ran, not under which phase
PHASES = ("dp.unpack", "dp.loss_grad", "dp.allreduce", "dp.apply")
MODEL = ("fm.linear", "fm.gather", "fm.interaction", "fm.dense",
         "linear.margin")


def scope_key(path: str) -> str:
    """``dp.loss_grad/transpose(jvp(fm.gather))`` for a backward op of the
    gather, ``dp.apply`` for the update, ``(none)`` outside every scope."""
    phase = next((p for p in PHASES if p in path), "")
    model = next((m for m in MODEL if m in path), "")
    if model and f"transpose(jvp({model}))" in path:
        model = f"transpose(jvp({model}))"
    elif "transpose(" in path and phase == "dp.loss_grad" and not model:
        model = "transpose(...)"
    return "/".join(x for x in (phase, model) if x) or "(none)"


def report(doc, module: str) -> dict:
    mods, ops = _xplane.step_ops(doc, module)
    steps = len(mods)
    by_scope, unscoped = {}, {}
    for name, _, dur, path in ops:
        key = scope_key(path)
        by_scope[key] = by_scope.get(key, 0.0) + dur
        if key == "(none)":
            short = name.split(" = ")[0]
            unscoped[short] = unscoped.get(short, 0.0) + dur
    module_ns = sum(m[2] for m in mods)
    ops_ns = sum(by_scope.values())
    per_step = {k: v / steps / 1e6 for k, v in sorted(
        by_scope.items(), key=lambda kv: -kv[1])} if steps else {}
    return {
        "steps": steps,
        "module_ms_a_step": module_ns / steps / 1e6 if steps else None,
        "ops_ms_a_step": ops_ns / steps / 1e6 if steps else None,
        "scope_ms_a_step": per_step,
        "unscoped_share_of_ops_pct": 100.0 * by_scope.get("(none)", 0.0)
        / ops_ns if ops_ns else None,
        "unscoped_top": sorted(((k, v / steps / 1e6) for k, v in
                                unscoped.items()), key=lambda kv: -kv[1])[:8]
        if steps else [],
        "host_lines": {i: sorted({e[0] for e in evs})
                       for i, evs in doc["host"].items()
                       if any(e[0].startswith("dmlc.") for e in evs)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    spec = cells.load_spec()
    with open(os.path.join(HERE, "proposed_per_layer.json")) as f:
        spec["per_layer"] = spec["per_layer"] + json.load(f)
    line = run.run_cell(args.workload, args.seed, args.seconds, True,
                        spec=spec)
    try:
        result_line.validate(line, spec, args.workload, True)
        line["validate"] = "passes"
    except result_line.LineError as e:
        line["validate"] = f"refused: {e}"
    doc = _xplane.load(trace.find_xplane(
        cells.cache_dir(args.workload, "trace")))
    line["report"] = report(doc, "sharded_step")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
