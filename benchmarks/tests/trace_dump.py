#!/usr/bin/env python3
"""Two things done by hand with a chip trace.

Show what a trace holds: the planes, the string stats of the first device
operations' metadata (that is how ``tf_op`` was found to carry the
``jax.named_scope`` path), and the host lines that hold ``dmlc.`` and
``bench.`` events:

    python3 benchmarks/tests/trace_dump.py <trace_dir or .xplane.pb>

Cut a recorded sample in the scopes form of ``readers/_xplane.py``: the
steps around the first epoch turnover, with the window narrowed so that it
starts and ends inside a step (an operation and a module event straddle each
edge); operation names are cut at their `` = ``:

    python3 benchmarks/tests/trace_dump.py <...> --cut recorded_scopes.json \\
        [--steps 3]
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

TURNOVER = "dmlc.device.epoch_turnover"
MODULE = "sharded_step"


def show(path: str) -> dict:
    from jax.profiler import ProfileData
    doc = _xplane.load(path)
    meta = _xplane.metadata_stats(path)
    first = sorted(meta)[0] if meta else None
    return {
        "xplane_bytes": os.path.getsize(path),
        "planes": [p.name for p in ProfileData.from_file(path).planes],
        "metadata_of_first_ops": {
            ev[:80]: {k: v[:160] for k, v in st.items()}
            for ev, st in list(meta[first].items())[:10]} if first else {},
        "host_lines": {i: sorted({e[0] for e in evs})
                       for i, evs in doc["host"].items()},
        "ops": len(doc["ops"]), "modules": len(doc["modules"]),
        "window_s": (doc["window"][1] - doc["window"][0]) / 1e9}


def cut(path: str, steps: int) -> dict:
    doc = _xplane.load(path)
    mods = _xplane.step_modules(doc, MODULE)
    turn = min(e[1] for evs in doc["host"].values() for e in evs
               if e[0] == TURNOVER)
    after = next(i for i, m in enumerate(mods) if m[1] > turn)
    first = after - steps // 2  # first whole step kept
    lo = mods[first - 1][1] + 0.4 * mods[first - 1][2]
    hi = mods[first + steps][1] + 0.25 * mods[first + steps][2]

    def touching(events):
        return [e for e in events if e[1] + e[2] > lo and e[1] < hi]

    return {
        "window": [lo, hi],
        "ops": [[n.split(" = ")[0], s, d, scope]
                for n, s, d, scope in touching(doc["ops"])],
        "modules": touching(doc["modules"]),
        "host": {i: kept for i, evs in doc["host"].items()
                 for kept in [[e for e in touching(evs)
                               if e[0] != trace.WINDOW_EVENT]] if kept}}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--cut")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    xplane = args.trace if args.trace.endswith(".pb") \
        else trace.find_xplane(args.trace)
    if args.cut:
        with open(args.cut, "w") as f:
            json.dump(cut(xplane, args.steps), f, separators=(",", ":"))
    else:
        print(json.dumps(show(xplane), indent=1))
