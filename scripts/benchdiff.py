#!/usr/bin/env python3
"""Bench regression ledger: compare runs from bench_history.jsonl.

Every ``bench.py`` run appends one normalized record (git SHA, host
fingerprint, lane metrics, stall verdict, resource envelope) to the
ledger; this tool turns that trajectory into a verdict:

    benchdiff.py --a -2 --b -1            # previous vs latest
    benchdiff.py --b -1 --trailing 5      # latest vs trailing median
    benchdiff.py --a r03 --b 84eb0fb      # round tag vs sha prefix
    benchdiff.py import --file BENCH_r01.json --sha <sha> --round 1

Exit code 0 = every shared metric inside the noise band, 1 = at least
one regression outside it, 2 = usage error.

Noise bands follow the recipe the in-run guards (PR 5's telemetry
overhead guard, PR 7's scaling floor) settled on: a difference only
counts when it exceeds what the host's own variation explains.  Here
the variation is estimated from the ledger itself — the trailing
coefficient of variation per metric when ``--trailing`` history exists
— and floored by ``--band`` (default 0.25: the hosts so far swung up
to ±40% minute to minute, so small deltas between single runs are
weather, not signal).  A same-record self-compare is
exactly ratio 1.0 everywhere and always exits 0.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join(REPO, "bench_history.jsonl")
SCHEMA = 1

# lane leaves that are comparable across runs (all higher-is-better;
# ratios like replay_speedup/ranged_vs_local count as metrics too — a
# regression in a ratio is a regression in the claim built on it)
GOOD_LEAVES = {
    "rows_per_sec", "mb_per_sec", "epoch1_rows_per_sec",
    "epoch2_rows_per_sec", "replay_speedup", "vs_recd_host",
    "records_per_sec", "native_records_per_sec",
    "write_records_per_sec", "read_records_per_sec",
    "local_rows_per_sec", "sequential_rows_per_sec",
    "ranged_rows_per_sec", "origin_ceiling_rows_per_sec",
    "mock_ceiling_rows_per_sec", "ranged_vs_sequential",
    "ranged_vs_local", "achieved_qps",
    "hbm_ingest_rows_per_sec", "overlap_ratio",
    "hbm_ingest_bw_util", "hbm_ingest_bw_util_best",
    "steps_per_sec", "sustained_qps",
}

# lane leaves that are comparable but LOWER-is-better (latencies,
# recovery times): flat_metrics carries them and compare() inverts the
# ratio so "REGRESSION" still means "got worse"
LOW_LEAVES = {
    "recovery_s", "open_loop_p99_ms", "slo_burn_clean",
}

# extras entries that are lanes worth carrying into the ledger
LANE_KEYS = ("cache_lane", "remote_lane", "csv_lane", "libfm_lane",
             "recordio_roundtrip", "rec_lane", "crec_lane", "recd_lane",
             "host_lane_rates", "thread_scaling", "serving_lane",
             "device_lane", "mesh_lane")


def lanes_from_extras(extras: dict) -> dict:
    """The comparable slice of a bench run's ``extras`` (numbers only —
    error strings and nested diagnostics are dropped)."""
    lanes = {}
    for key in LANE_KEYS:
        v = extras.get(key)
        if not isinstance(v, dict):
            continue
        flat = {k: x for k, x in v.items()
                if isinstance(x, (int, float)) and not isinstance(x, bool)}
        if flat:
            lanes[key] = flat
    return lanes


def make_record(result: dict, *, git_sha=None, git_dirty=None, host=None,
                env_overrides=None, host_resources=None, smoke=False,
                argv=None, round_no=None, ts=None, source=None) -> dict:
    """One normalized ledger record from a bench result line
    (``{"metric", "value", "unit", "vs_baseline", "extras"}``)."""
    extras = result.get("extras") or {}
    return {
        "schema": SCHEMA,
        "ts": ts if ts is not None else time.time(),
        "round": round_no,
        "git_sha": git_sha,
        "git_dirty": git_dirty,
        "host": host,
        "smoke": bool(smoke),
        "argv": argv,
        "env_overrides": env_overrides,
        "source": source,
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "stall_verdict": extras.get("bottleneck"),
        "device_unavailable": bool(extras.get("device_unavailable")),
        "lanes": lanes_from_extras(extras),
        "host_resources": host_resources,
    }


def append_record(record: dict, history: str) -> None:
    """Append one record to the ledger (one JSON object per line)."""
    with open(history, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def load_history(path: str) -> list:
    """Parse the ledger; unparsable lines are skipped with a warning
    (a half-written tail from a crashed run must not sink the diff)."""
    records = []
    if not os.path.exists(path):
        return records
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                print(f"# benchdiff: skipping unparsable ledger line "
                      f"{i + 1}", file=sys.stderr)
    return records


def resolve(records: list, ref: str) -> dict:
    """A record by index (``-1`` latest), sha prefix, round tag
    (``r3``/``round:3``), or ``@file.json`` (a ledger record or a raw
    bench output line)."""
    if ref.startswith("@"):
        with open(ref[1:]) as f:
            doc = json.load(f)
        if "metric" in doc and "schema" not in doc:
            return make_record(doc, source=ref[1:])
        return doc
    try:
        return records[int(ref)]
    except (ValueError, IndexError):
        pass
    if ref.lower().startswith("round:") or (
            ref[:1] in "rR" and ref[1:].isdigit()):
        n = int(ref.split(":")[-1].lstrip("rR"))
        for rec in reversed(records):
            if rec.get("round") == n:
                return rec
        raise SystemExit(f"benchdiff: no ledger record for round {n}")
    matches = [r for r in records
               if (r.get("git_sha") or "").startswith(ref)]
    if not matches:
        raise SystemExit(f"benchdiff: no ledger record matches {ref!r}")
    return matches[-1]


def flat_metrics(record: dict) -> dict:
    """``{"value": headline, "lane.leaf": v, ...}`` for one record."""
    out = {}
    if isinstance(record.get("value"), (int, float)):
        out["value"] = float(record["value"])
    for lane, leaves in (record.get("lanes") or {}).items():
        for leaf, v in leaves.items():
            if lane == "thread_scaling" or leaf in GOOD_LEAVES or \
                    leaf in LOW_LEAVES or lane == "host_lane_rates":
                out[f"{lane}.{leaf}"] = float(v)
    return out


def trailing_cv(records: list, metric: str) -> float:
    """Coefficient of variation of ``metric`` across ``records`` (0.0
    below 3 samples — two points cannot say what noise looks like)."""
    vals = [flat_metrics(r).get(metric) for r in records]
    vals = [v for v in vals if v]
    if len(vals) < 3:
        return 0.0
    mean = statistics.mean(vals)
    if mean == 0:
        return 0.0
    return statistics.pstdev(vals) / abs(mean)


def compare(a: dict, b: dict, band: float, trail: list) -> int:
    """Print the metric table; return the number of regressions."""
    am, bm = flat_metrics(a), flat_metrics(b)
    shared = sorted(set(am) & set(bm))
    if not shared:
        print("benchdiff: no shared metrics between the two records",
              file=sys.stderr)
        return 0
    label_a = a.get("git_sha") or a.get("source") or "a"
    label_b = b.get("git_sha") or b.get("source") or "b"
    print(f"# A={str(label_a)[:12]} (round {a.get('round')})  "
          f"B={str(label_b)[:12]} (round {b.get('round')})  "
          f"floor-band ±{band:.0%}")
    regressions = 0
    for m in shared:
        va, vb = am[m], bm[m]
        if va == 0:
            continue
        ratio = vb / va
        if m.rpartition(".")[2] in LOW_LEAVES:
            # lower-is-better leaf (recovery time): invert so ratio<1
            # still reads "got worse"
            ratio = va / vb if vb else 0.0
        eff_band = max(band, 2.0 * trailing_cv(trail, m))
        verdict = "ok"
        if ratio < 1.0 - eff_band:
            verdict = "REGRESSION"
            regressions += 1
        elif ratio > 1.0 + eff_band:
            verdict = "improved"
        print(f"{m:48s} {va:14.1f} -> {vb:14.1f}  x{ratio:6.3f} "
              f"(band ±{eff_band:.0%}) {verdict}")
    print(f"# {len(shared)} shared metrics, {regressions} regression(s)")
    return regressions


# ---------------------------------------------------------------------------
# legacy import: BENCH_r0N.json driver files -> ledger records
# ---------------------------------------------------------------------------
def git_commit_ts(sha: str) -> "float | None":
    try:
        out = subprocess.run(["git", "show", "-s", "--format=%ct", sha],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=30)
        if out.returncode == 0:
            return float(out.stdout.strip())
    except (OSError, ValueError, subprocess.TimeoutExpired):
        pass
    return None


def run_import(args) -> int:
    """``import`` subcommand: normalize one historical driver bench file
    (``{"n", "cmd", "rc", "tail", "parsed"}``) into the ledger under its
    historical sha — the day-one trajectory backfill."""
    with open(args.file) as f:
        doc = json.load(f)
    parsed = doc.get("parsed")
    if not parsed:
        raise SystemExit(f"benchdiff: {args.file} carries no parsed "
                         f"bench line")
    record = make_record(
        parsed, git_sha=args.sha, git_dirty=False,
        round_no=args.round if args.round is not None else doc.get("n"),
        ts=git_commit_ts(args.sha) or os.path.getmtime(args.file),
        smoke=False, source=os.path.basename(args.file))
    append_record(record, args.history)
    print(f"benchdiff: imported {args.file} as round "
          f"{record['round']} @ {args.sha[:12]}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare bench runs from the regression ledger")
    sub = ap.add_subparsers(dest="cmd")

    imp = sub.add_parser("import", help="import a legacy BENCH_r file")
    imp.add_argument("--file", required=True)
    imp.add_argument("--sha", required=True)
    imp.add_argument("--round", type=int, default=None)
    imp.add_argument("--history", default=DEFAULT_HISTORY)
    imp.set_defaults(fn=run_import)

    ap.add_argument("--history", default=DEFAULT_HISTORY)
    ap.add_argument("--a", dest="ref_a", default=None,
                    help="baseline record (default: the record before "
                         "--b, or the trailing median with --trailing)")
    ap.add_argument("--b", dest="ref_b", default="-1",
                    help="candidate record (default: latest)")
    ap.add_argument("--trailing", type=int, default=0,
                    help="compare --b against the median of the N "
                         "records before it (per metric)")
    ap.add_argument("--band", type=float, default=0.25,
                    help="floor noise band as a fraction (default 0.25; "
                         "widened per metric by 2x the trailing CV)")
    ap.add_argument("--list", action="store_true",
                    help="list ledger records and exit")

    args = ap.parse_args(argv)
    if getattr(args, "fn", None):
        return args.fn(args)

    records = load_history(args.history)
    if args.list:
        for i, r in enumerate(records):
            print(f"[{i - len(records):3d}] round={r.get('round')} "
                  f"sha={str(r.get('git_sha'))[:12]} "
                  f"metric={r.get('metric')} value={r.get('value')} "
                  f"smoke={r.get('smoke')}")
        return 0
    if not records and not (args.ref_b or "").startswith("@"):
        print(f"benchdiff: empty ledger {args.history}", file=sys.stderr)
        return 2
    b = resolve(records, args.ref_b)
    trail = []
    # records strictly BEFORE the candidate: the trailing window and the
    # default baseline must never include runs made after it (including
    # the very regression under investigation)
    before = records[:records.index(b)] if b in records else list(records)
    if args.trailing:
        trail = before[-args.trailing:]
        if not trail:
            print("benchdiff: no trailing history", file=sys.stderr)
            return 2
        # synthetic baseline: per-metric median of the trailing window
        merged = {}
        for m in flat_metrics(b):
            vals = [flat_metrics(r).get(m) for r in trail]
            vals = [v for v in vals if v is not None]
            if vals:
                merged[m] = statistics.median(vals)
        a = {"git_sha": f"trailing-{len(trail)}-median",
             "round": None, "value": merged.pop("value", None),
             "lanes": {}}
        for m, v in merged.items():
            lane, _, leaf = m.partition(".")
            a["lanes"].setdefault(lane, {})[leaf] = v
    elif args.ref_a is not None:
        a = resolve(records, args.ref_a)
    else:
        if not before:
            print("benchdiff: no earlier record to compare against",
                  file=sys.stderr)
            return 0 if b in records else 2
        a = before[-1]
    regressions = compare(a, b, args.band, trail)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
