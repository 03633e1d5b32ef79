"""Builds and validates a run's last line.

The driver reads exactly: ``correct``, ``attempted``, ``failed``, ``metrics``
(each metric of the cell for this trace mode as ``{"value", "unit"}``) and
``device`` (``platform``, ``kind``, ``count``, ``memory_peak_bytes``; in a
traced run also ``window_s`` and ``busy_s`` with 0 < busy_s <= window_s), and
optionally ``breakdown`` in a traced run. ``checks`` (each number compared
beside its limit) comes last. ``run.py`` validates its own line and exits
non-zero rather than print one that fails.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, Optional

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")


class LineError(ValueError):
    """The line would be refused by the driver."""


def _number(x: Any) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def expected_metrics(spec: Dict, workload: str, traced: bool
                     ) -> Dict[str, Dict]:
    """The metrics ``BENCHMARK.json`` lists for this cell and trace mode,
    by name. A metric without a ``workloads`` key belongs to every cell."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return {m["name"]: m for m in group
            if "workloads" not in m or workload in m["workloads"]}


def build(correct: bool, attempted: int, failed: int,
          metrics: Dict[str, Dict], device: Dict,
          breakdown: Optional[Dict], notes: Dict, checks: Dict) -> Dict:
    """``notes`` is for readers of the record (the driver ignores it);
    ``checks``, the numbers compared beside their limits, comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["notes"] = notes
    line["checks"] = checks
    return line


def validate(line: Dict, spec: Dict, workload: str, traced: bool) -> None:
    """Raise ``LineError`` naming the first thing the driver would refuse.
    A per-layer metric whose reader found nothing to read is absent from the
    line (never present as 0), and the line is refused: the metric's
    ``workloads`` promised it for this cell."""
    if not isinstance(line, dict):
        raise LineError("the line is not a JSON object")
    for k in REQUIRED:
        if k not in line:
            raise LineError(f"key {k!r} is missing")
    if not isinstance(line["correct"], bool):
        raise LineError("correct is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            raise LineError(f"{k} is not a count")
    if line["failed"] > line["attempted"]:
        raise LineError("failed exceeds attempted")
    want = expected_metrics(spec, workload, traced)
    got = line["metrics"]
    if not isinstance(got, dict):
        raise LineError("metrics is not an object")
    for name, m in want.items():
        if name not in got:
            raise LineError(f"metric {name!r} is missing")
        entry = got[name]
        if not isinstance(entry, dict) or not _number(entry.get("value")):
            raise LineError(f"metric {name!r} has no finite value")
        if entry.get("unit") != m["unit"]:
            raise LineError(f"metric {name!r} has unit {entry.get('unit')!r},"
                            f" BENCHMARK.json says {m['unit']!r}")
        if not traced and entry["value"] <= 0:
            raise LineError(f"end-to-end metric {name!r} is not above 0")
        if m["unit"] == "%" and ("roofline" in name or "mfu" in name) \
                and not 0 < entry["value"] <= 105:
            raise LineError(f"share {name!r} = {entry['value']} is outside "
                            f"(0, 105]")
    for name in got:
        if name not in want:
            raise LineError(f"metric {name!r} is not one of this cell's "
                            f"{'per-layer' if traced else 'end-to-end'} "
                            f"metrics")
    dev = line["device"]
    if not isinstance(dev, dict):
        raise LineError("device is not an object")
    for k in DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ()):
        if k not in dev:
            raise LineError(f"device.{k} is missing")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"],
                                                              str):
        raise LineError("device.platform and device.kind are strings")
    if not isinstance(dev["count"], int) or dev["count"] < 1:
        raise LineError("device.count is not a positive count")
    if not _number(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise LineError("device.memory_peak_bytes is not above 0")
    if traced:
        w, b = dev["window_s"], dev["busy_s"]
        if not _number(w) or not _number(b):
            raise LineError("device.window_s and device.busy_s are numbers")
        if w <= 0:
            raise LineError("device.window_s is not above 0")
        if b <= 0:
            raise LineError("device.busy_s is 0: no operation was found on "
                            "the device in the traced window")
        if b > w:
            raise LineError(f"device.busy_s {b} exceeds window_s {w}: "
                            f"overlapping lines were summed")
    if "breakdown" in line:
        if not traced:
            raise LineError("breakdown belongs to a traced run")
        bd = line["breakdown"]
        for k in ("device_ops", "idle_gaps"):
            rows = bd.get(k) if isinstance(bd, dict) else None
            if not isinstance(rows, list) or len(rows) > 10:
                raise LineError(f"breakdown.{k} is not a list of at most 10")
            for r in rows:
                if (not isinstance(r, list) or len(r) != 2
                        or not isinstance(r[0], str) or not _number(r[1])):
                    raise LineError(f"breakdown.{k} holds {r!r}, not "
                                    f"[name, seconds]")
    try:
        json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise LineError(f"the line does not serialise: {e}")


def dumps(line: Dict) -> str:
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
