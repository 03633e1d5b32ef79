"""From a profiler trace to numbers: device busy and idle time, the device
operations that took most time, the step module's device time, and the
longest idle gaps named by what the host was doing.

``load_xplane`` turns an ``.xplane.pb`` into a plain dict (``events`` form)
with nothing but jax; every reduction works on that form, so a recorded trace
kept as JSON tests the same code a chip run uses.

events form::

    {"planes": {plane_name: {line_name: [[event_name, start_ns, dur_ns], ...]}}}

What a v5e trace looks like (read by hand from this PR's first chip run, see
PERF.md): one plane per chip named ``/device:TPU:<n>``; its line ``XLA Ops``
carries one event per executed HLO operation, ``XLA Modules`` one per executed
program (``jit_<fn>(<fingerprint>)``) and ``Steps`` one per step group. These
lines overlap each other in time, so busy time is the union of the intervals
of ONE line, ``XLA Ops``, clipped to the window. Host threads are the lines of
``/host:CPU``; the benchmark's own ``TraceAnnotation``s (names starting with
``bench.``) are on the profiler's clock there, the same clock as the device
planes.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_EVENT = "bench.trace_window"
SPAN_PREFIX = "bench."
NAME_CHARS = 120  # an HLO operation's full text runs to hundreds


class TraceError(RuntimeError):
    """The trace cannot give the number asked of it."""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"the profiler left no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host_prefix: str = SPAN_PREFIX) -> Dict:
    """Device planes whole; of the host plane only the benchmark's own
    annotations (a host plane holds hundreds of thousands of events)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List]] = {}
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not device and plane.name != HOST_PLANE:
            continue
        lines: Dict[str, List] = {}
        for line in plane.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(keep_host_prefix)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        planes[plane.name] = lines
    return {"planes": planes}


def inventory(events: Dict) -> Dict:
    """Plane -> line -> (event count, first names): what to read by hand."""
    return {p: {l: [len(evs), sorted({e[0] for e in evs})[:8]]
                for l, evs in lines.items()}
            for p, lines in events["planes"].items()}


def window_of(events: Dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's traced sub-window, taken from
    its own annotation on the profiler's clock."""
    for evs in events["planes"].get(HOST_PLANE, {}).values():
        for name, start, dur in evs:
            if name == WINDOW_EVENT:
                return start, start + dur
    raise TraceError(f"no {WINDOW_EVENT!r} annotation in the host plane")


def _clip(evs: List, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    out = []
    for name, start, dur in evs:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b, name))
    return out


def _union(intervals: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b, _ in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def device_planes(events: Dict) -> List[str]:
    return sorted(p for p in events["planes"] if p.startswith(DEVICE_PREFIX)
                  and events["planes"][p].get(OPS_LINE))


def busy(events: Dict, chips: int) -> Dict:
    """``window_s`` and ``busy_s``: the union of the ``XLA Ops`` intervals
    inside the window, averaged over the chips used."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    if len(planes) < chips:
        raise TraceError(f"the trace holds {len(planes)} device planes with "
                         f"an {OPS_LINE!r} line, the cell uses {chips}")
    per_chip = []
    for p in planes[:chips]:
        spans = _union(_clip(events["planes"][p][OPS_LINE], lo, hi))
        per_chip.append(sum(b - a for a, b in spans))
    busy_s = sum(per_chip) / len(per_chip) / 1e9
    window_s = (hi - lo) / 1e9
    if busy_s <= 0:
        raise TraceError("no device operation ran inside the traced window")
    if busy_s > window_s:
        raise TraceError(f"busy {busy_s} s exceeds the window {window_s} s")
    return {"window_s": window_s, "busy_s": busy_s}


def top_ops(events: Dict, n: int = 10) -> List[List]:
    """The device operations that took most time in the window, on the
    first chip: [[name, seconds], ...]."""
    lo, hi = window_of(events)
    total: Dict[str, float] = {}
    for a, b, name in _clip(
            events["planes"][device_planes(events)[0]][OPS_LINE], lo, hi):
        total[name] = total.get(name, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:NAME_CHARS], sec / 1e9] for name, sec in ranked]


def module_time(events: Dict, match: str) -> Dict:
    """Device time of the program whose ``XLA Modules`` event names contain
    ``match``: events wholly inside the window, on the first chip."""
    lo, hi = window_of(events)
    plane = events["planes"][device_planes(events)[0]]
    evs = [(s, d, n) for n, s, d in plane.get(MODULES_LINE, [])
           if match in n and s >= lo and s + d <= hi]
    if not evs:
        return {"count": 0, "seconds": 0.0, "names": []}
    return {"count": len(evs), "seconds": sum(d for _, d, _ in evs) / 1e9,
            "names": sorted({n for _, _, n in evs})}


def idle_gaps(events: Dict, n: int = 10) -> List[List]:
    """The longest intervals of the window in which no operation ran on the
    first chip, each named by the benchmark annotation that covers most of
    it (``host:unannotated`` where none does), summed by name."""
    lo, hi = window_of(events)
    plane = events["planes"][device_planes(events)[0]]
    spans = _union(_clip(plane[OPS_LINE], lo, hi))
    gaps = []
    cur = lo
    for a, b in spans:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    host = [(s, s + d, name)
            for evs in events["planes"].get(HOST_PLANE, {}).values()
            for name, s, d in evs if name != WINDOW_EVENT]
    host.sort()
    starts = [h[0] for h in host]
    total: Dict[str, float] = {}
    for a, b in gaps:
        best_name, best = "host:unannotated", 0.0
        i = bisect.bisect_left(starts, a)
        # annotations are short and do not nest deeply: look a few back
        for s, e, name in host[max(0, i - 8):]:
            if s >= b:
                break
            cover = min(e, b) - max(s, a)
            if cover > best:
                best_name, best = name, cover
        total[best_name] = total.get(best_name, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / 1e9] for name, sec in ranked]


def breakdown(events: Dict) -> Dict:
    return {"device_ops": top_ops(events), "idle_gaps": idle_gaps(events)}
