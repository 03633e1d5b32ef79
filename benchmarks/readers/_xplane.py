"""One parse of a trace for the readers that need what the harness's
``events`` form drops: the scope path of each device operation and the
program's own ``dmlc.`` host spans, thread by thread. Cached by path, so the
readers of one run share it.

scopes form (plain, so a recorded sample kept as JSON feeds the same
reductions)::

    {"window": [start_ns, end_ns],
     "ops": [[name, start_ns, dur_ns, scope_path], ...],   first chip, XLA Ops
     "modules": [[name, start_ns, dur_ns], ...],            first chip
     "host": {"<line index>": [[name, start_ns, dur_ns], ...]}}

Where the scope path lives (found on the chip, PERF.md section 6, PR 26):
``jax.profiler.ProfileData`` gives an event's own stats only, and an
``XLA Ops`` event's own stats are its device offset and duration. The
``op_name`` that ``jax.named_scope`` writes sits in the *metadata* of the
event (``XEventMetadata.stats``, stat ``tf_op``), which that reader does not
expose. ``op_scopes`` therefore reads that one map straight from the file's
protobuf wire format (skipping the lines, which hold nearly all the bytes)
and the events come from ``ProfileData`` as everywhere else.
"""

from __future__ import annotations

import bisect
import functools
from typing import Dict, Iterator, List, Tuple

from harness import trace

HOST_PREFIXES = ("dmlc.", trace.SPAN_PREFIX)
SCOPE_STAT = "tf_op"


# -- protobuf wire format, as much of it as xplane.proto needs ------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview into the buffer, not a copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise trace.TraceError(f"wire type {wt} in an xplane file")
        yield num, wt, v


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def metadata_stats(path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """plane name -> {event name -> {stat name -> text}} for the device
    planes: the string stats of ``XPlane.event_metadata`` (field 4), their
    names from ``stat_metadata`` (field 5). A string stat is a ``str_value``
    (5) or a ``ref_value`` (7) naming another stat metadata entry."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = "", [], {}
        for pnum, _, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum == 4:
                events.append(_map_entry(v)[1])
            elif pnum == 5:
                sid, meta = _map_entry(v)
                for mnum, _, mv in _fields(meta):
                    if mnum == 2:
                        stat_names[sid] = bytes(mv).decode()
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        by_event: Dict[str, Dict[str, str]] = {}
        for meta in events:
            ev_name, stats = "", {}
            for mnum, _, mv in _fields(meta):
                if mnum == 2:
                    ev_name = bytes(mv).decode(errors="replace")
                elif mnum == 5:
                    key, text = 0, None
                    for snum, _, sv in _fields(mv):
                        if snum == 1:
                            key = sv
                        elif snum == 5:
                            text = bytes(sv).decode(errors="replace")
                        elif snum == 7:
                            text = stat_names.get(sv, "")
                    if text is not None:
                        stats[stat_names.get(key, "")] = text
            by_event[ev_name] = stats
        out[name] = by_event
    return out


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """plane name -> {event name -> scope path}, for the device planes."""
    return {plane: {ev: stats.get(SCOPE_STAT, "")
                    for ev, stats in events.items()}
            for plane, events in metadata_stats(path).items()}


# -- the scopes form ---------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def load(path: str) -> Dict:
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    pd = ProfileData.from_file(path)
    device = sorted(p.name for p in pd.planes
                    if p.name.startswith(trace.DEVICE_PREFIX) and any(
                        l.name == trace.OPS_LINE for l in p.lines))
    doc: Dict = {"window": None, "ops": [], "modules": [], "host": {}}
    for plane in pd.planes:
        if plane.name == trace.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if e.name.startswith(HOST_PREFIXES)]
                if evs:
                    doc["host"][str(i)] = evs
                for name, start, dur in evs:
                    if name == trace.WINDOW_EVENT:
                        doc["window"] = [start, start + dur]
        elif device and plane.name == device[0]:
            by_name = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    doc["ops"] = [[e.name[:trace.NAME_CHARS], float(e.start_ns),
                                   float(e.duration_ns),
                                   by_name.get(e.name, "")]
                                  for e in line.events]
                elif line.name == trace.MODULES_LINE:
                    doc["modules"] = [[e.name, float(e.start_ns),
                                       float(e.duration_ns)]
                                      for e in line.events]
    if doc["window"] is None:
        raise trace.TraceError(
            f"no {trace.WINDOW_EVENT!r} annotation in the host plane")
    return doc


def of_run(ctx) -> Dict:
    """The scopes form of the run's own trace, found as the harness finds
    it."""
    from harness import cells
    return load(trace.find_xplane(cells.cache_dir(ctx["cell"]["name"],
                                                  "trace")))


# -- reductions ----------------------------------------------------------------------

def inside(events: List, lo: float, hi: float) -> List:
    """Events wholly inside [lo, hi]: one straddling an edge is left out."""
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


def step_modules(doc: Dict, match: str) -> List:
    lo, hi = doc["window"]
    return [m for m in inside(doc["modules"], lo, hi) if match in m[0]]


def step_ops(doc: Dict, match: str) -> Tuple[List, List]:
    """(the step module's events wholly inside the window, the operations
    wholly inside one of them): a step cut by the window's edge gives
    neither its module event nor its operations."""
    mods = sorted(step_modules(doc, match), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    ops = []
    for op in doc["ops"]:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] + op[2] <= mods[i][1] + mods[i][2]:
            ops.append(op)
    return mods, ops


def matches(scope: str, how: Dict) -> bool:
    """``any``: at least one of these substrings; ``none``: none of these."""
    return (any(s in scope for s in how["any"])
            and not any(s in scope for s in how.get("none", ())))


def overlap(xs: List[Tuple[float, float]], ys: List[Tuple[float, float]]
            ) -> float:
    """Total length of the intersection of two sorted disjoint unions."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def clipped_union(events: List, lo: float, hi: float
                  ) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi], by the harness's
    own clip and union (``[name, start, dur, ...]`` events)."""
    return trace._union(trace._clip([e[:3] for e in events], lo, hi))


def idle_gaps(doc: Dict) -> List[Tuple[float, float]]:
    """The parts of the window in which no operation ran on the first chip
    (operations clipped to the window, as ``device_idle_share`` has it)."""
    lo, hi = doc["window"]
    gaps, cur = [], lo
    for a, b in clipped_union(doc["ops"], lo, hi):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps
