"""Unified telemetry: one metrics plane across C++, Python, and the tracker.

Before this layer, observability lived in three disjoint side-channels —
``io_retry_stats()`` (native IoStats counters), per-parser
``pipeline_stats()`` structs, and the tracker's ad-hoc event list — with no
shared naming, units, or reset semantics. This module is the Python half of
the unified plane (the native half is ``cpp/src/telemetry.h``):

- a process-wide registry of counters / gauges / log2-bucket latency
  histograms (same bucket scheme as the native side: bucket *i* counts
  observations ``v <= 2**i``, plus one +Inf overflow bucket);
- :func:`snapshot` merges the Python registry with the native registry's
  versioned JSON document (``dct_telemetry_snapshot``) into ONE document —
  the same metric names and values are retrievable through the C ABI,
  through this function, and through a live tracker's HTTP ``GET /metrics``
  scrape;
- two export formats from that one snapshot: Prometheus text exposition
  (:func:`prometheus_text`) and the tracker's JSONL event schema
  (:func:`events_jsonl` — tracker events are just another telemetry
  stream, ring-buffered by :func:`emit_event`).

Metric catalog, units, and env knobs: ``doc/observability.md``. Hot-path
cost: Python metrics are touched at batch granularity (never per row), and
:func:`enabled` gates timed spans; ``DMLC_TELEMETRY=0`` disables spans in
both halves.
"""

from __future__ import annotations

import atexit
import collections
import json
import logging
import math
import os
import resource
import statistics
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "HIST_BUCKETS",
           "SNAPSHOT_VERSION", "SPANS_MAX", "METRIC_HELP", "counter",
           "gauge", "histogram", "register_collector",
           "unregister_collector", "enabled", "enable", "reset",
           "emit_event", "events", "snapshot", "prometheus_text",
           "events_jsonl", "span", "emit_span", "new_span_id", "spans",
           "clock_anchor", "trace_snapshot", "trace_json", "rank_export",
           "cluster_prometheus_text", "cluster_trace_json",
           "stall_attribution", "straggler_attribution", "VERDICT_CODES",
           "flight_dump", "device_overlap_ratio", "quantile_from_buckets",
           "HoldWatch", "pulse_start", "pulse_stop",
           "WindowedView", "SloMonitor", "start_windowed_view",
           "stop_windowed_view", "windowed_view", "slo_page_active"]

SNAPSHOT_VERSION = 1
# must match cpp/src/telemetry.h kHistBuckets (le 2^0..2^27, then +Inf)
HIST_BUCKETS = 28
# Python half of the span ring: most recent SPANS_MAX completed spans
# (the native ring is cpp/src/telemetry.h kSpanRingSize)
SPANS_MAX = 8192

_lock = threading.Lock()
_counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], "Counter"] = {}
_gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], "Gauge"] = {}
_hists: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], "Histogram"] = {}
_collectors: List[Callable[[], None]] = []
_events: List[dict] = []
_EVENTS_MAX = 4096
_enabled: Optional[bool] = None

# span-ring state: completed spans (dicts) in emit order, a monotonically
# increasing span-id allocator, a small per-thread lane id map, and the
# per-thread currently-open span (the parent of the next nested one)
_spans: List[dict] = []
_spans_dropped = 0
_span_seq = 0
_tids: Dict[int, int] = {}
_tls = threading.local()
# jax.profiler.TraceAnnotation, looked up once jax is loaded (never
# imported from here: the tracker imports this module and must stay off
# jax); every annotation's name carries this prefix in the profiler's trace
_annotation_cls = None
ANNOTATION_PREFIX = "dmlc."


def _labels_key(labels: Optional[Dict[str, str]]
                ) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((labels or {}).items()))


class Counter:
    """A monotonically increasing value (Prometheus ``counter``). Thread-safe
    under the GIL plus a per-instance lock for the read-modify-write."""

    __slots__ = ("name", "labels", "_v", "_mu")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = dict(labels)
        self._v = 0
        self._mu = threading.Lock()

    def inc(self, n: int = 1) -> None:
        """Add ``n`` (default 1)."""
        with self._mu:
            self._v += n

    @property
    def value(self) -> int:
        """Current count."""
        return self._v

    def zero(self) -> None:
        """Reset to 0 (registry-wide :func:`reset` calls this)."""
        with self._mu:
            self._v = 0


class Gauge:
    """A point-in-time value that can go up or down (Prometheus
    ``gauge``)."""

    __slots__ = ("name", "labels", "_v")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = dict(labels)
        self._v = 0.0

    def set(self, v: float) -> None:
        """Set the current value."""
        self._v = v

    @property
    def value(self) -> float:
        """Current value."""
        return self._v

    def zero(self) -> None:
        """Reset to 0 (registry-wide :func:`reset` calls this)."""
        self._v = 0.0


class Histogram:
    """Fixed-bucket log2 latency histogram, bucket-compatible with the
    native side (cpp/src/telemetry.h Hist): bucket ``i`` counts
    observations ``v <= 2**i`` for ``i < HIST_BUCKETS``, the last bucket is
    +Inf overflow. Observe integer microseconds for ``*_us`` metrics."""

    __slots__ = ("name", "labels", "count", "sum", "buckets", "exemplars",
                 "_mu")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = dict(labels)
        self.count = 0
        self.sum = 0
        self.buckets = [0] * (HIST_BUCKETS + 1)
        # bucket index -> trace id of the LAST sampled observation that
        # landed there (doc/observability.md "Per-request tracing"): the
        # breadcrumb from a latency bucket back to the span chain that
        # produced it. Lazy — stays None until the first exemplar, so
        # unsampled histograms pay nothing
        self.exemplars: Optional[Dict[int, int]] = None
        self._mu = threading.Lock()

    @staticmethod
    def bucket_of(v: int) -> int:
        """Index of the first bucket whose upper bound ``2**i`` holds
        ``v``; ``HIST_BUCKETS`` is the overflow bucket."""
        if v <= 1:
            return 0
        w = int(v - 1).bit_length()  # ceil(log2(v))
        return w if w < HIST_BUCKETS else HIST_BUCKETS

    def observe(self, v: float, trace_id: Optional[int] = None) -> None:
        """Record one observation (non-negative; fractions are truncated
        for the bucket choice, summed exactly — sub-unit observations must
        not read as zero-cost in sum/count means). ``trace_id`` (a span
        id from a sampled request chain) is kept as the bucket's exemplar
        — last writer wins, exported in the JSON snapshot only (the text
        exposition stays plain 0.0.4)."""
        if v < 0:
            v = 0
        with self._mu:
            self.count += 1
            self.sum += v
            b = self.bucket_of(int(v))
            self.buckets[b] += 1
            if trace_id:
                if self.exemplars is None:
                    self.exemplars = {}
                self.exemplars[b] = trace_id

    def zero(self) -> None:
        """Reset all counts (registry-wide :func:`reset` calls this)."""
        with self._mu:
            self.count = 0
            self.sum = 0
            self.buckets = [0] * (HIST_BUCKETS + 1)
            self.exemplars = None

    def quantile(self, q: float) -> float:
        """Upper-bound estimate of the ``q``-quantile (0 < q <= 1) from
        the log2 buckets: the bound ``2**i`` of the first bucket where
        the cumulative count reaches ``ceil(q * count)``. Factor-of-two
        resolution — exactly what an open-loop latency capture needs to
        tell a 1 ms p99 from a 200 ms one without storing samples."""
        with self._mu:
            return quantile_from_buckets(self.buckets, self.count, q)


def quantile_from_buckets(buckets, count: int, q: float) -> float:
    """Shared quantile-from-log2-buckets estimate (see
    :meth:`Histogram.quantile`); works on any snapshot's bucket list.
    Returns 0.0 on an empty histogram and ``inf`` when the quantile
    lands in the +Inf overflow bucket."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    if count <= 0:
        return 0.0
    need = max(1, math.ceil(q * count))
    cum = 0
    for i, n in enumerate(buckets):
        cum += n
        if cum >= need:
            return float("inf") if i >= HIST_BUCKETS else float(1 << i)
    return float("inf")


def counter(name: str, labels: Optional[Dict[str, str]] = None) -> Counter:
    """Resolve-or-register the counter ``(name, labels)``; the returned
    object is stable for the process lifetime — resolve once, keep it."""
    key = (name, _labels_key(labels))
    with _lock:
        c = _counters.get(key)
        if c is None:
            c = _counters[key] = Counter(name, dict(key[1]))
        return c


def gauge(name: str, labels: Optional[Dict[str, str]] = None) -> Gauge:
    """Resolve-or-register the gauge ``(name, labels)`` (see
    :func:`counter`)."""
    key = (name, _labels_key(labels))
    with _lock:
        g = _gauges.get(key)
        if g is None:
            g = _gauges[key] = Gauge(name, dict(key[1]))
        return g


def histogram(name: str, labels: Optional[Dict[str, str]] = None
              ) -> Histogram:
    """Resolve-or-register the histogram ``(name, labels)`` (see
    :func:`counter`)."""
    key = (name, _labels_key(labels))
    with _lock:
        h = _hists.get(key)
        if h is None:
            h = _hists[key] = Histogram(name, dict(key[1]))
        return h


def register_collector(fn: Callable[[], None]) -> None:
    """Register a callback run at every :func:`snapshot` before the
    registry is read — how components with derived state (the tracker's
    per-rank heartbeat ages) refresh their gauges lazily instead of on a
    timer. Collectors must be fast and must not raise (exceptions are
    swallowed so one broken collector cannot sink a scrape)."""
    with _lock:
        if fn not in _collectors:
            _collectors.append(fn)


def unregister_collector(fn: Callable[[], None]) -> None:
    """Remove a collector registered with :func:`register_collector`
    (no-op when absent) — call on component shutdown so a dead tracker
    does not keep publishing."""
    with _lock:
        if fn in _collectors:
            _collectors.remove(fn)


def enabled() -> bool:
    """Whether timed-span instrumentation is on: ``DMLC_TELEMETRY`` env at
    first use (default on), overridable via :func:`enable`. Counters keep
    counting either way."""
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("DMLC_TELEMETRY", "1") not in ("0", "off")
    return _enabled


def enable(on: bool) -> None:
    """Set the span gate for BOTH halves: the Python registry and — when
    the native library is already loaded — the native registry
    (``dct_telemetry_enable``)."""
    global _enabled
    _enabled = bool(on)
    if not on:
        pulse_stop()
    lib = _native_lib_if_loaded()
    if lib is not None:
        lib.dct_telemetry_enable(1 if on else 0)


def reset(native: bool = True) -> None:
    """Zero every Python-registered metric and drop buffered events; with
    ``native=True`` (default) also zero the native registry when its
    library is loaded (``dct_telemetry_reset``). Also force-stops the
    process :class:`WindowedView` (test isolation: a leaked ticker thread
    from one test must not publish windows into the next), and for the
    same reason the pulse (the next iterator starts it again) and the count
    of long-hold records made."""
    global _spans_dropped, _hold_records, _hold_record_at
    stop_windowed_view(force=True)
    pulse_stop()
    with _lock:
        _hold_records, _hold_record_at = 0, 0.0
        for c in _counters.values():
            c.zero()
        for g in _gauges.values():
            g.zero()
        for h in _hists.values():
            h.zero()
        del _events[:]
        del _spans[:]
        _spans_dropped = 0
    if native:
        lib = _native_lib_if_loaded()
        if lib is not None:
            lib.dct_telemetry_reset()  # also drops the native span ring


def emit_event(event: str, **fields) -> None:
    """Append one event to the telemetry event stream (the PR-4 tracker
    JSONL schema: ``{"ts": ..., "event": ..., **fields}``; pass ``ts=`` to
    preserve an already-stamped time). The stream is a ring buffer of the
    most recent ``4096`` events; exposition via :func:`events_jsonl`. Also
    bumps ``telemetry_events_total{event=...}``."""
    rec = {"ts": fields.pop("ts", None) or time.time(), "event": event}
    rec.update(fields)
    with _lock:
        _events.append(rec)
        if len(_events) > _EVENTS_MAX:
            del _events[: len(_events) - _EVENTS_MAX]
    counter("telemetry_events_total", {"event": event}).inc()


def events() -> List[dict]:
    """A copy of the buffered event stream (most recent ``4096``)."""
    with _lock:
        return list(_events)


# -- distributed tracing (doc/observability.md "Distributed tracing") --------
def _thread_lane() -> int:
    """Small stable lane id for the calling thread (Chrome-trace tid)."""
    ident = threading.get_ident()
    with _lock:
        lane = _tids.get(ident)
        if lane is None:
            lane = _tids[ident] = len(_tids) + 1
        return lane


def _perf_us() -> float:
    return time.perf_counter() * 1e6


def clock_anchor() -> Dict[str, float]:
    """One (wall, monotonic) clock pair sampled back to back — the
    per-process anchor every snapshot/trace/dump carries, so timelines
    recorded on the monotonic clock (spans) merge with wall-clock streams
    (events) and with other processes' spans without drift. Keys:
    ``wall_us`` (``time.time()`` µs) and ``perf_us``
    (``time.perf_counter()`` µs)."""
    return {"wall_us": time.time() * 1e6, "perf_us": _perf_us()}


def _append_span(name: str, span_id: int, parent: int, start_us: float,
                 dur_us: float, args: Optional[dict]) -> None:
    """Append one completed record to the ring (the one shared writer:
    :func:`emit_span` and :class:`_Span` both land here)."""
    global _spans_dropped
    lane = _thread_lane()
    with _lock:
        rec = {"name": name, "id": span_id, "parent": parent, "tid": lane,
               "ts": int(start_us), "dur": int(dur_us)}
        if args:
            rec["args"] = args
        _spans.append(rec)
        if len(_spans) > SPANS_MAX:
            drop = len(_spans) - SPANS_MAX
            del _spans[:drop]
            _spans_dropped += drop


def new_span_id() -> int:
    """Allocate one span id from the process allocator WITHOUT emitting a
    span — the handle a sampled request carries across the worker-thread
    boundary so its child spans can name an explicit ``parent=`` and the
    root can be emitted later under ``span_id=`` (the ring's thread-local
    parent chain does not cross threads)."""
    global _span_seq
    with _lock:
        _span_seq += 1
        return _span_seq


def emit_span(name: str, start_us: float, dur_us: float,
              parent: Optional[int] = None, span_id: Optional[int] = None,
              **args) -> None:
    """Append one COMPLETED span to the process span ring: ``start_us``
    on the ``time.perf_counter()`` microsecond clock, ``dur_us`` its
    duration. Parents under the thread's currently open :func:`span`
    (matching the native ``EmitSpan``) unless an explicit ``parent=`` is
    given — the cross-thread handoff used by sampled request chains
    (pass ``parent=0`` for an explicit root). ``span_id=`` reuses an id
    from :func:`new_span_id` instead of allocating. Extra keyword args
    ride along as the span's ``args`` dict (keep them small — shard ids,
    byte counts). No-op when telemetry is disabled; the ring keeps the
    most recent :data:`SPANS_MAX` spans and counts what it overwrote."""
    if not enabled():
        return
    if span_id is None:
        span_id = new_span_id()
    if parent is None:
        parent = getattr(_tls, "open_span", 0)
    _append_span(name, span_id, parent, start_us, dur_us, args or None)


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where this process has jax loaded,
    else None. The class is a no-op ``TraceMe`` while no profile runs, so
    an opened span needs no "is a profile running" switch."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        # mid-import jax has no `profiler` attribute yet: look again later
        _annotation_cls = getattr(getattr(jax, "profiler", None),
                                  "TraceAnnotation", None)
    return _annotation_cls


class _Span:
    """Context manager behind :func:`span`; exposes ``set_arg`` for the
    dominant dimension of the work (bytes, rows, shard id). While open it
    is also a profiler annotation ``dmlc.<name>`` (see :func:`span`)."""

    __slots__ = ("name", "args", "_start", "_id", "_parent", "_active",
                 "_ann")

    def __init__(self, name: str, args: Optional[dict]):
        self.name = name
        self.args = args
        self._active = False
        self._ann = None

    def set_arg(self, key: str, value) -> None:
        """Attach one key/value to the span's args."""
        if self.args is None:
            self.args = {}
        self.args[key] = value
        if self._ann is not None:
            self._ann.set_metadata(**{key: value})

    def __enter__(self) -> "_Span":
        self._active = enabled()
        if not self._active:
            return self
        global _span_seq
        with _lock:
            _span_seq += 1
            self._id = _span_seq
        self._parent = getattr(_tls, "open_span", 0)
        _tls.open_span = self._id
        cls = _trace_annotation()
        if cls is not None:
            self._ann = cls(ANNOTATION_PREFIX + self.name,
                            **(self.args or {}))
            self._ann.__enter__()
        self._start = _perf_us()
        return self

    @property
    def elapsed_us(self) -> float:
        """Microseconds since the span opened (0 when telemetry is off):
        the one reading a site's histogram shares with its span."""
        return _perf_us() - self._start if self._active else 0.0

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._active:
            return
        dur = _perf_us() - self._start
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _tls.open_span = self._parent
        _append_span(self.name, self._id, self._parent, self._start, dur,
                     self.args)


def span(name: str, **args) -> _Span:
    """RAII trace span: ``with telemetry.span("rowblock.next"): ...``
    records one completed span (perf-counter clock, µs) into the process
    span ring at scope exit, parented under the thread's currently open
    span. Disabled (:func:`enabled` False) cost: one attribute read.
    Extra kwargs become the span's ``args``.

    One clock for host and device: in a process that has jax loaded an
    open span is also a ``jax.profiler.TraceAnnotation("dmlc." + name)``
    carrying the kwargs, so under ``jax.profiler.start_trace`` it lands in
    the trace's ``/host:CPU`` plane on the profiler's clock, beside the
    device planes (no-op ``TraceMe`` while no profile runs). Spans emitted
    post hoc (:func:`emit_span`) live in the ring only."""
    return _Span(name, args or None)


def spans() -> List[dict]:
    """A copy of the buffered Python span ring (most recent
    :data:`SPANS_MAX` completed spans, emit order)."""
    with _lock:
        return list(_spans)


def _native_trace_doc() -> Optional[dict]:
    """The native span-ring document (``dct_trace_snapshot``), or None
    when the library is not loaded. Never triggers a build."""
    lib = _native_lib_if_loaded()
    if lib is None:
        return None
    import ctypes
    out = ctypes.c_char_p()
    if lib.dct_trace_snapshot(ctypes.byref(out)) != 0:
        return None
    try:
        return json.loads(ctypes.string_at(out).decode())
    finally:
        lib.dct_str_free(out)


def trace_snapshot() -> dict:
    """The process trace document: Python spans (perf-counter clock) plus
    the native ring's document (steady clock) when the library is already
    loaded, each with its own (wall, monotonic) anchor pair. Schema:
    ``{"version", "pid", "anchor": {"wall_us", "perf_us"}, "spans": [...],
    "dropped", "native": <dct_trace_snapshot doc>|None}``. Use
    :func:`trace_json` for the merged wall-clock Chrome-trace render."""
    return {"version": 1, "pid": os.getpid(), "anchor": clock_anchor(),
            "spans": spans(), "dropped": _spans_dropped,
            "native": _native_trace_doc()}


def _wall_spans(snap: dict) -> List[dict]:
    """Flatten a :func:`trace_snapshot` doc into ONE list of spans on the
    wall-clock µs timeline: each half's spans are shifted by its own
    (wall, monotonic) anchor pair, so native (steady-clock) and Python
    (perf-counter) spans land on the same axis — and, across processes,
    on the same axis as every other rank's."""
    out = []
    a = snap.get("anchor") or {}
    shift = float(a.get("wall_us", 0)) - float(a.get("perf_us", 0))
    for s in snap.get("spans", ()):
        rec = dict(s)
        rec["ts"] = int(s["ts"] + shift)
        rec["cat"] = "python"
        out.append(rec)
    nat = snap.get("native")
    if nat:
        na = nat.get("anchor") or {}
        nshift = float(na.get("wall_us", 0)) - float(na.get("steady_us", 0))
        for s in nat.get("spans", ()):
            rec = {"name": s["name"], "id": s["id"], "parent": s["parent"],
                   # native lanes get their own tid namespace so a native
                   # worker thread never shares a lane with a Python one
                   "tid": 1000 + int(s["tid"]),
                   "ts": int(s["ts"] + nshift), "dur": int(s["dur"]),
                   "cat": "native"}
            if s.get("arg"):
                rec["args"] = {"arg": s["arg"]}
            out.append(rec)
    out.sort(key=lambda r: r["ts"])
    return out


def _chrome_events(wall_spans: List[dict], pid, label: str) -> List[dict]:
    """Chrome-trace/Perfetto events for one process lane: complete ("X")
    events plus the process_name metadata record."""
    evs = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": label}}]
    for s in wall_spans:
        ev = {"ph": "X", "name": s["name"], "pid": pid, "tid": s["tid"],
              "ts": s["ts"], "dur": max(int(s["dur"]), 0),
              "cat": s.get("cat", "python"),
              "args": dict(s.get("args") or {},
                           span_id=s["id"], parent=s["parent"])}
        evs.append(ev)
    return evs


def trace_json(snap: Optional[dict] = None) -> str:
    """Render the process trace (default: take :func:`trace_snapshot`
    now) as Chrome-trace JSON — loadable in Perfetto / ``chrome://
    tracing``. C++ and Python spans are merged onto ONE wall-clock µs
    timeline via each half's (wall, monotonic) anchor pair; native worker
    threads get their own ``tid`` lanes. For the job-wide merged view
    across ranks, scrape a live tracker's ``GET /trace``
    (:func:`cluster_trace_json`)."""
    if snap is None:
        snap = trace_snapshot()
    pid = snap.get("pid", 0)
    evs = _chrome_events(_wall_spans(snap), pid, f"pid {pid}")
    return json.dumps({"traceEvents": evs, "displayTimeUnit": "ms"})


# -- stall attribution (doc/observability.md "Stall attribution") ------------
# verdict -> stall_verdict_code gauge value
VERDICT_CODES = {"unknown": -1, "fill_bound": 0, "parse_bound": 1,
                 "consumer_bound": 2, "transfer_bound": 3,
                 "stage_bound": 4, "compile_bound": 5,
                 "straggler_bound": 6}

# the consumer counts as the binding stage when it spent less than this
# fraction of the pipeline's busy time waiting on the head-of-line chunk
# (the pipeline kept up; whatever is downstream of it did not)
_STARVED_WAIT_FRACTION = 0.05


def stall_attribution(snap: Optional[dict] = None) -> dict:
    """Per-stage occupancy plus a fill-bound / parse-bound /
    consumer-bound / transfer-bound / stage-bound / compile-bound
    verdict, derived from the span-backed stage histograms of one
    snapshot (default: take one now).

    The decision tree reads the batch path's own instrumentation, device
    lane first (doc/observability.md "Device lane"): XLA compilation time
    (``device_compile_us``, the jax.monitoring hook) dominating every
    other stage means shapes are churning (``compile_bound``); the NET
    host batch-assembly time — ``device_stage_us`` minus the fill/parse/
    pipeline-wait time nested inside it — dominating means the pad+bucket
    +pack stage binds (``stage_bound``); ``device_transfer_us``
    dominating both fill and parse means the host→HBM hop binds
    (``transfer_bound``). Host side, a small
    ``parse_stage_reassemble_wait_us`` relative to the pipeline's busy
    time means the pipeline kept up and the CONSUMER binds
    (``consumer_bound``); otherwise the consumer was starved by the
    pipeline, and the larger of the fill (source read + cache replay) and
    parse (scan + slice decode) sums names the stage. With no stage
    observations (spans disabled, nothing run) the verdict is
    ``unknown``. Returns ``{"verdict", "stage_us": {...}, "occupancy":
    {stage: fraction}}``; the same result rides every snapshot as the
    ``stall_stage_occupancy{stage=}`` / ``stall_verdict_code`` gauges."""
    if snap is None:
        snap = snapshot()
    sums: Dict[str, float] = {}
    for h in snap.get("histograms", ()):
        if not h.get("labels"):
            sums[h["name"]] = sums.get(h["name"], 0.0) + float(h["sum"])
    fill = sums.get("parse_stage_fill_us", 0.0) + \
        sums.get("cache_read_us", 0.0)
    parse = sums.get("parse_stage_parse_us", 0.0) + \
        sums.get("parse_stage_scan_us", 0.0)
    wait = sums.get("parse_stage_reassemble_wait_us", 0.0)
    transfer = sums.get("device_transfer_us", 0.0)
    # NET batch assembly: device_stage_us wraps batcher.next_batch(),
    # which nests the parse pipeline's fill/parse/head-of-line time —
    # subtracting those leaves the pad+bucket+pack cost this stage adds
    stage = max(sums.get("device_stage_us", 0.0) - fill - parse - wait,
                0.0)
    compile_t = sums.get("device_compile_us", 0.0)
    dev_wait = sums.get("device_wait_us", 0.0)
    busy = fill + parse
    stage_us = {"fill": fill, "parse": parse, "pipeline_wait": wait,
                "transfer": transfer, "stage": stage,
                "compile": compile_t, "device_wait": dev_wait}
    total = busy + transfer + stage + compile_t
    occupancy = {k: (stage_us[k] / total if total > 0 else 0.0)
                 for k in ("fill", "parse", "transfer", "stage",
                           "compile")}
    occupancy["pipeline_wait"] = wait / total if total > 0 else 0.0
    if total <= 0:
        verdict = "unknown"
    elif compile_t > max(transfer, stage, fill, parse):
        verdict = "compile_bound"
    elif stage > max(transfer, fill, parse):
        verdict = "stage_bound"
    elif transfer > max(fill, parse):
        verdict = "transfer_bound"
    elif wait <= _STARVED_WAIT_FRACTION * busy:
        verdict = "consumer_bound"
    elif fill > parse:
        verdict = "fill_bound"
    else:
        verdict = "parse_bound"
    return {"verdict": verdict, "stage_us": stage_us,
            "occupancy": occupancy}


def device_overlap_ratio(span_list: Optional[List[dict]] = None
                         ) -> Optional[float]:
    """Fraction of host→device transfer time hidden behind consumer
    compute, derived from the Python span ring (default: read it now):
    each ``device.put`` span's interval is intersected with the merged
    ``device.wait`` intervals — transfer time the consumer spent WAITING
    through is exposed, the rest ran while the consumer computed and is
    hidden. All spans share one ``perf_counter`` clock across threads, so
    the interval math needs no anchor shifting. Returns a value in
    [0, 1], or ``None`` when the ring holds no ``device.put`` span (the
    device lane never ran, or spans are disabled)."""
    if span_list is None:
        span_list = spans()
    xfer = [(s["ts"], s["ts"] + s["dur"]) for s in span_list
            if s["name"] == "device.put"]
    if not xfer:
        return None
    waits = sorted((s["ts"], s["ts"] + s["dur"]) for s in span_list
                   if s["name"] == "device.wait")
    merged: List[List[float]] = []
    for a, b in waits:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    total = exposed = 0.0
    for a, b in xfer:
        total += b - a
        for wa, wb in merged:
            if wa >= b:
                break
            lo, hi = max(a, wa), min(b, wb)
            if hi > lo:
                exposed += hi - lo
    if total <= 0:
        return None
    return min(max((total - exposed) / total, 0.0), 1.0)


def straggler_attribution(step_durs_by_rank: Dict[int, List[float]],
                          factor: float = 2.0,
                          min_steps: int = 3) -> dict:
    """Name the mesh straggler from per-rank recent step durations
    (doc/observability.md "Step timelines"): a rank is ``straggler_bound``
    when its median step over the window sustains above ``factor`` times
    the median of the OTHER ranks' medians — a sustained-ratio test, so
    one GC pause or one slow step cannot page. Ranks with fewer than
    ``min_steps`` observations abstain; fewer than two voting ranks (no
    peer baseline) is ``unknown``. Returns ``{"verdict", "rank",
    "ratio", "median_us": {rank: median}}`` — ``rank``/``ratio`` are
    ``None``/``0.0`` when no straggler is bound."""
    medians: Dict[int, float] = {}
    for rank, durs in step_durs_by_rank.items():
        if len(durs) >= max(1, int(min_steps)):
            s = sorted(durs)
            medians[rank] = float(s[len(s) // 2])
    out = {"verdict": "unknown", "rank": None, "ratio": 0.0,
           "median_us": medians}
    if len(medians) < 2:
        return out
    worst_rank, worst_ratio = None, 0.0
    for rank, med in medians.items():
        peers = sorted(m for r, m in medians.items() if r != rank)
        peer_med = peers[len(peers) // 2]
        if peer_med <= 0:
            continue
        ratio = med / peer_med
        if ratio > worst_ratio:
            worst_rank, worst_ratio = rank, ratio
    if worst_rank is not None and worst_ratio > factor:
        out["verdict"] = "straggler_bound"
        out["rank"] = worst_rank
        out["ratio"] = worst_ratio
    return out


# -- flight recorder (doc/observability.md "Flight recorder") ----------------
_flight_seq = 0


def flight_dump(reason: str, rank: Optional[int] = None) -> Optional[str]:
    """Write a postmortem — the span ring (both halves), the event ring,
    and a full metric snapshot, with this process's clock anchors — to
    ``$DMLC_TRACE_DUMP/flight_<pid>_<n>.json``. No-op (returns None) when
    ``DMLC_TRACE_DUMP`` is unset; every failure is swallowed, because a
    postmortem writer must never mask the failure it is recording.
    Called on abort broadcasts, tracker aborts, and dead-rank write-offs;
    the native half mirrors it for fault-plane quarantines."""
    out_dir = os.environ.get("DMLC_TRACE_DUMP")
    if not out_dir:
        return None
    global _flight_seq
    try:
        with _lock:
            _flight_seq += 1
            seq = _flight_seq
        doc = {"reason": reason, "rank": rank, "pid": os.getpid(),
               "wall_ts": time.time(), "anchor": clock_anchor(),
               "trace": trace_snapshot(), "metrics": snapshot()}
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"flight_{os.getpid()}_{seq}.json")
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return path
    except Exception:
        return None


# -- the hold and the pulse (doc/observability.md "The hold and the pulse") ---
# A consumer of batches spends its time waiting for one (device.wait) or
# holding one (device.hold: its step and whatever else it does before it asks
# for the next). A hold far over the running median is a stray stall, and
# what tells its causes apart exists only at that moment, inside the process:
# whether the host ran at all, whether the interpreter lock was held, whether
# the consumer computed or waited, and whether puts to the device went on.
# The constants are fixed here and stated in the doc; none is a knob.
PULSE_PERIOD_S = 0.020       # the nap both pulses ask for
PULSE_TICKS = 1024           # ticks kept, about twenty seconds of them
HOLD_RING = 64               # holds an iterator keeps for its median
HOLD_MIN_HOLDS = 8           # no excess and no record before this many
HOLD_LONG_FLOOR_US = 50_000  # long: this far over the median, or half of it
HOLD_RECORD_GAP_S = 1.0      # at most one record in this long ...
HOLD_RECORDS_MAX = 64        # ... and this many a process
PUT_SLOW_FACTOR = 3          # a put this many times its usual is blocked
_STACK_FRAMES = 5
_USUAL_PUTS = 16             # puts before the hold that give the usual time

_pulse_lock = threading.Lock()
_pulse_thread: Optional[threading.Thread] = None
_pulse_halt: Optional[threading.Event] = None
_pulse_native = False        # the native pulse was started from here
_pulse_at_exit = False
_pulse_ticks: "collections.deque" = collections.deque(maxlen=PULSE_TICKS)
_pulse_due_us = 0.0          # when the running nap of the Python pulse ends
_running_holds: Dict[int, "_RunningHold"] = {}
_hold_records = 0
_hold_record_at = 0.0
_marks_cache: Tuple[int, list, list] = (-1, [], [])


def _stack_of(ident: int) -> List[str]:
    """The innermost frames of thread ``ident`` as ``file:line function``."""
    frame = sys._current_frames().get(ident)
    out = []
    while frame is not None and len(out) < _STACK_FRAMES:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                   f"{code.co_name}")
        frame = frame.f_back
    return out


def _pulse_loop(halt: threading.Event) -> None:
    """The Python pulse: nap, wake (which needs the interpreter lock), and
    record how late. Each tick is an opened span, so a profiler trace has a
    ``dmlc.pulse`` line whose holes are where no Python thread could run.
    A hold that has run past its limit gets the consumer's stack, once."""
    global _pulse_due_us
    late_hist = histogram("pulse_py_late_us")
    due = _perf_us()  # the first tick is due at once
    while True:
        _pulse_due_us = due
        if halt.wait(max(0.0, (due - _perf_us()) / 1e6)):
            return
        woke = _perf_us()
        with span("pulse") as sp:
            late = max(0.0, woke - due)
            late_hist.observe(late)
            _pulse_ticks.append((woke, late))
            sp.set_arg("late_us", int(late))
            for run in list(_running_holds.values()):
                if (run.stack is None and run.limit_us is not None
                        and woke - run.t0_us > run.limit_us):
                    run.stack = _stack_of(run.ident)
        due = _perf_us() + PULSE_PERIOD_S * 1e6


def pulse_start() -> None:
    """Start the two pulses unless they run: a Python daemon thread here
    and, where the native library is loaded, its thread
    (``dct_pulse_start``). One pair a process however many iterators call
    this; nothing starts while telemetry is disabled. They stop at exit, by
    :func:`pulse_stop` and by ``enable(False)``."""
    global _pulse_thread, _pulse_halt, _pulse_native, _pulse_at_exit
    th = _pulse_thread
    if th is not None and th.is_alive() and _pulse_native:
        return
    if not enabled():
        return
    with _pulse_lock:
        if _pulse_thread is None or not _pulse_thread.is_alive():
            _pulse_halt = threading.Event()
            _pulse_thread = threading.Thread(
                target=_pulse_loop, args=(_pulse_halt,), name="dmlc-pulse",
                daemon=True)
            _pulse_thread.start()
            if not _pulse_at_exit:
                _pulse_at_exit = True
                atexit.register(pulse_stop)
        lib = _native_lib_if_loaded()
        if lib is not None and not _pulse_native:
            lib.dct_pulse_start()
            _pulse_native = True


def pulse_stop() -> None:
    """Stop both pulses and wait for their threads (idempotent)."""
    global _pulse_thread, _pulse_halt, _pulse_native
    with _pulse_lock:
        th, halt = _pulse_thread, _pulse_halt
        _pulse_thread = _pulse_halt = None
        native, _pulse_native = _pulse_native, False
    if halt is not None:
        halt.set()
    if th is not None and th is not threading.current_thread():
        th.join(timeout=2.0)
    lib = _native_lib_if_loaded()
    if native and lib is not None:
        lib.dct_pulse_stop()


def _pulse_late_us(t0_us: float, t1_us: float
                   ) -> Tuple[float, Optional[float]]:
    """The largest lateness of each pulse between two perf-counter times:
    (python, native); native is None where that pulse does not run. A nap
    that should have ended in the interval and has not counts by how far it
    is overdue: the thread that asks may have got the processor, or the
    interpreter lock, back before the pulse."""
    py = max((late for woke, late in list(_pulse_ticks)
              if t0_us <= woke <= t1_us), default=0.0)
    now = _perf_us()
    th = _pulse_thread
    if th is not None and th.is_alive() and t0_us <= _pulse_due_us <= t1_us:
        py = max(py, now - _pulse_due_us)
    lib = _native_lib_if_loaded()
    if lib is None or not _pulse_native:
        return py, None
    import ctypes
    late = ctypes.c_uint64(0)
    lib.dct_pulse_max_late_us(int(max(0.0, now - t0_us)),
                              int(max(0.0, now - t1_us)),
                              ctypes.byref(late), None)
    return py, float(late.value)


def _compile_marks() -> Tuple[float, int]:
    """(``device_compile_us``' sum, ``model_step_builds_total`` over its
    labels) now: their rise inside a hold says the step compiled there. The
    matching metric objects are looked up again only when the registry has
    grown."""
    global _marks_cache
    size = len(_hists) + len(_counters)
    if _marks_cache[0] != size:
        with _lock:
            _marks_cache = (
                size,
                [h for (n, _), h in _hists.items()
                 if n == "device_compile_us"],
                [c for (n, _), c in _counters.items()
                 if n == "model_step_builds_total"])
    _, hists, counters = _marks_cache
    return sum(h.sum for h in hists), sum(c.value for c in counters)


class _RunningHold:
    """A hold that has begun: what its end, and the pulse, need of it."""

    __slots__ = ("ident", "t0_us", "cpu_s", "median_us", "limit_us", "marks",
                 "rusage", "stack")

    def __init__(self, median_us: Optional[float]):
        self.ident = threading.get_ident()
        self.median_us = median_us
        # a hold is long when hold - median >= max(50 ms, median / 2)
        self.limit_us = None if median_us is None else median_us + max(
            HOLD_LONG_FLOOR_US, median_us / 2)
        self.stack: Optional[List[str]] = None
        self.marks = _compile_marks()
        self.rusage = resource.getrusage(resource.RUSAGE_SELF)
        self.cpu_s = time.thread_time()
        self.t0_us = _perf_us()


class HoldWatch:
    """One iterator's account of its consumer's holds: :meth:`begin` as a
    batch is handed over (just before the ``yield``), :meth:`end` when the
    consumer asks for the next, :meth:`abandon` from the generator's
    ``finally``. Every hold is a post hoc span ``device.hold`` (with the
    consumer thread's CPU time as ``cpu_us``), an observation of
    ``device_hold_us`` and one of ``device_hold_excess_us``, the hold less
    the median of the iterator's last :data:`HOLD_RING` holds (0 until
    :data:`HOLD_MIN_HOLDS` are in). A hold that ends far over the median
    is counted in ``device_long_holds_total`` and recorded with a verdict
    (:func:`_long_hold`)."""

    __slots__ = ("_holds", "_run", "_hold_us", "_excess_us", "_long")

    def __init__(self):
        self._holds: "collections.deque" = collections.deque(maxlen=HOLD_RING)
        self._run: Optional[_RunningHold] = None
        self._hold_us = histogram("device_hold_us")
        self._excess_us = histogram("device_hold_excess_us")
        self._long = counter("device_long_holds_total")

    def begin(self) -> None:
        """The consumer is about to be handed a batch (a no-op while
        telemetry is disabled)."""
        if not enabled():
            return
        holds = self._holds
        self._run = run = _RunningHold(
            statistics.median(holds) if len(holds) >= HOLD_MIN_HOLDS
            else None)
        _running_holds[id(self)] = run

    def end(self, put_since_us: Optional[float] = None) -> None:
        """``put_since_us``: when the put now in flight began, if one is."""
        run = self._run
        if run is None:
            return
        t1_us = _perf_us()
        cpu_us = (time.thread_time() - run.cpu_s) * 1e6
        self.abandon()
        hold_us = t1_us - run.t0_us
        self._hold_us.observe(hold_us)
        self._excess_us.observe(  # a hold under the median observes 0
            0 if run.median_us is None else hold_us - run.median_us)
        self._holds.append(hold_us)
        if run.limit_us is not None and hold_us >= run.limit_us:
            self._long.inc()
            _long_hold(run, t1_us, hold_us, cpu_us, put_since_us)
        emit_span("device.hold", run.t0_us, hold_us, cpu_us=int(cpu_us))

    def abandon(self) -> None:
        """Forget the running hold, if any (the consumer dropped the
        generator: no hold is recorded)."""
        self._run = None
        _running_holds.pop(id(self), None)


def _spans_over(t0_us: float, t1_us: float
                ) -> Tuple[dict, dict, Optional[float]]:
    """What both span rings hold of the interval (perf-counter times): for
    each lane the milliseconds its spans cover of it, by span name, and the
    ``device.put`` spans apart, with the shortest of those that completed
    inside it. The Python ring is on the interval's clock;
    the native ring's steady clock is reached through the two anchor pairs,
    by way of the wall clock. A ring is in completion order, so the walk
    goes back from its newest span and stops once it is before the interval
    and has the usual puts."""
    lanes: Dict[str, Dict[str, float]] = {}
    inside = {"device.put": [], "device.put.block": []}
    usual: List[float] = []

    def walk(records, cat, tid_base, lo, hi):
        for s in reversed(records):
            ts, end = s["ts"], s["ts"] + s["dur"]
            name = s["name"]
            if end < lo:
                if cat == "native" or len(usual) >= _USUAL_PUTS:
                    break
                if name == "device.put":
                    usual.append(s["dur"])
                continue
            if name in inside and end <= hi:
                inside[name].append(s["dur"])
            covered = min(end, hi) - max(ts, lo)
            if covered > 0:
                lane = lanes.setdefault(f"{cat}:{tid_base + int(s['tid'])}",
                                        {})
                lane[name] = lane.get(name, 0.0) + covered / 1e3

    walk(spans(), "python", 0, t0_us, t1_us)
    nat = _native_trace_doc()
    if nat:
        a, na = clock_anchor(), nat.get("anchor") or {}
        shift = (a["wall_us"] - a["perf_us"]) - (
            float(na.get("wall_us", 0)) - float(na.get("steady_us", 0)))
        # native lanes keep the tid namespace _wall_spans gives them
        walk(nat.get("spans", ()), "native", 1000, t0_us + shift,
             t1_us + shift)
    top = {lane: {n: round(ms, 1) for n, ms in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:4]}
        for lane, by_name in sorted(lanes.items())}
    puts = {"n": len(inside["device.put"]),
            "median_us": (statistics.median(inside["device.put"])
                          if inside["device.put"] else None),
            "block_n": len(inside["device.put.block"]),
            "block_median_us": (statistics.median(inside["device.put.block"])
                                if inside["device.put.block"] else None),
            "usual_us": statistics.median(usual) if usual else None}
    return top, puts, min(inside["device.put"], default=None)


def _puts_state(fastest_us: Optional[float], usual_us: Optional[float],
                in_flight_us: Optional[float]) -> str:
    """What the path to the device did during a hold the consumer waited
    through: ``puts_flowing`` (a put completed inside it in under
    :data:`PUT_SLOW_FACTOR` times the usual), ``puts_blocked`` (one
    completed that slowly, or is in flight that long) or ``puts_idle`` (no
    put ran inside it)."""
    slow = None if usual_us is None else PUT_SLOW_FACTOR * usual_us
    if fastest_us is not None:
        return ("puts_flowing" if slow is None or fastest_us < slow
                else "puts_blocked")
    if in_flight_us is not None and in_flight_us >= (slow or 0.0):
        return "puts_blocked"
    return "puts_idle"


def _long_hold(run: _RunningHold, t1_us: float, hold_us: float,
               cpu_us: float, put_since_us: Optional[float]) -> None:
    """Build the record of one long hold on the consumer's thread and send
    it out three ways: the event ``long_hold``, one WARNING line on the
    package's logger, and a flight dump (a file only where
    ``DMLC_TRACE_DUMP`` is set). At most one record in
    :data:`HOLD_RECORD_GAP_S` and :data:`HOLD_RECORDS_MAX` a process; the
    counter has counted the hold already. The verdict, in this order:
    ``compile`` (compile time inside the hold is half the excess or more),
    ``host_frozen`` (both pulses that late), ``gil_held`` (the Python pulse
    alone), ``consumer_busy`` (the consumer's CPU time is half the hold or
    more), else ``consumer_waiting`` with what the puts did beside it."""
    global _hold_records, _hold_record_at
    with _lock:
        now = time.monotonic()
        if (_hold_records >= HOLD_RECORDS_MAX
                or now - _hold_record_at < HOLD_RECORD_GAP_S):
            return
        _hold_records += 1
        _hold_record_at = now
    try:
        ru0, ru1 = run.rusage, resource.getrusage(resource.RUSAGE_SELF)
        marks = _compile_marks()
        compile_us = marks[0] - run.marks[0]
        py_late, native_late = _pulse_late_us(run.t0_us, t1_us)
        lanes, puts, fastest = _spans_over(run.t0_us, t1_us)
        half = (hold_us - run.median_us) / 2
        if compile_us >= half:
            verdict = "compile"
        elif py_late >= half and (native_late or 0.0) >= half:
            verdict = "host_frozen"
        elif py_late >= half:
            verdict = "gil_held"
        elif cpu_us >= hold_us / 2:
            verdict = "consumer_busy"
        else:
            verdict = "consumer_waiting"
        rec = {"verdict": verdict, "hold_us": int(hold_us),
               "median_us": int(run.median_us), "cpu_us": int(cpu_us),
               "pulse_py_late_us": int(py_late),
               "pulse_native_late_us":
                   None if native_late is None else int(native_late),
               "compile_us": int(compile_us),
               "step_builds": int(marks[1] - run.marks[1]),
               "utime_us": int((ru1.ru_utime - ru0.ru_utime) * 1e6),
               "stime_us": int((ru1.ru_stime - ru0.ru_stime) * 1e6),
               "nvcsw": ru1.ru_nvcsw - ru0.ru_nvcsw,
               "nivcsw": ru1.ru_nivcsw - ru0.ru_nivcsw,
               "majflt": ru1.ru_majflt - ru0.ru_majflt,
               "put_spans": puts, "lanes": lanes,
               "stack": run.stack or []}
        if verdict == "consumer_waiting":
            rec["puts"] = _puts_state(
                fastest, puts["usual_us"],
                None if put_since_us is None else t1_us - put_since_us)
        emit_event("long_hold", **rec)
        logging.getLogger("dmlc_core_tpu").warning(
            "long hold: %.1f ms for a median of %.1f: %s %s",
            hold_us / 1e3, run.median_us / 1e3,
            verdict + ("/" + rec["puts"] if "puts" in rec else ""),
            json.dumps(rec, separators=(",", ":")))
        flight_dump("long-hold: " + verdict)
    except Exception:  # a record that fails must not stop the loop it watches
        logging.getLogger("dmlc_core_tpu").exception("long hold: no record")


# -- cluster aggregation (the tracker's /metrics + /trace) -------------------
def rank_export(max_spans: int = 2048) -> dict:
    """The per-rank telemetry document a worker ships to the tracker in
    answer to a TELEMETRY_PULL frame (doc/observability.md "Cluster
    aggregation"): the merged metric snapshot plus the span ring
    flattened onto the WALL clock (each half shifted by its own anchor
    pair, so the tracker merges ranks without knowing their monotonic
    epochs). Spans are capped at the most recent ``max_spans`` to bound
    the frame."""
    snap = snapshot()
    wall = _wall_spans(trace_snapshot())
    if len(wall) > max_spans:
        wall = wall[-max_spans:]
    return {"pid": os.getpid(), "anchor": snap["anchor"],
            "metrics": {"counters": snap["counters"],
                        "gauges": snap["gauges"],
                        "histograms": snap["histograms"]},
            "spans": wall}


def _aggregate_ranks(per_rank: Dict[int, dict]) -> dict:
    """Element-wise job sums across rank metric docs: counters by (name,
    labels); histograms by (name, labels) with bucket-wise addition."""
    counters: Dict[tuple, float] = {}
    hists: Dict[tuple, dict] = {}
    for doc in per_rank.values():
        m = doc.get("metrics", {})
        for c in m.get("counters", ()):
            key = (c["name"], _labels_key(c.get("labels")))
            counters[key] = counters.get(key, 0) + c["value"]
        for h in m.get("histograms", ()):
            key = (h["name"], _labels_key(h.get("labels")))
            agg = hists.get(key)
            if agg is None:
                hists[key] = {"count": h["count"], "sum": h["sum"],
                              "buckets": list(h["buckets"])}
            else:
                agg["count"] += h["count"]
                agg["sum"] += h["sum"]
                agg["buckets"] = [a + b for a, b in
                                  zip(agg["buckets"], h["buckets"])]
    return {"counters": counters, "histograms": hists}


def cluster_prometheus_text(per_rank: Dict[int, dict],
                            local_snap: Optional[dict] = None) -> str:
    """The job-wide Prometheus exposition a live tracker serves at
    ``GET /metrics``: the tracker process's own merged snapshot
    (unchanged — back-compatible with single-process scrapes), every
    pulled rank's series re-labeled with ``rank="<r>"``, and job-level
    sums under the ``job:<name>`` aggregate-naming convention (counters
    summed value-wise, histograms bucket-wise) so job counters equal the
    per-rank sums counter-for-counter. One ``# HELP``/``# TYPE`` pair per
    metric name across the whole document."""
    if local_snap is None:
        local_snap = snapshot()
    # family-grouped: the tracker's own series and every rank's
    # rank="r"-labeled series of one metric land in ONE contiguous group
    # (the exposition format's grouping rule)
    fams: Dict[str, dict] = {}
    _collect_doc(fams, local_snap)
    for rank in sorted(per_rank):
        _collect_doc(fams, per_rank[rank].get("metrics", {}),
                     extra=f'rank="{rank}"')
    agg = _aggregate_ranks(per_rank)
    for (name, labels), value in sorted(agg["counters"].items()):
        f = fams.setdefault("job:" + name, {
            "kind": "counter",
            "help": f"job-wide sum of {name} across ranks", "lines": []})
        f["lines"].append(f"job:{name}{_fmt_labels(dict(labels))} "
                          f"{_fmt_value(value)}")
    for (name, labels), h in sorted(agg["histograms"].items()):
        f = fams.setdefault("job:" + name, {
            "kind": "histogram",
            "help": f"job-wide bucket-wise sum of {name} across ranks",
            "lines": []})
        _render_hist_series(f["lines"], "job:" + name, dict(labels), h)
    return _emit_families(fams)


def cluster_trace_json(per_rank: Dict[int, dict],
                       local_trace: Optional[dict] = None,
                       meta: Optional[dict] = None) -> str:
    """The merged job timeline a live tracker serves at ``GET /trace``:
    one Chrome-trace/Perfetto document with a process lane PER RANK (the
    event ``pid`` is the rank, the lane is labeled with the rank and its
    OS pid) plus the tracker's own lane. Every rank's spans arrive
    already wall-clock-shifted by that rank's anchor pair
    (:func:`rank_export`), so the lanes share one timeline. ``meta``
    (e.g. the tracker's :func:`straggler_attribution` verdict) rides as
    one metadata ("M") event on the tracker lane."""
    evs: List[dict] = []
    for rank in sorted(per_rank):
        doc = per_rank[rank]
        evs += _chrome_events(doc.get("spans", ()), rank,
                              f"rank {rank} (pid {doc.get('pid', '?')})")
    if local_trace is None:
        local_trace = trace_snapshot()
    evs += _chrome_events(_wall_spans(local_trace), 999999,
                          f"tracker (pid {local_trace.get('pid', '?')})")
    if meta:
        evs.append({"ph": "M", "name": "job_meta", "pid": 999999,
                    "tid": 0, "args": dict(meta)})
    return json.dumps({"traceEvents": evs, "displayTimeUnit": "ms"})


def _native_lib_if_loaded():
    """The loaded ctypes library, or None. NEVER triggers the native
    build: a tracker-only process (or a scrape) must not block minutes on
    a C++ compile just to report its own metrics."""
    try:
        from dmlc_core_tpu.io import native as _native
    except Exception:  # jax/numpy missing in a minimal tracker venv
        return None
    return _native._lib


def _native_snapshot_dict(force: bool) -> Optional[dict]:
    if force:
        from dmlc_core_tpu.io import native as _native
        _native.lib()
    lib = _native_lib_if_loaded()
    if lib is None:
        return None
    import ctypes
    out = ctypes.c_char_p()
    if lib.dct_telemetry_snapshot(ctypes.byref(out)) != 0:
        return None
    try:
        doc = json.loads(ctypes.string_at(out).decode())
    finally:
        lib.dct_str_free(out)
    return doc


def snapshot(native: Optional[bool] = None) -> dict:
    """The merged telemetry document — the single source every surface
    serves (C ABI consumers read the native half directly; the tracker's
    ``GET /metrics`` renders this via :func:`prometheus_text`).

    ``native``: ``None`` (default) merges the native registry only when
    the library is ALREADY loaded (never triggers a build); ``True``
    forces loading/building it; ``False`` excludes it.

    Schema (version 1, append-only): ``{"version", "enabled", "anchor":
    {"wall_us", "perf_us"}, "native": bool, "native_anchor": {...}|None,
    "counters": [{"name", "labels", "value"}], "gauges": [...],
    "histograms": [{"name", "labels", "count", "sum", "buckets":
    [HIST_BUCKETS+1 counts]}], "events": [...]}``. The anchor is this
    process's (wall, monotonic) clock pair; ``native_anchor`` the native
    half's (wall, steady) pair from the same snapshot. The gauge list
    ends with the derived stall-attribution gauges
    (``stall_stage_occupancy{stage=}`` + ``stall_verdict_code``,
    :func:`stall_attribution`)."""
    with _lock:
        collectors = list(_collectors)
    for fn in collectors:
        try:
            fn()
        except Exception:
            pass  # a broken collector must not sink the scrape
    doc = {"version": SNAPSHOT_VERSION, "enabled": enabled(),
           "anchor": clock_anchor(), "native": False,
           "native_anchor": None, "counters": [], "gauges": [],
           "histograms": [], "events": []}
    if native is not False:
        nat = _native_snapshot_dict(force=bool(native))
        if nat is not None:
            doc["native"] = True
            doc["native_anchor"] = nat.get("anchor")
            doc["counters"] += nat.get("counters", [])
            doc["gauges"] += nat.get("gauges", [])
            doc["histograms"] += nat.get("histograms", [])
    with _lock:
        for c in _counters.values():
            doc["counters"].append({"name": c.name, "labels": c.labels,
                                    "value": c.value})
        for g in _gauges.values():
            doc["gauges"].append({"name": g.name, "labels": g.labels,
                                  "value": g.value})
        for h in _hists.values():
            rec = {"name": h.name, "labels": h.labels, "count": h.count,
                   "sum": h.sum, "buckets": list(h.buckets)}
            if h.exemplars:
                # JSON-snapshot only (never the text exposition): the
                # bucket -> last-sampled-trace-id breadcrumbs
                rec["exemplars"] = dict(h.exemplars)
            doc["histograms"].append(rec)
        doc["events"] = list(_events)
        # the Python ring's overflow count, labeled so it can never
        # collide with the native half's spans_dropped_total sample
        doc["counters"].append({"name": "spans_dropped_total",
                                "labels": {"half": "python"},
                                "value": _spans_dropped})
    # derived stall-attribution gauges ride every snapshot (and therefore
    # every /metrics scrape) without a collector: they are computed FROM
    # the snapshot, so a collector would recurse
    att = stall_attribution(doc)
    for stage, frac in att["occupancy"].items():
        doc["gauges"].append({"name": "stall_stage_occupancy",
                              "labels": {"stage": stage}, "value": frac})
    doc["gauges"].append({"name": "stall_verdict_code", "labels": {},
                          "value": VERDICT_CODES[att["verdict"]]})
    # same derivation rule for the device lane's overlap ratio: computed
    # FROM the span ring at snapshot time (doc/observability.md "Device
    # lane"); -1 marks "no transfer observed yet", keeping 0 meaningful
    # (a lane that ran fully exposed)
    ratio = device_overlap_ratio()
    doc["gauges"].append({"name": "device_overlap_ratio", "labels": {},
                          "value": -1.0 if ratio is None else ratio})
    return doc


def _escape_label(v: str) -> str:
    """Prometheus label-value escaping: backslash, double-quote, newline."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
                 .replace("\n", "\\n")


def _fmt_labels(labels: Dict[str, str], extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(v)}"' for k, v in sorted(labels.items())]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt_value(v) -> str:
    if isinstance(v, float) and not v.is_integer():
        return repr(v)
    return str(int(v))


# One-line HELP text per cataloged metric name, emitted as ``# HELP``
# exposition lines (doc/observability.md is the long-form catalog).
# Uncataloged names (tests, ad-hoc metrics) simply carry no HELP line.
# MACHINE-CHECKED (scripts/analyze.py Pass 4, doc/analysis.md): every
# metric registered in shipped code — either half — must have an entry
# here AND a doc/observability.md catalog row, and every entry here must
# match a live registration; `make analyze` fails on drift either way.
METRIC_HELP: Dict[str, str] = {
    "io_requests_total": "HTTP requests sent",
    "io_retries_total": "backoff sleeps taken",
    "io_backoff_ms_total": "total milliseconds slept in backoff",
    "io_timeouts_total": "per-attempt socket timeout expiries",
    "io_faults_injected_total": "DMLC_IO_FAULT_PLAN firings",
    "io_giveups_total": "retry loops that exhausted their budget",
    "io_deadline_exhausted_total": "giveups caused by the per-op deadline",
    "io_connect_us": "TCP connect latency per request (us)",
    "io_ttfb_us": "request-sent to first response byte (us)",
    "io_recv_us": "one response-body pull (us)",
    "io_range_issued_total": "range fetches issued",
    "io_range_retried_total": "per-range retry attempts",
    "io_range_degraded_200_total":
        "streams degraded to the sequential lane (origin ignored Range)",
    "io_range_bytes": "completed range sizes (bytes)",
    "io_range_wait_us": "consumer head-of-line wait (us)",
    "io_range_sched_bytes": "scheduler's current range size",
    "io_range_sched_concurrency": "scheduler's current worker credit",
    "parse_chunks_read_total": "chunks admitted by reader stages",
    "parse_blocks_delivered_total": "row blocks handed to consumers",
    "parse_reader_waits_total": "reader blocked on the in-flight bound",
    "parse_worker_waits_total": "worker slept with no claimable slice",
    "parse_consumer_waits_total":
        "consumer slept on the head-of-line chunk",
    "parse_cells_total":
        "feature cells of the lines a cell-counting text format parsed",
    "parse_cells_missing_total": "of those cells, the empty ones (skipped)",
    "split_open_us":
        "one open the split's reader waited out: no stream open, to the "
        "first byte read from the new one (us)",
    "split_objects_opened_total":
        "streams a split opened: a part's first object after BeforeFirst "
        "and every next one after the one before it was drained",
    "split_bytes_read_total": "bytes a split read from its objects",
    "parse_stage_fill_us": "one ReadChunk, source to owned bytes (us)",
    "parse_stage_scan_us": "one TileCuts slice pre-tiling (us)",
    "parse_stage_parse_us": "one worker slice decode (us)",
    "parse_stage_reassemble_wait_us":
        "one consumer head-of-line wait (us)",
    "cache_hits_total": "epochs served from a validated binary shard",
    "cache_misses_total": "epochs served from the text lane",
    "cache_transcodes_total": "completed atomically-published transcodes",
    "cache_write_errors_total":
        "transcode passes lost to local-I/O failure (quarantined)",
    "cache_read_us": "one replay block hand-out (us)",
    "cache_write_us": "one transcoded block append (us)",
    "fs_fault_injected_total": "DMLC_FS_FAULT_PLAN firings per op",
    "ckpt_save_failures_total": "checkpoint saves that raised",
    "event_log_dropped_total":
        "tracker event-log lines dropped by a contained I/O failure",
    "rowblock_batch_us": "one RowBlockIter native block pull (us)",
    "rowblock_batches_total": "row blocks served",
    "rowblock_skipped_batches_total": "on_error=skip skips",
    "device_transfer_us": "one device_put, submit to arrays ready (us)",
    "device_put_submit_us": "the device_put dispatch alone (us)",
    "device_put_block_us": "dispatch-to-ready DMA wait (us)",
    "device_batches_total": "batches dispatched to the device",
    "device_transfer_bytes_total": "host bytes handed to device_put",
    "device_nnz_sent_total":
        "nnz entries of the CSR batches dispatched to the device, padding "
        "included: shards x the batch's nnz bucket",
    "device_nnz_real_total":
        "of the entries sent, the real nonzeros, as the batcher's fill "
        "counted them",
    "device_cols_distinct_total":
        "distinct columns of the CSR batches dispatched, summed over their "
        "shards, as the batcher's dedupe counted them: the rows a step "
        "gathers and scatters",
    "device_cols_owner_max_total":
        "where the columns have several key-range owners: of a batch's "
        "distinct columns, those of the owner that got the most",
    "device_stretch_sent_total":
        "where the columns have several key-range owners: positions of the "
        "owner-major lists dispatched (shards x owners x stretch capacity)",
    "device_stretch_real_total":
        "of the stretch positions sent, the distinct columns in them",
    "device_tail_batches_total":
        "short last batches sent at the rungs of the batch before them, "
        "their own being lower (the padding is in device_nnz_sent_total)",
    "device_stage_us":
        "one host batch assembly (parse+pad+bucket+pack) on the staging "
        "thread (us)",
    "device_wait_us":
        "consumer head-of-line wait for the next device batch (us)",
    "device_first_batch_wait_us":
        "consumer wait for the first batch after a (re)start: how late an "
        "epoch's first batch comes (us)",
    "device_turnover_us":
        "one before_first(): staging threads joined and the batcher "
        "reset (us)",
    "device_hold_us":
        "the consumer's hold of one batch: handed over, to its asking for "
        "the next (its step and loss read); with device_wait_us the "
        "consumer's whole time (us)",
    "device_hold_excess_us":
        "a hold less the median of the iterator's last 64, 0 where under "
        "it or before eight holds: observed every hold, so its sum is the "
        "time lost to stray stalls (us)",
    "device_long_holds_total":
        "holds that ended max(50 ms, median / 2) or more over the median; "
        "each is the event long_hold with a verdict, at most one record a "
        "second and 64 a process",
    "pulse_py_late_us":
        "how late a Python thread woke from a nap of 20 ms: it needs the "
        "interpreter lock, so a held lock or a frozen host shows at its "
        "length (us)",
    "pulse_native_late_us":
        "how late a native thread woke from a nap of 20 ms: it needs no "
        "interpreter lock, so only a frozen host or process shows (us)",
    "model_step_dispatch_us":
        "one learner step handed to the runtime, host side; the call that "
        "built the step function traces and compiles inside it (us)",
    "model_step_builds_total":
        "jitted step functions built, one per new batch signature, by "
        "model class",
    "model_step_row_updates_total":
        "steps that took the row form (a CSR batch, on one device or on a "
        "mesh): the gradient stays in the batch's rows, by model class",
    "model_step_rule_updates_total":
        "steps that ran an update rule other than plain SGD (FMLearner "
        "updater='ftrl': FTRL-proximal on w, AdaGrad on v, with a state "
        "for every row), by model class and rule",
    "model_state_bytes":
        "bytes of the state a learner's init made, one copy, by model class "
        "and part: params (the model's tables) or slots (what its update "
        "rule keeps beside them)",
    "model_step_state_copies_total":
        "states copied on the device before a step because they were not "
        "what the learner's last step returned (init's, a restored "
        "checkpoint's, a caller's own); every other step updates its tables "
        "where they lie, by model class",
    "model_step_allreduce_bytes_total":
        "bytes handed to the collectives of the mesh step (loss sum, weight "
        "sum, and every shard's distinct columns with the rows of its "
        "gradient, pulled and pushed on range-sharded tables, or, in the "
        "table form, a gradient of the parameters' shapes), by model class; "
        "0 on one device",
    "device_put_failures_total": "device_put calls that raised",
    "device_host_q_depth": "staged host batches queued for transfer",
    "device_ready_q_depth": "device batches queued for the consumer",
    "device_compile_events_total":
        "first sight of a device batch shape (one XLA re-trace per "
        "jitted consumer)",
    "device_distinct_shapes": "distinct device batch shapes this process",
    "device_zero_copy_batches_total":
        "batches transferred by the zero-copy device_put path (staging "
        "buffers aliased/DMA'd in place, no host copy)",
    "device_zero_copy_fallbacks_total":
        "batches that fell back to the copying device_put path, by reason",
    "device_recycle_skipped":
        "aliased host staging buffers dropped from the deferred-recycle "
        "parking lot because the consumer held more batches than its "
        "depth (zero-copy backends)",
    "device_alias_probe_total":
        "one probe per iterator of whether device arrays alias the host "
        "staging buffers, by verdict (no_alias, aliases, unprobeable)",
    "device_jit_compiles_total":
        "trips through the backend-compile stage (jax.monitoring), "
        "persistent-cache hits included",
    "device_compile_cache_hits_total":
        "backend-compile trips the persistent compilation cache answered",
    "device_compile_us":
        "one XLA compilation phase: trace, lower or backend compile "
        "(us, jax.monitoring)",
    "device_overlap_ratio":
        "fraction of transfer time hidden behind consumer compute "
        "(-1 before any transfer)",
    "tracker_num_workers": "workers the tracker expects",
    "tracker_alive": "1 while the tracker thread is serving",
    "tracker_finished": "1 once every worker checked out",
    "tracker_aborted": "1 after the job was aborted",
    "tracker_rank_phase_code":
        "0 assigned, 1 alive, 2 dead, 3 shutdown, 4 lost",
    "tracker_rank_heartbeat_age_seconds":
        "seconds since the rank's last beat (-1 before the first)",
    "tracker_rank_restarts": "recover count per rank",
    "tracker_rank_attempts": "assignment handshakes served per rank",
    "telemetry_events_total": "events per kind",
    "tracker_lease_pool": "shards free for acquisition",
    "tracker_lease_held": "shards currently leased to a rank",
    "tracker_lease_done": "shards checked out exactly once",
    "tracker_lease_reassigned": "leases reclaimed this epoch",
    "tracker_lease_reassigned_total": "reclaim events across the job",
    "lease_renew_us": "tracker-side implicit lease renewal on a ping (us)",
    "lease_acquire_us": "worker-side acquire round trip (us)",
    # elastic mesh training (doc/robustness.md "Elastic mesh training")
    "tracker_world_relaunches_total":
        "whole-world relaunches after a mesh abort (run_job mesh mode)",
    "mesh_step_aborts_total":
        "structured step aborts on this rank (between-steps raise or "
        "step-deadline watchdog)",
    "device_abort_drains_total":
        "device-pipeline abort drains (staging/transfer stopped, parked "
        "buffers dropped)",
    "stall_stage_occupancy":
        "fraction of instrumented batch-path time in the stage",
    "stall_verdict_code":
        "-1 unknown, 0 fill, 1 parse, 2 consumer, 3 transfer, 4 stage, "
        "5 compile, 6 straggler bound",
    "spans_dropped_total":
        "span-ring records overwritten by wrap, per half",
    # SLO plane (WindowedView/SloMonitor, doc/observability.md "SLO plane")
    "window_rate":
        "per-second counter rate over the rolling window, summed across "
        "label sets",
    "window_quantile":
        "delta-histogram quantile over the rolling window (overflow "
        "clamped to the top bucket bound)",
    "slo_burn_rate":
        "error-budget burn multiple per objective and window",
    "slo_page": "1 while any SLO objective is paging (latched)",
    "slo_page_trips_total": "SLO page activations per objective",
    "tracker_straggler_rank":
        "rank bound as the mesh straggler (-1 when none)",
    # measurement rig (scripts/loadrig.py, doc/benchmarking.md)
    "rig_requests_total": "open/closed-loop requests completed",
    "rig_errors_total": "load-generator requests that failed",
    "rig_shed_total":
        "open-loop arrivals shed past the lateness budget",
    "rig_intended_us":
        "request latency from the INTENDED start time (us; "
        "coordinated-omission-safe)",
    "rig_service_us":
        "request latency from the actual send time (us; hides queueing "
        "behind a stalled origin — kept for the divergence itself)",
    # online scoring plane (dmlc_core_tpu/serving/, doc/serving.md)
    "serve_requests_total": "HTTP requests parsed by the front end",
    "serve_admitted_total": "score requests admitted to the queue",
    "serve_scored_total": "score requests answered 200 with scores",
    "serve_shed_total":
        "requests shed by reason: queue_full, late (intended-time "
        "lateness budget), draining, breaker, slo_burn",
    "serve_rejects_total":
        "error responses by HTTP status code (sheds are additionally "
        "counted by reason in serve_shed_total)",
    "serve_errors_total": "5xx server-side failures (forward/internal)",
    "serve_queue_depth": "admission queue occupancy (bounded)",
    "serve_inflight": "admitted requests awaiting their response",
    "serve_batches_total": "micro-batches run through the forward",
    "serve_batch_rows": "real (pre-padding) rows per micro-batch",
    "serve_batch_fill":
        "percent of the padded rows bucket holding real rows",
    "serve_parse_us": "micro-batch native parse time (us)",
    "serve_forward_us": "padded-batch jitted forward time (us)",
    "serve_request_us":
        "admit-to-reply latency on the INTENDED-time clock (us; queue "
        "wait included, coordinated-omission-safe)",
    "serve_model_reloads_total": "model reloads that swapped params in",
    "serve_model_reload_failures_total":
        "failed reloads (last-good model kept serving)",
    "serve_breaker_state": "0 closed, 1 open, 2 half-open",
    "serve_draining": "1 while draining shutdown runs",
    "serve_distinct_shapes":
        "distinct padded (kind, rows, nnz) forward shapes this process",
    "serve_access_log_dropped_total":
        "access-log lines dropped by a contained I/O failure",
}


def _escape_help(text: str) -> str:
    """HELP-line escaping per the exposition spec: backslash and
    newline only (label-value escaping additionally covers quotes)."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _render_hist_series(lines: List[str], name: str, labels: Dict[str, str],
                        h: dict) -> None:
    """One histogram's cumulative ``_bucket{le=}`` / ``_sum`` /
    ``_count`` sample lines."""
    cum = 0
    for i, n in enumerate(h["buckets"]):
        cum += n
        le = "+Inf" if i == len(h["buckets"]) - 1 else str(1 << i)
        le_label = 'le="' + le + '"'
        lines.append(f"{name}_bucket{_fmt_labels(labels, le_label)} {cum}")
    lines.append(f"{name}_sum{_fmt_labels(labels)} "
                 f"{_fmt_value(h['sum'])}")
    lines.append(f"{name}_count{_fmt_labels(labels)} "
                 f"{_fmt_value(h['count'])}")


def _family(fams: Dict[str, dict], name: str, kind: str) -> List[str]:
    """The sample-line bucket for one metric family (first-seen order,
    first-seen kind)."""
    f = fams.get(name)
    if f is None:
        f = fams[name] = {"kind": kind, "lines": []}
    return f["lines"]


def _collect_doc(fams: Dict[str, dict], doc: dict, extra: str = "") -> None:
    """Bucket one metric document's counters/gauges/histograms by family,
    with an optional extra label (``rank="0"``) appended to every
    sample."""
    for c in doc.get("counters", ()):
        _family(fams, c["name"], "counter").append(
            f"{c['name']}{_fmt_labels(c['labels'], extra)} "
            f"{_fmt_value(c['value'])}")
    for g in doc.get("gauges", ()):
        _family(fams, g["name"], "gauge").append(
            f"{g['name']}{_fmt_labels(g['labels'], extra)} "
            f"{_fmt_value(g['value'])}")
    for h in doc.get("histograms", ()):
        labels = dict(h["labels"])
        if extra:
            k, v = extra.split("=", 1)
            labels[k] = v.strip('"')
        _render_hist_series(_family(fams, h["name"], "histogram"),
                            h["name"], labels, h)


def _emit_families(fams: Dict[str, dict]) -> str:
    """Render bucketed families as exposition text: every line of one
    metric family contiguous (the format's grouping rule — interleaved
    families are rejected by strict parsers), one ``# HELP`` (from the
    :data:`METRIC_HELP` catalog, spec escaping) + ``# TYPE`` pair first."""
    lines: List[str] = []
    for name, f in fams.items():
        help_text = f.get("help") or METRIC_HELP.get(name)
        if help_text:
            lines.append(f"# HELP {name} {_escape_help(help_text)}")
        lines.append(f"# TYPE {name} {f['kind']}")
        lines += f["lines"]
    return "\n".join(lines) + "\n"


def prometheus_text(snap: Optional[dict] = None) -> str:
    """Render a snapshot (default: take one now) in the Prometheus text
    exposition format (version 0.0.4): samples grouped per metric family
    behind one ``# HELP`` (from the :data:`METRIC_HELP` catalog) +
    ``# TYPE`` pair, label escaping per the spec, histograms as
    cumulative ``_bucket{le=...}`` series ending in ``le="+Inf"`` plus
    ``_sum``/``_count``."""
    if snap is None:
        snap = snapshot()
    fams: Dict[str, dict] = {}
    _collect_doc(fams, snap)
    return _emit_families(fams)


def events_jsonl(snap: Optional[dict] = None) -> str:
    """Render a snapshot's event stream (default: take one now) as JSONL —
    the PR-4 ``DMLC_TRACKER_EVENT_LOG`` schema, one ``{"ts", "event",
    ...}`` object per line."""
    if snap is None:
        snap = snapshot()
    return "".join(json.dumps(rec) + "\n" for rec in snap.get("events", []))


# ---------------------------------------------------------------------------
# Rolling windows + SLO plane (doc/observability.md "SLO plane"): every
# registry series is process-lifetime cumulative, which is the right
# substrate (resets are visible, sums are exact) but the wrong operator
# surface — "is NOW bad" needs rates and quantiles over the last minutes,
# not since boot.  The WindowedView snapshots the merged registry (native
# + Python — deltas over snapshots, so the C++ half needs zero hot-path
# changes) on a cadence and publishes per-window rate/quantile gauges;
# the SloMonitor turns two of those windows into multi-window burn rates
# against declared objectives and latches a page with hysteresis.
# ---------------------------------------------------------------------------

# cardinality ceiling on the compact per-(name, labels) state one tick
# keeps: a test registering thousands of ad-hoc series must degrade the
# window view (silently-partial windows over the FIRST _MAX_SERIES keys),
# never the process
_MAX_SERIES = 4096


def _compact_snapshot(snap: dict) -> Tuple[Dict[tuple, float],
                                           Dict[tuple, tuple]]:
    """Reduce one merged snapshot to the per-(name, labels) counter
    values and histogram (count, sum, buckets) tuples the window math
    needs — gauges are point-in-time and carry no delta meaning, so they
    are dropped (which is also what makes :meth:`WindowedView.tick` safe
    to run off :func:`snapshot`: the derived gauges it appends are
    ignored here)."""
    counters: Dict[tuple, float] = {}
    hists: Dict[tuple, tuple] = {}
    for c in snap.get("counters", ()):
        if len(counters) >= _MAX_SERIES:
            break
        key = (c["name"], _labels_key(c.get("labels")))
        counters[key] = counters.get(key, 0.0) + float(c["value"])
    for h in snap.get("histograms", ()):
        if len(hists) >= _MAX_SERIES:
            break
        key = (h["name"], _labels_key(h.get("labels")))
        prev = hists.get(key)
        if prev is None:
            hists[key] = (int(h["count"]), float(h["sum"]),
                          tuple(h["buckets"]))
        else:
            hists[key] = (prev[0] + int(h["count"]),
                          prev[1] + float(h["sum"]),
                          tuple(a + b for a, b in
                                zip(prev[2], h["buckets"])))
    return counters, hists


class SloMonitor:
    """Multi-window burn-rate monitor over a :class:`WindowedView`
    (doc/observability.md "SLO plane").

    Two declared objectives, both on the serving plane's own series:
    **availability** (fraction of non-error, non-shed answers,
    ``DMLC_SLO_AVAILABILITY_TARGET``) and **latency** (fraction of
    answers under ``DMLC_SLO_LATENCY_TARGET_MS`` on the intended-time
    clock, ``DMLC_SLO_LATENCY_TARGET``). Each objective's burn rate —
    (bad fraction over the window) / (error budget) — is published per
    window as ``slo_burn_rate{slo=,window=}``; a page latches when EVERY
    window burns at ``DMLC_SLO_FAST_BURN`` or above (the multi-window
    rule: the fast window proves it is happening NOW, the slow window
    proves it is not a blip) and clears with hysteresis when the fastest
    window drops under ``DMLC_SLO_CLEAR_BURN``. A page flips
    ``slo_page``, bumps ``slo_page_trips_total{slo=}``, and lands a
    flight dump naming the tripping windows and burn values.

    Sheds the admission gate took BECAUSE of the page (``reason=
    "slo_burn"``) are excluded from the bad count — otherwise the
    monitor's own load-shedding would hold the burn high forever and the
    page could never clear once the underlying fault lifted."""

    def __init__(self):
        from dmlc_core_tpu.tracker.wire import env_float, env_int
        self.availability_target = env_float(
            "DMLC_SLO_AVAILABILITY_TARGET", 0.999)
        self.latency_target_ms = env_int("DMLC_SLO_LATENCY_TARGET_MS", 250)
        self.latency_target = env_float("DMLC_SLO_LATENCY_TARGET", 0.99)
        self.fast_burn = env_float("DMLC_SLO_FAST_BURN", 14.4)
        self.slow_burn = env_float("DMLC_SLO_SLOW_BURN", 6.0)
        self.clear_burn = env_float("DMLC_SLO_CLEAR_BURN", 1.0)
        self._paging: set = set()
        self._page_gauge = gauge("slo_page")

    @property
    def paging(self) -> bool:
        """Whether any objective is currently paging (latched)."""
        return bool(self._paging)

    @staticmethod
    def _availability_burn(dcounters: Dict[tuple, float],
                           budget: float) -> float:
        good = bad = 0.0
        for (name, labels), v in dcounters.items():
            v = max(v, 0.0)
            if name == "serve_scored_total":
                good += v
            elif name == "serve_errors_total":
                bad += v
            elif name == "serve_shed_total":
                if dict(labels).get("reason") != "slo_burn":
                    bad += v
        total = good + bad
        if total <= 0:
            return 0.0
        return (bad / total) / budget

    def _latency_burn(self, dhists: Dict[tuple, tuple],
                      budget: float) -> float:
        count = 0
        buckets = [0] * (HIST_BUCKETS + 1)
        for (name, _labels), (dc, _ds, db) in dhists.items():
            if name != "serve_request_us":
                continue
            count += max(dc, 0)
            for i, n in enumerate(db):
                buckets[i] += max(n, 0)
        if count <= 0:
            return 0.0
        target_us = self.latency_target_ms * 1000
        good = sum(n for i, n in enumerate(buckets)
                   if i < HIST_BUCKETS and (1 << i) <= target_us)
        bad = max(count - good, 0)
        return (bad / count) / budget

    def evaluate(self, deltas: Dict[str, tuple]) -> None:
        """Evaluate both objectives over one tick's per-window deltas
        (``{window_label: (elapsed_s, dcounters, dhists)}`` from
        :meth:`WindowedView.deltas`), publish the burn gauges, and run
        the page/clear latch."""
        if not deltas:
            return
        burns: Dict[str, Dict[str, float]] = {"availability": {},
                                              "latency": {}}
        avail_budget = max(1.0 - self.availability_target, 1e-9)
        lat_budget = max(1.0 - self.latency_target, 1e-9)
        for label, (_elapsed, dcounters, dhists) in deltas.items():
            burns["availability"][label] = self._availability_burn(
                dcounters, avail_budget)
            burns["latency"][label] = self._latency_burn(
                dhists, lat_budget)
        # the hysteresis clear reads the most responsive window — the
        # one whose delta spans the least elapsed time
        fastest = min(deltas, key=lambda lb: deltas[lb][0])
        for slo, per_window in burns.items():
            for label, burn in per_window.items():
                labels = {"slo": slo, "window": label}
                gauge("slo_burn_rate", labels).set(round(burn, 4))
            if slo not in self._paging:
                if per_window and min(per_window.values()) >= \
                        self.fast_burn:
                    self._paging.add(slo)
                    counter("slo_page_trips_total", {"slo": slo}).inc()
                    detail = ", ".join(
                        f"{lb}={b:.1f}x" for lb, b in
                        sorted(per_window.items()))
                    emit_event("slo-page", slo=slo, burns=detail)
                    flight_dump(f"slo-page: {slo} burn [{detail}] >= "
                                f"{self.fast_burn}x budget")
            elif per_window.get(fastest, 0.0) < self.clear_burn:
                self._paging.discard(slo)
                emit_event("slo-page-clear", slo=slo)
        self._page_gauge.set(1.0 if self._paging else 0.0)


class WindowedView:
    """Rolling-window view over the cumulative registry
    (doc/observability.md "SLO plane").

    A daemon ticker (cadence ``DMLC_SLO_TICK_MS``) takes compact
    registry snapshots and keeps just enough of them to serve deltas for
    each configured window (default ``fast`` = ``DMLC_SLO_WINDOW_FAST_S``
    and ``slow`` = ``DMLC_SLO_WINDOW_SLOW_S``; knob-scaled down to
    sub-second in tests). Every tick publishes, per window:
    ``window_rate{name=,window=}`` (counter delta per second, summed
    across label sets) and ``window_quantile{name=,window=,q=}``
    (p50/p99 from the window's DELTA histogram buckets via
    :func:`quantile_from_buckets`, overflow clamped to the top bucket
    bound) — ordinary gauges, so every ``/metrics`` surface serves them
    with zero extra plumbing. An attached :class:`SloMonitor` (serving
    processes) is fed the same deltas.

    Use the module helpers :func:`start_windowed_view` /
    :func:`stop_windowed_view` (refcounted process singleton);
    :meth:`tick` is public so tests can drive the clock
    deterministically with ``now=``."""

    def __init__(self, windows: Optional[Dict[str, float]] = None):
        from dmlc_core_tpu.tracker.wire import env_int
        self.tick_s = max(env_int("DMLC_SLO_TICK_MS", 5000), 10) / 1000.0
        if windows is None:
            windows = {"fast": float(env_int("DMLC_SLO_WINDOW_FAST_S",
                                             300)),
                       "slow": float(env_int("DMLC_SLO_WINDOW_SLOW_S",
                                             3600))}
        self.windows = dict(windows)
        self.slo: Optional[SloMonitor] = None
        self._snaps: List[tuple] = []   # (t, counters, hists)
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- window math --------------------------------------------------------
    def tick(self, now: Optional[float] = None) -> None:
        """Take one compact snapshot at ``now`` (default: the monotonic
        clock), prune history past the longest window, publish the
        window gauges, and feed the SLO monitor."""
        if now is None:
            now = time.monotonic()
        counters, hists = _compact_snapshot(snapshot())
        horizon = max(self.windows.values()) + 2 * self.tick_s
        with self._mu:
            self._snaps.append((now, counters, hists))
            while len(self._snaps) > 2 and self._snaps[1][0] < \
                    now - horizon:
                self._snaps.pop(0)
            deltas = self._deltas_locked(now)
        self._publish(deltas)
        if self.slo is not None:
            self.slo.evaluate(deltas)

    def _baseline_locked(self, now: float, seconds: float):
        base = None
        for rec in self._snaps:
            if rec[0] <= now - seconds:
                base = rec           # newest snap at/before window start
            else:
                break
        return base or self._snaps[0]

    def _deltas_locked(self, now: float) -> Dict[str, tuple]:
        out: Dict[str, tuple] = {}
        if len(self._snaps) < 2:
            return out
        cur_t, cur_c, cur_h = self._snaps[-1]
        for label, seconds in self.windows.items():
            base_t, base_c, base_h = self._baseline_locked(now, seconds)
            elapsed = cur_t - base_t
            if elapsed <= 0:
                continue
            dcounters = {k: v - base_c.get(k, 0.0)
                         for k, v in cur_c.items()}
            dhists = {}
            for k, (c, s, b) in cur_h.items():
                bc, bs, bb = base_h.get(k, (0, 0.0, (0,) * len(b)))
                dhists[k] = (c - bc, s - bs,
                             tuple(x - y for x, y in zip(b, bb)))
            out[label] = (elapsed, dcounters, dhists)
        return out

    def deltas(self) -> Dict[str, tuple]:
        """This instant's per-window ``(elapsed_s, dcounters, dhists)``
        map (the same structure :meth:`tick` publishes from) — the raw
        material for tests and ad-hoc window math."""
        with self._mu:
            return self._deltas_locked(time.monotonic())

    def _publish(self, deltas: Dict[str, tuple]) -> None:
        top = float(1 << HIST_BUCKETS)  # overflow clamp: top bucket bound
        for label, (elapsed, dcounters, dhists) in deltas.items():
            rates: Dict[str, float] = {}
            for (name, _labels), v in dcounters.items():
                rates[name] = rates.get(name, 0.0) + max(v, 0.0)
            for name, total in rates.items():
                gauge("window_rate",
                      {"name": name, "window": label}).set(
                          round(total / elapsed, 4))
            per_name: Dict[str, tuple] = {}
            for (name, _labels), (dc, _ds, db) in dhists.items():
                pc, pb = per_name.get(
                    name, (0, (0,) * (HIST_BUCKETS + 1)))
                per_name[name] = (pc + max(dc, 0),
                                  tuple(x + max(y, 0)
                                        for x, y in zip(pb, db)))
            for name, (dc, db) in per_name.items():
                if dc <= 0:
                    continue
                for q in (0.5, 0.99):
                    val = quantile_from_buckets(list(db), dc, q)
                    gauge("window_quantile",
                          {"name": name, "window": label,
                           "q": str(q)}).set(min(val, top))

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "WindowedView":
        """Start the ticker thread (idempotent)."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="windowed-view")
            self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the ticker thread (idempotent, joins briefly)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.tick_s):
            try:
                self.tick()
            except Exception:
                pass  # a broken tick must not kill the view


_view_lock = threading.Lock()
_view: Optional[WindowedView] = None
_view_refs = 0


def start_windowed_view(slo: bool = False) -> WindowedView:
    """Start (or ref) the process :class:`WindowedView` singleton; with
    ``slo=True`` also attach the :class:`SloMonitor` (serving processes
    want the burn monitors, a tracker just wants the window series).
    Pair every call with :func:`stop_windowed_view`."""
    global _view, _view_refs
    with _view_lock:
        if _view is None:
            _view = WindowedView().start()
        if slo and _view.slo is None:
            _view.slo = SloMonitor()
        _view_refs += 1
        return _view


def stop_windowed_view(force: bool = False) -> None:
    """Drop one reference on the process view; the last drop (or
    ``force=True``, used by :func:`reset` for test isolation) stops the
    ticker and clears the singleton."""
    global _view, _view_refs
    with _view_lock:
        if _view is None:
            _view_refs = 0
            return
        _view_refs = 0 if force else max(_view_refs - 1, 0)
        if _view_refs == 0:
            v, _view = _view, None
        else:
            return
    v.stop()


def windowed_view() -> Optional[WindowedView]:
    """The live process :class:`WindowedView`, or None when no component
    has started one."""
    return _view


def slo_page_active() -> bool:
    """Whether the process SLO monitor is currently paging — the burn
    signal the serving admission gate and ``/readyz`` read."""
    v = _view
    return v is not None and v.slo is not None and v.slo.paging
