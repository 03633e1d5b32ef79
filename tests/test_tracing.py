"""Distributed tracing plane (doc/observability.md "Distributed tracing").

Covers the ISSUE 11 acceptance surface:

- The Python span ring: nesting/parenting, the bounded-ring cap, the
  disabled gate, and ``trace_json`` merging BOTH halves (native
  steady-clock spans + Python perf-counter spans) onto one wall-clock
  Chrome-trace timeline via each half's anchor pair.
- Clock anchors: every snapshot/trace/dump carries a (wall, monotonic)
  pair so cross-process merges cannot drift.
- Stall attribution: the span-derived fill/parse/consumer/transfer-bound
  verdict flips to the matching stage under an injected stall (slow mock
  origin → fill_bound, slow consumer → consumer_bound), plus the full
  deterministic synthetic matrix.
- The flight recorder: ``DMLC_TRACE_DUMP`` dumps from both halves, and —
  end to end — a SIGKILL'd elastic rank leaves a tracker-side dump whose
  event ring names the shard the dead rank held.
- Cluster aggregation, end to end with REAL worker processes: ``/trace``
  returns both ranks' batch-path spans as separate lanes on one merged
  timeline with sane per-lane ordering, and ``/metrics`` job-level
  ``job:`` sums equal the per-rank series counter-for-counter. Plus
  ``/healthz``.
- The pulse (ISSUE 36): one Python and one native thread that nap 20 ms
  and record how late they woke; each Python tick an opened span, so a
  line of the profiler's trace; one pair a process, none while telemetry
  is disabled, gone after ``enable(False)``.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.io.native import (NativeParser, native_flight_dump,
                                     native_telemetry_snapshot,
                                     native_trace_snapshot)
from dmlc_core_tpu.tracker.rendezvous import RabitTracker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "telemetry_worker.py")
ELASTIC_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "elastic_worker.py")


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    telemetry.enable(True)
    yield
    telemetry.reset()
    telemetry.enable(True)


def _libsvm_file(tmp_path, rows=2000, features=12, name="t.libsvm"):
    import random
    rng = random.Random(11)
    path = tmp_path / name
    with open(path, "w") as f:
        for i in range(rows):
            feats = " ".join(
                f"{j}:{rng.uniform(-2, 2):.5f}" for j in range(features))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


# -- the Python span ring -----------------------------------------------------
class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation (this file stays off
    jax): records what telemetry does with the class it found."""

    log = []

    def __init__(self, name, **kw):
        self.name = name
        self.log.append(("init", name, kw))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))

    def set_metadata(self, **kw):
        self.log.append(("meta", self.name, kw))


@pytest.fixture
def fake_annotation(monkeypatch):
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(telemetry, "_annotation_cls", _FakeAnnotation)
    return _FakeAnnotation.log


def test_open_span_is_a_profiler_annotation(fake_annotation):
    with telemetry.span("outer", shard=3) as outer:
        outer.set_arg("bytes", 42)
        with telemetry.span("inner"):
            pass
    # completed after the fact: the ring only, never an annotation
    telemetry.emit_span("posthoc", 1.0, 2.0)
    assert fake_annotation == [
        ("init", "dmlc.outer", {"shard": 3}), ("enter", "dmlc.outer"),
        ("meta", "dmlc.outer", {"bytes": 42}),
        ("init", "dmlc.inner", {}), ("enter", "dmlc.inner"),
        ("exit", "dmlc.inner"), ("exit", "dmlc.outer")]
    # the ring keeps its own names
    assert [s["name"] for s in telemetry.spans()] == ["inner", "outer",
                                                      "posthoc"]


def test_disabled_span_makes_no_record_and_no_annotation(fake_annotation):
    telemetry.enable(False)
    with telemetry.span("quiet", rows=1) as sp:
        sp.set_arg("bytes", 2)
        assert sp.elapsed_us == 0.0
    assert fake_annotation == [] and telemetry.spans() == []
    telemetry.enable(True)
    with telemetry.span("loud") as sp:
        assert sp.elapsed_us >= 0.0
    assert [e[0] for e in fake_annotation] == ["init", "enter", "exit"]


def test_telemetry_span_leaves_jax_unimported():
    """The tracker imports telemetry and must stay off jax: a process
    that never loaded jax opens spans with no annotation and no import."""
    code = (
        "import sys\n"
        "from dmlc_core_tpu import telemetry\n"
        "with telemetry.span('x', rows=1) as sp:\n"
        "    sp.set_arg('bytes', 2)\n"
        "assert [s['name'] for s in telemetry.spans()] == ['x']\n"
        "assert telemetry._trace_annotation() is None\n"
        "bad = sorted(m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib')))\n"
        "assert not bad, bad\n"
        "print('NOJAX')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NOJAX" in r.stdout


def test_span_nesting_and_parenting():
    with telemetry.span("outer", shard=3) as outer:
        outer.set_arg("bytes", 42)
        with telemetry.span("inner"):
            pass
    got = {s["name"]: s for s in telemetry.spans()}
    assert set(got) == {"outer", "inner"}
    assert got["inner"]["parent"] == got["outer"]["id"]
    assert got["outer"]["parent"] == 0
    assert got["outer"]["args"] == {"shard": 3, "bytes": 42}
    assert got["outer"]["dur"] >= got["inner"]["dur"] >= 0


def test_span_ring_is_bounded():
    for i in range(telemetry.SPANS_MAX + 50):
        telemetry.emit_span("wrap", float(i), 1.0)
    got = telemetry.spans()
    assert len(got) == telemetry.SPANS_MAX
    # the ring keeps the most RECENT window
    assert got[0]["ts"] == 50
    assert got[-1]["ts"] == telemetry.SPANS_MAX + 49
    assert telemetry.trace_snapshot()["dropped"] == 50


def test_disabled_gate_emits_nothing():
    telemetry.enable(False)
    try:
        with telemetry.span("gated"):
            pass
        telemetry.emit_span("gated_manual", 1.0, 1.0)
        assert telemetry.spans() == []
    finally:
        telemetry.enable(True)


# -- merged two-half trace ----------------------------------------------------
def test_trace_json_merges_native_and_python_on_one_clock(tmp_path):
    path = _libsvm_file(tmp_path, rows=3000)
    from dmlc_core_tpu.data import RowBlockIter
    it = RowBlockIter.create(path, nthread=2)
    assert sum(b.size for b in it) == 3000
    it.close()
    doc = json.loads(telemetry.trace_json())
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    cats = {e["cat"] for e in evs}
    assert cats == {"native", "python"}
    names = {e["name"] for e in evs}
    assert {"parse.fill", "parse.slice", "rowblock.next"} <= names
    # one clock: every merged span lands within a sane wall-clock window
    now_us = time.time() * 1e6
    for e in evs:
        assert abs(e["ts"] - now_us) < 300e6, (e["name"], e["ts"])
        assert e["dur"] >= 0
    # metadata record present (Perfetto lane naming)
    assert any(e.get("ph") == "M" and e["name"] == "process_name"
               for e in doc["traceEvents"])
    # native worker threads get their own tid namespace
    nat_tids = {e["tid"] for e in evs if e["cat"] == "native"}
    py_tids = {e["tid"] for e in evs if e["cat"] == "python"}
    assert not (nat_tids & py_tids)


def test_anchor_pair_in_every_surface(tmp_path):
    snap = telemetry.snapshot()
    assert set(snap["anchor"]) == {"wall_us", "perf_us"}
    ts = telemetry.trace_snapshot()
    assert set(ts["anchor"]) == {"wall_us", "perf_us"}
    # native surfaces carry the (wall, steady) pair
    nat = native_telemetry_snapshot()
    assert set(nat["anchor"]) == {"wall_us", "steady_us"}
    ntr = native_trace_snapshot()
    assert set(ntr["anchor"]) == {"wall_us", "steady_us"}
    # the pairs agree on the wall clock (sampled within the same test)
    assert abs(nat["anchor"]["wall_us"] - snap["anchor"]["wall_us"]) < 60e6


# -- flight recorder ----------------------------------------------------------
def test_flight_dump_both_halves(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_TRACE_DUMP", str(tmp_path / "dumps"))
    with telemetry.span("doomed", shard=5):
        pass
    telemetry.emit_event("bad-thing", shard=5)
    path = telemetry.flight_dump("test-reason", rank=3)
    assert path is not None and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "test-reason" and doc["rank"] == 3
    assert set(doc["anchor"]) == {"wall_us", "perf_us"}
    assert any(s["name"] == "doomed" for s in doc["trace"]["spans"])
    assert any(e["event"] == "bad-thing"
               for e in doc["metrics"]["events"])
    # the native half writes its own dump file
    assert native_flight_dump("native-test-reason")
    nat = [f for f in os.listdir(tmp_path / "dumps")
           if f.startswith("flight_native_")]
    assert len(nat) == 1
    ndoc = json.load(open(tmp_path / "dumps" / nat[0]))
    assert ndoc["reason"] == "native-test-reason"
    assert "trace" in ndoc and "metrics" in ndoc


def test_flight_dump_noop_without_env(monkeypatch):
    monkeypatch.delenv("DMLC_TRACE_DUMP", raising=False)
    assert telemetry.flight_dump("nope") is None
    assert native_flight_dump("nope") is False


# -- stall attribution --------------------------------------------------------
def test_stall_verdict_synthetic_matrix():
    """Deterministic flips across all four verdicts from synthetic stage
    sums (hand-built snapshot docs — registering native-reserved metric
    names in the Python registry would shadow the native values in every
    later merged snapshot). The e2e tests below drive the two injectable
    verdicts for real."""
    def scenario(fill, parse, wait, transfer):
        hists = [
            {"name": name, "labels": {}, "count": 1, "sum": s,
             "buckets": [0] * (telemetry.HIST_BUCKETS + 1)}
            for name, s in (("parse_stage_fill_us", fill),
                            ("parse_stage_parse_us", parse),
                            ("parse_stage_reassemble_wait_us", wait),
                            ("device_transfer_us", transfer)) if s]
        return telemetry.stall_attribution(
            {"counters": [], "gauges": [], "histograms": hists})

    assert scenario(0, 0, 0, 0)["verdict"] == "unknown"
    assert scenario(9000, 1000, 5000, 0)["verdict"] == "fill_bound"
    assert scenario(1000, 9000, 5000, 0)["verdict"] == "parse_bound"
    assert scenario(5000, 5000, 100, 0)["verdict"] == "consumer_bound"
    att = scenario(2000, 3000, 5000, 9000)
    assert att["verdict"] == "transfer_bound"
    assert att["occupancy"]["transfer"] == pytest.approx(9000 / 14000)
    # the verdict gauges ride the snapshot itself: a real observation
    # into the (Python-side) transfer histogram flips the gauge
    telemetry.histogram("device_transfer_us").observe(9000)
    snap = telemetry.snapshot(native=False)
    codes = {g["name"]: g["value"] for g in snap["gauges"]
             if g["name"] == "stall_verdict_code"}
    assert codes["stall_verdict_code"] == \
        telemetry.VERDICT_CODES["transfer_bound"]


class _SlowOriginHandler(BaseHTTPRequestHandler):
    """Serves one body, throttled per 64 KB piece — a slow mock origin."""
    protocol_version = "HTTP/1.1"
    body: bytes = b""
    piece_delay_s = 0.03

    def log_message(self, *a):
        pass

    def do_HEAD(self):
        self.send_response(200)
        self.send_header("Content-Length", str(len(self.body)))
        self.end_headers()

    def do_GET(self):
        self.send_response(200)
        self.send_header("Content-Length", str(len(self.body)))
        self.end_headers()
        for off in range(0, len(self.body), 65536):
            self.wfile.write(self.body[off:off + 65536])
            self.wfile.flush()
            time.sleep(self.piece_delay_s)


def test_stall_verdict_fill_bound_under_slow_origin(tmp_path, monkeypatch):
    """An injected origin stall (every 64 KB piece throttled) must flip
    the verdict to fill_bound: the source read dominates while the parse
    workers starve."""
    # sequential lane: the ranged readahead exists to HIDE origin latency
    monkeypatch.setenv("DMLC_IO_RANGE", "0")
    path = _libsvm_file(tmp_path, rows=2000, name="slow.libsvm")
    handler = type("H", (_SlowOriginHandler,),
                   {"body": open(path, "rb").read()})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        telemetry.reset()
        with NativeParser(
                f"http://127.0.0.1:{srv.server_address[1]}/slow.libsvm",
                nthread=2) as p:
            assert sum(b.num_rows for b in p) == 2000
        att = telemetry.stall_attribution()
        assert att["verdict"] == "fill_bound", att
        assert att["stage_us"]["fill"] > att["stage_us"]["parse"]
    finally:
        srv.shutdown()
        srv.server_close()


def test_stall_verdict_consumer_bound_under_slow_consumer(tmp_path,
                                                          monkeypatch):
    """An injected consumer stall (sleep per pulled block over many small
    chunks) must flip the verdict to consumer_bound: the pipeline runs
    ahead and the reassemble wait stays a sliver of its busy time.

    The one structural wait — the consumer always parks once while chunk 1
    fills and parses — is amortized over ~64 chunks, but a loaded host
    can still stretch that first chunk past the 5% occupancy threshold,
    so the measurement retries (the PR 5 overhead-guard recipe): the
    regression this pins (a slow consumer NOT reading as consumer_bound)
    fails every attempt."""
    monkeypatch.setenv("DCT_CHUNK_SIZE_KB", "64")  # many chunks to hide
    path = _libsvm_file(tmp_path, rows=40000, name="slowc.libsvm")
    with NativeParser(path, nthread=2) as p:  # warm: cache + native lib
        sum(b.num_rows for b in p)
    att = None
    for _ in range(4):
        telemetry.reset()
        with NativeParser(path, nthread=2) as p:
            total = 0
            for b in p:
                total += b.num_rows
                time.sleep(0.005)  # the consumer is the slow stage
        assert total == 40000
        att = telemetry.stall_attribution()
        if att["verdict"] == "consumer_bound":
            break
    assert att["verdict"] == "consumer_bound", att


# -- scrape endpoints, tracker only ------------------------------------------
def test_healthz_and_404(tmp_path):
    tracker = RabitTracker("127.0.0.1", 2)
    tracker.start()
    try:
        base = f"http://127.0.0.1:{tracker.port}"
        doc = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=10).read())
        assert doc["status"] == "ok"
        assert doc["num_workers"] == 2 and doc["alive_ranks"] == 0
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert e.value.code == 404
        assert b"/healthz" in e.value.read()
        # /metrics and /trace serve the tracker-only view with no workers
        scrape = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert "tracker_num_workers 2" in scrape
        trace = json.loads(urllib.request.urlopen(
            base + "/trace", timeout=10).read())
        assert isinstance(trace["traceEvents"], list)
    finally:
        tracker.stop()


# -- the e2e acceptance: 2 real worker processes, scraped live ---------------
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? (?P<value>\S+)$")


def _parse_exposition(text):
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        samples[(m.group("name"), m.group("labels") or "")] = \
            float(m.group("value"))
    return samples


def test_two_worker_job_trace_and_metric_sums(tmp_path):
    """The acceptance pin: a REAL 2-process job scraped live — /trace
    holds both ranks' fetch→parse→batch spans as separate lanes on one
    merged wall-clock timeline with sane per-lane ordering, and every
    /metrics job: counter equals the sum of its per-rank series."""
    data = _libsvm_file(tmp_path, rows=4000, name="job.libsvm")
    tracker = RabitTracker("127.0.0.1", 2, heartbeat_ms=100)
    tracker.start()

    def spawn(task):
        env = dict(os.environ)
        env.update({str(k): str(v)
                    for k, v in tracker.worker_envs().items()})
        env.update({"DMLC_TASK_ID": str(task),
                    "DMLC_TRACKER_CLIENT_TIMEOUT": "60"})
        return subprocess.Popen(
            [sys.executable, WORKER, REPO, str(tmp_path), data],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)

    workers = [spawn(0), spawn(1)]
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if all(os.path.exists(tmp_path / f"parsed_{t}")
                   for t in (0, 1)):
                break
            for w in workers:
                assert w.poll() is None, w.stderr.read().decode()
            time.sleep(0.05)
        else:
            pytest.fail("workers never finished parsing")

        base = f"http://127.0.0.1:{tracker.port}"
        trace = json.loads(urllib.request.urlopen(
            base + "/trace", timeout=30).read())
        scrape = urllib.request.urlopen(
            base + "/metrics", timeout=30).read().decode()
    finally:
        open(tmp_path / "release", "w").close()
        for w in workers:
            try:
                w.wait(timeout=60)
            except subprocess.TimeoutExpired:
                w.kill()
    assert all(w.returncode == 0 for w in workers), \
        [w.stderr.read().decode() for w in workers]
    tracker.join(timeout=30)

    # --- /trace: both ranks' batch-path spans, one merged timeline ---
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_rank = {r: [e for e in evs if e["pid"] == r] for r in (0, 1)}
    now_us = time.time() * 1e6
    for rank, revs in by_rank.items():
        names = {e["name"] for e in revs}
        assert {"parse.fill", "parse.slice", "rowblock.next"} <= names, \
            (rank, names)
        # one merged wall clock: every span within a sane window
        for e in revs:
            assert abs(e["ts"] - now_us) < 600e6, (rank, e)
        # per-lane ordering: within each (pid, tid) lane, consecutive
        # spans (sorted by start) either nest inside their predecessor or
        # begin after it ends — a lane can never jumble (the Perfetto
        # render contract); 1 ms slack absorbs µs rounding
        lanes = {}
        for e in revs:
            lanes.setdefault(e["tid"], []).append(e)
        for lane_evs in lanes.values():
            lane_evs.sort(key=lambda e: (e["ts"], -e["dur"]))
            for a, b in zip(lane_evs, lane_evs[1:]):
                nested = b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1000
                disjoint = b["ts"] >= a["ts"] + a["dur"] - 1000
                assert nested or disjoint, (rank, a, b)
    # process_name metadata for both rank lanes (Perfetto labeling)
    meta = {e["pid"]: e["args"]["name"]
            for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "rank 0" in meta[0] and "rank 1" in meta[1]

    # --- /metrics: job sums equal per-rank sums, counter-for-counter ---
    samples = _parse_exposition(scrape)
    job_counters = [(n, lbl) for (n, lbl) in samples
                    if n.startswith("job:") and "_bucket" not in n
                    and not n.endswith("_sum") and not n.endswith("_count")]
    assert job_counters, "no job-level sums in the scrape"
    checked = 0
    for name, lbl in job_counters:
        base_name = name[len("job:"):]
        rank_total = 0.0
        rank_series = 0
        for (n2, lbl2), v in samples.items():
            if n2 != base_name or "rank=" not in lbl2:
                continue
            rest = ",".join(p for p in lbl2.split(",")
                            if not p.startswith("rank="))
            if rest == lbl:
                rank_total += v
                rank_series += 1
        assert rank_series == 2, (name, lbl)
        assert samples[(name, lbl)] == pytest.approx(rank_total), name
        checked += 1
    assert checked >= 5  # parse counters, rowblock counters, events, ...
    # both ranks really parsed: the job-wide block counter covers 2x4000
    assert samples[("job:parse_blocks_delivered_total", "")] >= 2
    assert samples[("job:rowblock_batches_total", "")] >= 2


def test_sigkill_rank_leaves_flight_recorder_dump(tmp_path, monkeypatch):
    """A SIGKILL'd elastic rank cannot dump its own state — the TRACKER's
    write-off dump is the postmortem: it lands in DMLC_TRACE_DUMP and its
    event ring names the exact shard the dead rank held."""
    dump_dir = tmp_path / "dumps"
    monkeypatch.setenv("DMLC_TRACE_DUMP", str(dump_dir))
    import numpy as np
    rng = np.random.default_rng(5)
    data = str(tmp_path / "chaos.libsvm")
    with open(data, "w") as f:
        for i in range(640):
            feats = " ".join(f"{j}:{rng.uniform():.5f}" for j in range(1, 4))
            f.write(f"{i % 2} 0:{float(i):.1f} {feats}\n")
    tracker = RabitTracker("127.0.0.1", 2, heartbeat_ms=100,
                           dead_after_ms=800, recover_grace_ms=400,
                           num_shards=8)
    tracker.start()

    def spawn(task, extra):
        env = dict(os.environ)
        env.update({str(k): str(v)
                    for k, v in tracker.worker_envs().items()})
        env.update({"DMLC_TASK_ID": str(task),
                    "DMLC_TRACKER_CLIENT_TIMEOUT": "60"})
        env.update(extra)
        return subprocess.Popen(
            [sys.executable, ELASTIC_WORKER, REPO, str(tmp_path), data],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)

    victim = spawn(0, {"ELASTIC_VICTIM": "1"})
    survivor = spawn(1, {"ELASTIC_WAIT_ARMED": "1"})
    victim.wait(timeout=60)
    assert victim.returncode == -9
    survivor.wait(timeout=60)
    assert survivor.returncode == 0, survivor.stderr.read().decode()
    tracker.join(timeout=30)  # completes: elastic write-off, not abort

    held_at_death = int((tmp_path / "victim_armed").read_text())
    dumps = [json.load(open(dump_dir / f)) for f in os.listdir(dump_dir)
             if f.startswith(f"flight_{os.getpid()}_")]
    lost = [d for d in dumps if d["reason"].startswith("rank-lost")]
    assert lost, [d["reason"] for d in dumps]
    doc = lost[0]
    events = doc["metrics"]["events"]
    reclaimed = [e for e in events if e["event"] == "lease-reclaim"]
    assert any(e["shard"] == held_at_death for e in reclaimed), \
        (held_at_death, reclaimed)
    # the dump carries the anchor pair and the span/event rings
    assert set(doc["anchor"]) == {"wall_us", "perf_us"}
    assert "spans" in doc["trace"]


# -- per-request tracing primitives (doc/observability.md) -------------------
def test_new_span_id_and_explicit_parent_handoff():
    """The cross-thread handoff contract: `new_span_id` reserves an id
    without emitting, children on OTHER threads parent under it
    explicitly, the root is emitted later under `span_id=`, and
    `parent=0` marks an explicit root (the thread-local chain never
    crosses threads)."""
    rid = telemetry.new_span_id()

    def worker():
        telemetry.emit_span("child", 1000.0, 50.0, parent=rid)

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    telemetry.emit_span("root", 900.0, 200.0, parent=0, span_id=rid,
                        request_id="r-1")
    got = {s["name"]: s for s in telemetry.spans()}
    assert got["child"]["parent"] == rid
    assert got["root"]["id"] == rid and got["root"]["parent"] == 0
    assert got["root"]["args"]["request_id"] == "r-1"
    # the reserved id came off the one process allocator: no collision
    assert telemetry.new_span_id() > rid


def test_request_id_sanitize_or_mint():
    from dmlc_core_tpu.tracker import minihttp
    assert minihttp.request_id("abc-DEF_1.2") == "abc-DEF_1.2"
    minted = minihttp.request_id(None)
    assert re.fullmatch(r"[0-9a-f]{16}", minted)
    # injection/oversize/garbage all mint instead of echoing
    for bad in ("x" * 65, "a b", "a\r\nSet-Cookie: x", ""):
        out = minihttp.request_id(bad)
        assert re.fullmatch(r"[0-9a-f]{16}", out), (bad, out)


# -- step timelines: straggler attribution on a REAL 2-process job -----------
def test_step_timeline_straggler_e2e(tmp_path):
    """Acceptance pin (doc/observability.md "Step timelines"): a real
    2-process job whose slowed rank steps ~8x slower yields the
    `straggler_bound` verdict with the correct rank as the /trace
    `job_meta` record, the slow rank's visibly-longer `mesh.step` spans
    on its lane, and the `tracker_straggler_rank` gauge on /metrics."""
    tracker = RabitTracker("127.0.0.1", 2, heartbeat_ms=100)
    tracker.start()
    step_worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "step_worker.py")

    def spawn(task, sleep_ms):
        env = dict(os.environ)
        env.update({str(k): str(v)
                    for k, v in tracker.worker_envs().items()})
        env.update({"DMLC_TASK_ID": str(task),
                    "DMLC_TRACKER_CLIENT_TIMEOUT": "60",
                    "DMLC_TEST_STEP_SLEEP_MS": str(sleep_ms),
                    "DMLC_TEST_STEPS": "6"})
        return subprocess.Popen(
            [sys.executable, step_worker, REPO, str(tmp_path)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)

    workers = [spawn(0, 10), spawn(1, 80)]  # task 1 is the straggler
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if all(os.path.exists(tmp_path / f"stepped_{t}")
                   for t in (0, 1)):
                break
            for w in workers:
                assert w.poll() is None, w.stderr.read().decode()
            time.sleep(0.05)
        else:
            pytest.fail("workers never finished stepping")
        slow_rank = int((tmp_path / "stepped_1").read_text().split()[0])

        base = f"http://127.0.0.1:{tracker.port}"
        trace = json.loads(urllib.request.urlopen(
            base + "/trace", timeout=30).read())
        scrape = urllib.request.urlopen(
            base + "/metrics", timeout=30).read().decode()
    finally:
        open(tmp_path / "release", "w").close()
        for w in workers:
            try:
                w.wait(timeout=60)
            except subprocess.TimeoutExpired:
                w.kill()
    assert all(w.returncode == 0 for w in workers), \
        [w.stderr.read().decode() for w in workers]
    tracker.join(timeout=30)

    # the merged timeline: mesh.step spans per rank lane, slow lane slower
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"
           and e["name"] == "mesh.step"]
    by_rank = {}
    for e in evs:
        by_rank.setdefault(e["pid"], []).append(e)
    assert set(by_rank) == {0, 1}, sorted(by_rank)
    fast_rank = 1 - slow_rank
    med = {r: sorted(x["dur"] for x in v)[len(v) // 2]
           for r, v in by_rank.items()}
    assert med[slow_rank] > 2.0 * med[fast_rank], med
    assert {e["args"]["step"] for e in by_rank[slow_rank]} == set(range(6))

    # the verdict rides the trace as job_meta, naming the slow rank
    meta = [e for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "job_meta"]
    assert meta, "no job_meta record on /trace"
    verdict = meta[0]["args"]
    assert verdict["verdict"] == "straggler_bound", verdict
    assert verdict["rank"] == slow_rank and verdict["ratio"] > 2.0

    # ... and the gauge on /metrics
    samples = _parse_exposition(scrape)
    assert samples[("tracker_straggler_rank", "")] == slow_rank


# -- the pulse (doc/observability.md "The hold and the pulse") ----------------
def _pulse_threads():
    return [t for t in threading.enumerate() if t.name == "dmlc-pulse"]


def _hist_count(name):
    return sum(h["count"]
               for h in telemetry.snapshot(native=True)["histograms"]
               if h["name"] == name)


def test_pulse_ticks_at_once_and_each_tick_is_an_opened_span(
        fake_annotation):
    native_telemetry_snapshot()   # the native library is loaded
    telemetry.pulse_start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not (
            _hist_count("pulse_py_late_us")
            and _hist_count("pulse_native_late_us")):
        time.sleep(0.001)
    # both tick at once when they start, before any nap has ended
    assert _hist_count("pulse_py_late_us") >= 1
    assert _hist_count("pulse_native_late_us") >= 1
    time.sleep(0.05)
    telemetry.pulse_stop()
    ticks = [s for s in telemetry.spans() if s["name"] == "pulse"]
    assert len(ticks) == _hist_count("pulse_py_late_us") >= 2
    assert all(s["args"]["late_us"] >= 0 for s in ticks)
    # an opened span: the profiler's trace has a dmlc.pulse line
    assert ("enter", "dmlc.pulse") in fake_annotation
    assert sum(e == ("enter", "dmlc.pulse") for e in fake_annotation) == \
        sum(e == ("exit", "dmlc.pulse") for e in fake_annotation) == \
        len(ticks)
    # the lateness of every tick is kept for the record of a long hold
    py, native = telemetry._pulse_late_us(0.0, time.perf_counter() * 1e6)
    assert py >= max(s["args"]["late_us"] for s in ticks) - 1
    assert native is None   # stopped: no native pulse to ask


def test_pulse_is_one_pair_a_process_and_stops_with_telemetry():
    native_telemetry_snapshot()
    for _ in range(3):
        telemetry.pulse_start()
    assert len(_pulse_threads()) == 1
    n0 = _hist_count("pulse_native_late_us")
    time.sleep(0.3)
    rose = _hist_count("pulse_native_late_us") - n0
    # one native thread ticks 15 times in 0.3 s (late, never early); two
    # would tick 30
    assert 3 <= rose <= 17, rose
    telemetry.enable(False)
    assert _pulse_threads() == []
    n1 = _hist_count("pulse_native_late_us")
    p1 = _hist_count("pulse_py_late_us")
    time.sleep(0.05)
    assert _hist_count("pulse_native_late_us") == n1
    assert _hist_count("pulse_py_late_us") == p1
    # while telemetry is disabled nothing starts
    telemetry.pulse_start()
    assert _pulse_threads() == []
    time.sleep(0.03)
    assert _hist_count("pulse_native_late_us") == n1
