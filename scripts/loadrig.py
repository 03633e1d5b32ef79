#!/usr/bin/env python3
"""Out-of-process measurement rig: origins, clients, and an open-loop
load generator (doc/benchmarking.md).

Every remote-lane number the repo published before this rig was bounded
by its own harness: the mock origins ran *inside* the client process,
GIL-sharing the same cores that fetch and parse, so ``vs_local`` capped
at whatever a Python thread could serve between parse slices.  This
script moves the measurement plane out of the client's process:

``origin``
    Launch any mock backend (s3 / azure / webhdfs / http,
    tests/mock_origin.py) as its own process tree: the listener socket
    binds once, then ``--workers`` pre-forked processes accept from it
    (kernel load-balanced), each serving a deterministically
    pre-generated corpus with latency/bandwidth shaping applied
    server-side.  Prints ``RIG_READY port=... pids=...`` when up.

``parse-client`` / ``fetch-client``
    The client half, one process per measurement: set the backend env,
    parse (or raw-read) a URI, print one JSON line with the timing and
    the process's own CPU/telemetry — a fresh native singleton per
    endpoint and no shared interpreter with the origin.

``loadgen``
    Open-loop HTTP load at a scheduled arrival rate (see
    :func:`open_loop`).

Python API: :func:`spawn_origin`, :func:`open_loop`,
:func:`closed_loop` — the serving lane plugs its request function into
the same generator the rig self-tests pin.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# origin: pre-forked mock backends over one shared listener
# ---------------------------------------------------------------------------
def _child_dies_with_parent():
    """Best-effort PR_SET_PDEATHSIG so orphaned origin workers never
    outlive a crashed launcher (Linux only; guarded)."""
    try:
        import ctypes
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG
    except Exception:  # noqa: BLE001 - best-effort containment
        pass


def run_origin(args) -> int:
    """The ``origin`` subcommand: bind, pre-fork, serve until killed."""
    from tests import mock_origin

    config = mock_origin.OriginConfig(
        latency_ms=args.latency_ms, latency_block=args.latency_block,
        first_byte_ms=args.first_byte_ms,
        stall_every=args.stall_every, stall_seconds=args.stall_seconds,
        reset_every=args.reset_every, get_500_every=args.get_500_every,
        get_truncate_every=args.get_truncate_every,
        slow_every=args.slow_every, slow_ms=args.slow_ms,
        ignore_range=args.ignore_range,
        bad_content_range_every=args.bad_content_range_every,
        backlog=args.backlog, workers=args.workers)
    corpus = mock_origin.build_corpus(args.corpus)

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", args.port))
    listener.listen(config.backlog)
    port = listener.getsockname()[1]

    deadline = time.monotonic() + args.ttl
    pids = []
    for _ in range(max(args.workers, 1)):
        pid = os.fork()
        if pid == 0:
            _child_dies_with_parent()
            state, handler_cls = mock_origin.state_and_handler(
                args.backend)
            if hasattr(state, "port"):
                state.port = port
            mock_origin.load_corpus(args.backend, state, corpus)
            server = mock_origin.make_server(handler_cls, state, config,
                                             sock=listener)
            # the TTL backstop also applies inside each worker: a
            # launcher SIGKILLed before cleanup must not leak servers
            threading.Thread(target=_ttl_exit,
                             args=(deadline,), daemon=True).start()
            try:
                server.serve_forever()
            except KeyboardInterrupt:
                pass
            os._exit(0)
        pids.append(pid)

    def _term(signum, frame):
        for p in pids:
            try:
                os.kill(p, signal.SIGTERM)
            except OSError:
                pass
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    print(f"RIG_READY backend={args.backend} port={port} "
          f"pids={','.join(str(p) for p in pids)}", flush=True)
    try:
        while pids and time.monotonic() < deadline:
            if args.parent_pid and os.getppid() != args.parent_pid:
                break  # the launcher's caller is gone: so is its run
            try:
                done, _ = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if done:
                pids.remove(done)
            else:
                time.sleep(0.2)
    finally:
        _term(None, None)
    return 0


def _ttl_exit(deadline: float):
    while time.monotonic() < deadline:
        time.sleep(1.0)
    os._exit(0)


class OriginProcess:
    """Handle to a spawned out-of-process origin (see
    :func:`spawn_origin`): ``.port``, worker ``.pids`` (for CPU
    attribution), ``.env()`` for clients, ``.uri(key)``, ``.close()``."""

    def __init__(self, backend: str, proc: subprocess.Popen, port: int,
                 pids):
        self.backend = backend
        self.proc = proc
        self.port = port
        self.pids = list(pids)

    def env(self) -> dict:
        """Env vars a client process needs to reach this origin."""
        from tests import mock_origin
        return mock_origin.client_env(self.backend, self.port)

    def uri(self, key: str) -> str:
        """Client URI for a corpus key."""
        from tests import mock_origin
        return mock_origin.uri_for(self.backend, self.port, key)

    def cpu_seconds(self) -> float:
        """Cumulative utime+stime of the launcher + every worker still
        alive (0.0 where /proc is unavailable)."""
        total = 0
        tick = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
        for pid in [self.proc.pid] + self.pids:
            try:
                with open(f"/proc/{pid}/stat") as f:
                    rest = f.read().rsplit(")", 1)[1].split()
                total += int(rest[11]) + int(rest[12])
            except (OSError, IndexError, ValueError):
                pass
        return total / tick

    def close(self) -> None:
        """Terminate the origin process tree."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def spawn_origin(backend: str, corpus_specs, config=None,
                 timeout_s: float = 30.0,
                 ttl_s: "float | None" = None,
                 port: int = 0) -> OriginProcess:
    """Launch ``loadrig.py origin`` as a subprocess and wait for
    ``RIG_READY``. The launcher stops when the caller is gone (it watches
    its parent's pid; its workers die with it by PR_SET_PDEATHSIG), and
    with ``ttl_s`` after that many seconds whatever happens: an origin
    never outlives its run. ``port`` 0 is an ephemeral one; a process
    whose native core has already read an origin's address (it does once)
    asks for that port again.

    ``corpus_specs`` is a list of ``key=@path`` / ``key=size:seed``
    strings (tests/mock_origin.build_corpus); ``config`` an
    ``OriginConfig`` whose shaping knobs become CLI flags, so the
    in-process and out-of-process modes share one configuration
    surface."""
    from tests import mock_origin
    config = config or mock_origin.OriginConfig()
    cmd = [sys.executable, os.path.abspath(__file__), "origin",
           "--backend", backend]
    for spec in corpus_specs:
        cmd.extend(["--corpus", spec])
    cmd.extend(config.cli_args())
    if ttl_s is not None:
        cmd.extend(["--ttl", str(ttl_s)])
    if port:
        cmd.extend(["--port", str(port)])
    cmd.extend(["--parent-pid", str(os.getpid())])
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    deadline = time.monotonic() + timeout_s
    line = ""
    # select-gate every read: a wedged origin that neither prints nor
    # exits must surface as the timeout error, not a readline hang
    import select
    while time.monotonic() < deadline:
        ready, _, _ = select.select(
            [proc.stdout], [], [],
            min(0.5, max(deadline - time.monotonic(), 0.01)))
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"origin died before RIG_READY "
                    f"(rc={proc.returncode})")
            continue
        line = proc.stdout.readline()
        if line.startswith("RIG_READY"):
            break
        if proc.poll() is not None and not line:
            raise RuntimeError(
                f"origin died before RIG_READY (rc={proc.returncode})")
    if not line.startswith("RIG_READY"):
        proc.kill()
        raise RuntimeError("origin did not become ready in time")
    fields = dict(kv.split("=", 1) for kv in line.split()[1:])
    return OriginProcess(backend, proc, int(fields["port"]),
                         [int(p) for p in fields["pids"].split(",") if p])


# ---------------------------------------------------------------------------
# clients: one process per measurement
# ---------------------------------------------------------------------------
def run_parse_client(args) -> int:
    """The ``parse-client`` subcommand: parse a URI, print one JSON line
    with rows/s (best of --reps) plus this process's CPU and the range
    scheduler's telemetry — everything the parent needs to attribute the
    number without sharing a process with it."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.io.native import NativeParser

    best = None
    rows = 0
    cpu0 = os.times()
    wall0 = time.time()
    for _ in range(max(args.reps, 1)):
        t0 = time.time()
        got = 0
        with NativeParser(args.uri, nthread=args.nthread,
                          fmt=args.fmt) as p:
            for blk in p:
                got += blk.num_rows
        dt = time.time() - t0
        rows = got
        best = dt if best is None else min(best, dt)
    cpu1 = os.times()
    total_wall = time.time() - wall0
    snap = telemetry.snapshot()
    counters = {}
    for c in snap["counters"]:
        counters[c["name"]] = counters.get(c["name"], 0) + c["value"]
    gauges = {g["name"]: g["value"] for g in snap["gauges"]}
    hists = {h["name"]: {"count": h["count"], "sum": h["sum"]}
             for h in snap["histograms"]
             if h["name"].startswith("io_range")}
    print(json.dumps({
        "rows": rows, "best_dt": best, "total_dt": round(total_wall, 4),
        "rows_per_sec": round(rows / best, 1) if best else 0.0,
        # CPU around the parse loop only (not interpreter startup):
        # what the attribution verdict divides by the wall time
        "cpu_s": round((cpu1.user - cpu0.user)
                       + (cpu1.system - cpu0.system)
                       + (cpu1.children_user - cpu0.children_user)
                       + (cpu1.children_system - cpu0.children_system),
                       3),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("io_", "parse_"))},
        "gauges": {k: v for k, v in gauges.items()
                   if k.startswith("io_range")},
        "range_hists": hists,
    }))
    return 0


def run_fetch_client(args) -> int:
    """The ``fetch-client`` subcommand: raw-read a URI, print sha256 +
    length — the byte-identity probe against an out-of-process origin."""
    from dmlc_core_tpu.io.native import NativeStream
    t0 = time.time()
    with NativeStream(args.uri, "r") as s:
        data = s.read_all()
    print(json.dumps({"sha256": hashlib.sha256(data).hexdigest(),
                      "bytes": len(data),
                      "dt": round(time.time() - t0, 4)}))
    return 0


# ---------------------------------------------------------------------------
# open-loop load generator (Treadmill-style scheduled arrivals;
# HdrHistogram-style intended-time capture)
# ---------------------------------------------------------------------------
def _percentiles(h) -> dict:
    return {"p50": h.quantile(0.50), "p99": h.quantile(0.99),
            "p999": h.quantile(0.999),
            "mean": round(h.sum / h.count, 1) if h.count else 0.0,
            "count": h.count}


def open_loop(request_fn, qps: float, duration_s: float, *,
              max_inflight: int = 16, shed_after_ms: float = 0.0,
              phases=None) -> dict:
    """Drive ``request_fn`` at a *scheduled* arrival rate and capture
    latency against the INTENDED start time of each request.

    This is the coordinated-omission-safe discipline (Tene, "How NOT to
    Measure Latency"; Treadmill, ISCA '16): arrival ``i`` is due at
    ``t0 + i/qps`` whether or not the system is keeping up.  When every
    worker is stuck behind a stalled origin, the arrivals that queue up
    behind it are charged their full wait — ``intended_us`` — while the
    conventional send-to-response clock — ``service_us`` — hides it.
    Both histograms are returned so the divergence itself is a metric.

    ``phases`` ([(qps, seconds), ...]) overrides ``qps``/``duration_s``
    for ramp profiles.  ``max_inflight`` bounds concurrency (worker
    threads); with ``shed_after_ms`` > 0 arrivals already later than
    the budget are counted shed instead of issued — the overload
    policy a serving lane wants instead of an unbounded queue.
    ``request_fn`` returns truthy on success; exceptions count as
    errors.  Returns achieved/offered QPS, counts, and
    p50/p99/p999/mean for both clocks (us).
    """
    from dmlc_core_tpu import telemetry

    phases = list(phases) if phases else [(float(qps), float(duration_s))]
    offsets = []
    base = 0.0
    for ph_qps, ph_dur in phases:
        n = max(int(ph_qps * ph_dur), 0)
        offsets.extend(base + i / ph_qps for i in range(n))
        base += ph_dur
    intended = telemetry.Histogram("rig_intended_us", {})
    service = telemetry.Histogram("rig_service_us", {})
    lock = threading.Lock()
    state = {"next": 0, "done": 0, "errors": 0, "shed": 0,
             "max_late_ms": 0.0}
    t0 = time.monotonic() + 0.05  # everyone sees the same epoch

    req_c = telemetry.counter("rig_requests_total", {"mode": "open"})
    err_c = telemetry.counter("rig_errors_total", {"mode": "open"})
    shed_c = telemetry.counter("rig_shed_total", {"mode": "open"})
    t_int = telemetry.histogram("rig_intended_us")
    t_srv = telemetry.histogram("rig_service_us")

    def worker():
        while True:
            with lock:
                i = state["next"]
                if i >= len(offsets):
                    return
                state["next"] = i + 1
            due = t0 + offsets[i]
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
                now = time.monotonic()
            late_ms = (now - due) * 1e3
            with lock:
                state["max_late_ms"] = max(state["max_late_ms"], late_ms)
            if shed_after_ms and late_ms > shed_after_ms:
                with lock:
                    state["shed"] += 1
                shed_c.inc()
                continue
            t_issue = time.monotonic()
            try:
                ok = request_fn()
            except Exception:  # noqa: BLE001 - an error IS the datum
                ok = False
            t_done = time.monotonic()
            intended.observe((t_done - due) * 1e6)
            service.observe((t_done - t_issue) * 1e6)
            t_int.observe((t_done - due) * 1e6)
            t_srv.observe((t_done - t_issue) * 1e6)
            req_c.inc()
            with lock:
                state["done"] += 1
                if not ok:
                    state["errors"] += 1

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, max_inflight))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if state["errors"]:
        err_c.inc(state["errors"])
    span = max(time.monotonic() - t0, 1e-9)
    offered = len(offsets) / max(base, 1e-9)
    return {
        "mode": "open",
        "offered_qps": round(offered, 1),
        "achieved_qps": round(state["done"] / span, 1),
        "duration_s": round(span, 3),
        "arrivals": len(offsets),
        "completed": state["done"],
        "errors": state["errors"],
        "shed": state["shed"],
        "max_inflight": max_inflight,
        "max_lateness_ms": round(state["max_late_ms"], 1),
        "intended_us": _percentiles(intended),
        "service_us": _percentiles(service),
    }


def closed_loop(request_fn, workers: int, duration_s: float) -> dict:
    """The comparison mode open-loop exists to correct: ``workers``
    callers issue back-to-back requests, so the *measured* rate sinks to
    whatever the system serves and queueing delay is never observed —
    under saturation its latency numbers look healthy while throughput
    quietly caps.  Returned shape matches :func:`open_loop` (no
    intended clock: a closed loop has no schedule to be late against)."""
    from dmlc_core_tpu import telemetry
    service = telemetry.Histogram("rig_service_us", {})
    lock = threading.Lock()
    state = {"done": 0, "errors": 0}
    deadline = time.monotonic() + duration_s
    req_c = telemetry.counter("rig_requests_total", {"mode": "closed"})
    err_c = telemetry.counter("rig_errors_total", {"mode": "closed"})

    def worker():
        while time.monotonic() < deadline:
            t_issue = time.monotonic()
            try:
                ok = request_fn()
            except Exception:  # noqa: BLE001 - an error IS the datum
                ok = False
            service.observe((time.monotonic() - t_issue) * 1e6)
            req_c.inc()
            with lock:
                state["done"] += 1
                if not ok:
                    state["errors"] += 1
                    err_c.inc()

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, workers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    span = max(time.monotonic() - t0, 1e-9)
    return {
        "mode": "closed",
        "achieved_qps": round(state["done"] / span, 1),
        "duration_s": round(span, 3),
        "completed": state["done"],
        "errors": state["errors"],
        "workers": workers,
        "service_us": _percentiles(service),
    }


def http_request_fn(url: str, timeout_s: float = 10.0, *,
                    method: str = "GET", body: bytes | None = None,
                    headers: dict | None = None, payload_fn=None,
                    on_status=None):
    """A request function for :func:`open_loop`/:func:`closed_loop`:
    issue ``method`` against ``url`` over a per-thread persistent
    connection (reconnects on error), True on a fully-read 2xx.

    POST bodies come from ``body`` (fixed) or ``payload_fn`` (called
    per request for generated traffic — see :func:`score_payload_fn`);
    ``payload_fn`` wins when both are given. ``on_status(status)``, if
    provided, observes every completed response's status code (the
    serving overload tests count sheds vs scores with it; transport
    errors never reach it)."""
    import http.client
    import urllib.parse
    parsed = urllib.parse.urlsplit(url)
    path = parsed.path or "/"
    if parsed.query:
        path += "?" + parsed.query
    tls = threading.local()

    def request() -> bool:
        conn = getattr(tls, "conn", None)
        if conn is None:
            conn = http.client.HTTPConnection(parsed.hostname,
                                              parsed.port,
                                              timeout=timeout_s)
            tls.conn = conn
        try:
            payload = payload_fn() if payload_fn is not None else body
            conn.request(method, path, payload, headers or {})
            resp = conn.getresponse()
            resp.read()
            if on_status is not None:
                on_status(resp.status)
            return 200 <= resp.status < 300
        except Exception:
            try:
                conn.close()
            finally:
                tls.conn = None
            raise

    return request


def parse_corpus_spec(spec: str) -> dict:
    """``"libsvm:rows=4,features=64,nnz=8,seed=3"`` -> option dict.

    The payload-corpus grammar for generated score traffic:
    ``<fmt>[:k=v,...]`` with ``fmt`` libsvm|csv, ``rows`` per payload
    (``rows_max`` > ``rows`` makes sizes ragged across requests),
    ``features`` the id space, ``nnz`` per row, ``seed`` the corpus
    seed. Unknown keys are an error — specs travel through CLIs and a
    typo must not silently change the traffic."""
    fmt, _, tail = spec.partition(":")
    fmt = fmt.strip().lower()
    if fmt not in ("libsvm", "csv"):
        raise ValueError(f"corpus spec {spec!r}: fmt must be libsvm|csv")
    out = {"fmt": fmt, "rows": 4, "rows_max": 0, "features": 64,
           "nnz": 8, "seed": 0}
    for tok in tail.split(","):
        if not tok.strip():
            continue
        key, sep, val = tok.partition("=")
        key = key.strip()
        if not sep or key not in ("rows", "rows_max", "features",
                                  "nnz", "seed"):
            raise ValueError(f"corpus spec {spec!r}: bad token {tok!r}")
        out[key] = int(val)
    if out["rows"] <= 0 or out["features"] <= 0 or out["nnz"] <= 0:
        raise ValueError(f"corpus spec {spec!r}: rows/features/nnz "
                         "must be positive")
    return out


def score_payload_fn(spec: str):
    """Per-request payload generator from a corpus spec (see
    :func:`parse_corpus_spec`): returns ``(payload_fn, content_type)``
    for :func:`http_request_fn`.

    Deterministic and thread-safe: request *i* (a process-wide counter)
    always produces the same payload for the same spec, so a rerun
    offers byte-identical traffic. With ``rows_max`` set, payload sizes
    cycle raggedly between ``rows`` and ``rows_max`` — the traffic
    shape the serving bucket-padding census pin drives."""
    import random
    opts = parse_corpus_spec(spec)
    counter = [0]
    counter_lock = threading.Lock()
    ctype = ("application/x-libsvm" if opts["fmt"] == "libsvm"
             else "text/csv")

    def payload() -> bytes:
        with counter_lock:
            i = counter[0]
            counter[0] += 1
        rng = random.Random((opts["seed"] << 20) ^ i)
        rows = opts["rows"]
        if opts["rows_max"] > rows:
            rows += i % (opts["rows_max"] - rows + 1)
        lines = []
        for _ in range(rows):
            if opts["fmt"] == "libsvm":
                ids = rng.sample(range(opts["features"]),
                                 min(opts["nnz"], opts["features"]))
                feats = " ".join(f"{j}:{rng.uniform(-1, 1):.4f}"
                                 for j in sorted(ids))
                lines.append(f"{rng.randint(0, 1)} {feats}")
            else:
                lines.append(",".join(f"{rng.uniform(-1, 1):.4f}"
                                      for _ in range(opts["features"])))
        return ("\n".join(lines) + "\n").encode()

    return payload, ctype


def run_loadgen(args) -> int:
    """The ``loadgen`` subcommand: open- (default) or closed-loop HTTP
    load against --url; prints the result JSON. ``--score-corpus``
    switches to POST with per-request generated payloads."""
    if args.score_corpus:
        payload_fn, ctype = score_payload_fn(args.score_corpus)
        fn = http_request_fn(args.url, args.timeout_s, method="POST",
                             headers={"Content-Type": ctype},
                             payload_fn=payload_fn)
    elif args.body_file:
        with open(args.body_file, "rb") as f:
            body = f.read()
        fn = http_request_fn(
            args.url, args.timeout_s, method=args.method, body=body,
            headers={"Content-Type": args.content_type}
            if args.content_type else None)
    else:
        fn = http_request_fn(args.url, args.timeout_s,
                             method=args.method)
    if args.closed_loop:
        out = closed_loop(fn, args.workers, args.duration_s)
    else:
        out = open_loop(fn, args.qps, args.duration_s,
                        max_inflight=args.workers,
                        shed_after_ms=args.shed_after_ms)
    print(json.dumps(out))
    return 0


# ---------------------------------------------------------------------------
def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    o = sub.add_parser("origin", help="serve a mock backend out of "
                                      "process (pre-forked workers)")
    o.add_argument("--backend", required=True,
                   choices=("s3", "azure", "webhdfs", "http"))
    o.add_argument("--corpus", action="append", default=[],
                   help="key=@path or key=<size>:<seed>; repeatable")
    o.add_argument("--port", type=int, default=0)
    o.add_argument("--workers", type=int, default=2)
    o.add_argument("--backlog", type=int, default=128)
    o.add_argument("--latency-ms", type=int, default=0)
    o.add_argument("--latency-block", type=int, default=256 * 1024)
    o.add_argument("--first-byte-ms", type=int, default=None,
                   help="sleep before the response head (unset: "
                        "--latency-ms, which then also paces the body)")
    o.add_argument("--stall-every", type=int, default=0)
    o.add_argument("--stall-seconds", type=float, default=3.0)
    o.add_argument("--reset-every", type=int, default=0)
    o.add_argument("--get-500-every", type=int, default=0)
    o.add_argument("--get-truncate-every", type=int, default=0)
    o.add_argument("--slow-every", type=int, default=0)
    o.add_argument("--slow-ms", type=int, default=0)
    o.add_argument("--ignore-range", action="store_true")
    o.add_argument("--bad-content-range-every", type=int, default=0)
    o.add_argument("--parent-pid", type=int, default=0,
                   help="stop once this process is no longer the parent "
                        "(spawn_origin passes its own pid)")
    o.add_argument("--ttl", type=float, default=600.0,
                   help="self-destruct after this many seconds — an "
                        "orphaned rig must never outlive its run")
    o.set_defaults(fn=run_origin)

    pc = sub.add_parser("parse-client",
                        help="parse a URI in this fresh process; print "
                             "JSON timing + telemetry")
    pc.add_argument("--uri", required=True)
    pc.add_argument("--fmt", default="libsvm")
    pc.add_argument("--nthread", type=int, default=0)
    pc.add_argument("--reps", type=int, default=1)
    pc.set_defaults(fn=run_parse_client)

    fc = sub.add_parser("fetch-client",
                        help="raw-read a URI; print JSON sha256+bytes")
    fc.add_argument("--uri", required=True)
    fc.set_defaults(fn=run_fetch_client)

    lg = sub.add_parser("loadgen", help="open/closed-loop HTTP load")
    lg.add_argument("--url", required=True)
    lg.add_argument("--qps", type=float, default=100.0)
    lg.add_argument("--duration-s", type=float, default=5.0)
    lg.add_argument("--workers", type=int, default=16)
    lg.add_argument("--shed-after-ms", type=float, default=0.0)
    lg.add_argument("--timeout-s", type=float, default=10.0)
    lg.add_argument("--closed-loop", action="store_true")
    lg.add_argument("--method", default="GET",
                    help="HTTP method (POST needs --body-file or "
                         "--score-corpus)")
    lg.add_argument("--body-file", default="",
                    help="fixed request body read from this file")
    lg.add_argument("--content-type", default="",
                    help="Content-Type for --body-file requests")
    lg.add_argument("--score-corpus", default="",
                    help="generate POST payloads from a corpus spec, "
                         "e.g. libsvm:rows=4,features=64,nnz=8,seed=3")
    lg.set_defaults(fn=run_loadgen)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
