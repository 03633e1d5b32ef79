"""Rig lane (doc/benchmarking.md): the out-of-process measurement plane.

Pins the honesty properties the rig exists for:

- out-of-process origins serve byte-identical data to the in-process
  mocks for all four backends (same corpus function, same handlers,
  different process) — measured through the real native client in a
  fresh subprocess, so the endpoint-env singletons never collide with
  the module-level mocks the rest of the suite pins;
- the open-loop generator records latency against INTENDED start times:
  an origin that stalls 200 ms every Nth response is visible in the
  intended-time p99 and invisible in the naive service-time p99 — the
  coordinated-omission pin (Tene / HdrHistogram);
- open-loop and closed-loop measurements diverge under saturation: the
  closed loop's throughput quietly caps while its latency looks healthy.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

import loadrig  # noqa: E402
from tests import mock_origin  # noqa: E402


def fetch_sha(origin, key) -> dict:
    """Raw-read a corpus key through the native client in a fresh
    process (fresh endpoint singletons) and return its JSON report."""
    env = dict(os.environ, **origin.env())
    out = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "loadrig.py"),
         "fetch-client", "--uri", origin.uri(key)],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-500:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# origin plane
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,key", [
    ("s3", "bkt/rig/blob.bin"),
    ("azure", "ctr/rig/blob.bin"),
    ("webhdfs", "/rig/blob.bin"),
    ("http", "/rig/blob.bin"),
])
def test_out_of_process_byte_identity(backend, key):
    """Every backend's out-of-process origin serves exactly the bytes
    the in-process mock stores for the same corpus spec."""
    import hashlib
    spec = f"{key}=1048576:97"
    want = mock_origin.pseudo_bytes(1048576, 97)
    # the in-process mock's store holds exactly these bytes...
    state, _, shutdown = mock_origin.serve_backend(backend)
    try:
        mock_origin.load_corpus(backend, state,
                                mock_origin.build_corpus([spec]))
        store = {"s3": lambda: state.objects[("bkt", "rig/blob.bin")],
                 "azure": lambda: state.blobs[("ctr", "rig/blob.bin")],
                 "webhdfs": lambda: state.files["/rig/blob.bin"],
                 "http": lambda: state.objects["/rig/blob.bin"]}
        assert store[backend]() == want
    finally:
        shutdown()
    # ...and the out-of-process origin serves them byte-identically
    # through the real native client (signing/redirects included)
    with loadrig.spawn_origin(backend, [spec]) as org:
        got = fetch_sha(org, key)
    assert got["bytes"] == len(want)
    assert got["sha256"] == hashlib.sha256(want).hexdigest()


def test_preforked_workers_and_teardown():
    """--workers pre-forks that many processes over one listener, and
    close() leaves none of them behind."""
    cfg = mock_origin.OriginConfig(workers=2)
    org = loadrig.spawn_origin("http", ["/x=4096:1"], cfg)
    try:
        assert len(org.pids) == 2
        assert fetch_sha(org, "/x")["bytes"] == 4096
    finally:
        org.close()
    deadline = time.monotonic() + 10
    live = set(org.pids)
    while live and time.monotonic() < deadline:
        for pid in list(live):
            try:
                os.kill(pid, 0)
            except OSError:
                live.discard(pid)
        time.sleep(0.1)
    assert not live, f"origin workers survived close(): {live}"


def test_one_config_surface():
    """The same OriginConfig drives in-process serving and the
    out-of-process CLI: knobs land on the state either way, and
    reset_state returns every knob to its default."""
    cfg = mock_origin.OriginConfig(latency_ms=7, reset_every=3,
                                   backlog=64, slow_every=5, slow_ms=40)
    state, _, shutdown = mock_origin.serve_backend("http", cfg)
    try:
        assert (state.latency_ms, state.reset_every,
                state.slow_every, state.slow_ms) == (7, 3, 5, 40)
        mock_origin.reset_state(state)
        assert (state.latency_ms, state.reset_every,
                state.slow_every, state.slow_ms) == (0, 0, 0, 0)
    finally:
        shutdown()
    args = cfg.cli_args()
    for flag, val in (("--latency-ms", "7"), ("--reset-every", "3"),
                      ("--slow-every", "5"), ("--slow-ms", "40"),
                      ("--backlog", "64")):
        assert val == args[args.index(flag) + 1]
    # an unknown knob errors instead of silently no-opping
    with pytest.raises(AttributeError):
        mock_origin.apply_config(
            state, mock_origin.OriginConfig(extra={"no_such_knob": 1}))


# -- an object store's shape (ISSUE 34) ---------------------------------------
@pytest.mark.parametrize("nbytes,latency_ms,block,first,want", [
    # unset, the head waits latency_ms as it always did
    (4 << 20, 3, 262144, None, [3] + [3] * 15),
    (0, 7, 262144, None, [7]),
    (1, 0, 262144, None, []),
    # 100 ms to the first byte, then 256 KiB every 3 ms
    (4 << 20, 3, 262144, 100, [100] + [3] * 15),
    ((4 << 20) + 1, 3, 262144, 100, [100] + [3] * 16),
    (262144, 3, 262144, 100, [100]),
    # the first byte alone: no pacing of the body
    (4 << 20, 0, 262144, 100, [100]),
    (4 << 20, 3, 262144, 0, [3] * 15),
])
def test_first_byte_ms_sleeps_before_the_head_and_latency_paces_the_body(
        monkeypatch, nbytes, latency_ms, block, first, want):
    """The sleeps a response of n bytes incurs (``time.sleep`` recorded,
    no wall clock)."""
    from tests import mock_s3
    slept, wrote = [], []

    class Handler:
        wfile = type("W", (), {"write": staticmethod(
            lambda b: wrote.append(len(b)))})

        def send_response(self, status):
            wrote.append("head")

        def send_header(self, k, v):
            pass

        def end_headers(self):
            pass

    monkeypatch.setattr(mock_s3.time, "sleep",
                        lambda s: slept.append(round(s * 1e3)))
    mock_s3.send_with_latency(Handler(), 206, bytes(nbytes), None,
                              latency_ms, block, first)
    assert slept == want
    assert wrote[0] == "head" and sum(wrote[1:]) == nbytes


def test_first_byte_ms_round_trips_through_the_cli_and_the_state():
    cfg = mock_origin.OriginConfig(first_byte_ms=100, latency_ms=3,
                                   workers=4)
    args = cfg.cli_args()
    assert args[args.index("--first-byte-ms") + 1] == "100"
    assert args[args.index("--latency-ms") + 1] == "3"
    assert "--first-byte-ms" not in mock_origin.OriginConfig(
        latency_ms=3).cli_args()
    # the CLI's parser gives the knob back as it was
    sys_argv = ["origin", "--backend", "s3"] + args
    parsed = loadrig.build_parser().parse_args(sys_argv)
    assert (parsed.first_byte_ms, parsed.latency_ms, parsed.workers) \
        == (100, 3, 4)
    assert loadrig.build_parser().parse_args(
        ["origin", "--backend", "s3"]).first_byte_ms is None
    for backend in ("s3", "http"):
        state, _, shutdown = mock_origin.serve_backend(backend, cfg)
        try:
            assert (state.first_byte_ms, state.latency_ms) == (100, 3)
            mock_origin.reset_state(state)
            assert (state.first_byte_ms, state.latency_ms) == (None, 0)
        finally:
            shutdown()


def test_keys_of_one_path_share_one_buffer(tmp_path):
    day = tmp_path / "day"
    day.write_bytes(b"0\t1\n" * 1000)
    other = tmp_path / "other"
    other.write_bytes(b"x")
    corpus = mock_origin.build_corpus(
        [f"criteo/day_{i:02d}=@{day}" for i in range(24)]
        + [f"criteo/other=@{other}", "criteo/p=16:3"])
    days = [corpus[f"criteo/day_{i:02d}"] for i in range(24)]
    assert all(d is days[0] for d in days) and len(days[0]) == 4000
    assert corpus["criteo/other"] == b"x" and len(corpus["criteo/p"]) == 16
    state, _, shutdown = mock_origin.serve_backend("s3")
    try:
        mock_origin.load_corpus("s3", state, corpus)
        assert all(state.objects[("criteo", f"day_{i:02d}")] is days[0]
                   for i in range(24))
    finally:
        shutdown()


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    with open(f"/proc/{pid}/stat") as f:  # a zombie awaiting its reaper
        return f.read().rsplit(")", 1)[1].split()[0] != "Z"


def test_an_origin_stops_when_its_caller_is_gone(tmp_path):
    """``spawn_origin``'s launcher watches its parent: a caller that is
    killed leaves no origin behind (and ``ttl_s`` bounds it besides)."""
    script = tmp_path / "caller.py"
    script.write_text(
        "import sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from scripts import loadrig\n"
        "o = loadrig.spawn_origin('s3', ['b/k=64:1'], ttl_s=120)\n"
        "print(o.proc.pid, *o.pids, flush=True)\n"
        "time.sleep(60)\n")
    caller = subprocess.Popen([sys.executable, str(script)],
                              stdout=subprocess.PIPE, text=True)
    pids = [int(p) for p in caller.stdout.readline().split()]
    assert len(pids) >= 2
    caller.kill()
    caller.wait(10)
    deadline = time.time() + 10
    live = pids
    while live and time.time() < deadline:
        live = [p for p in live if _pid_alive(p)]
        time.sleep(0.1)
    assert not live, f"the origin outlived its caller: {live}"


# ---------------------------------------------------------------------------
# open-loop load generator
# ---------------------------------------------------------------------------
def test_open_loop_smoke_fixed_qps():
    """5 s at a fixed target QPS against an out-of-process origin: every
    arrival completes, none shed, achieved tracks offered."""
    with loadrig.spawn_origin("http", ["/tiny=4096:3"]) as org:
        fn = loadrig.http_request_fn(org.uri("/tiny"))
        r = loadrig.open_loop(fn, qps=150, duration_s=5, max_inflight=8)
    assert r["arrivals"] == 750
    assert r["completed"] == 750
    assert r["errors"] == 0 and r["shed"] == 0
    assert abs(r["achieved_qps"] - r["offered_qps"]) \
        <= 0.25 * r["offered_qps"]
    # both clocks populated; intended can never undercut service
    assert r["service_us"]["count"] == 750
    assert r["intended_us"]["p99"] >= r["service_us"]["p99"]


def test_coordinated_omission_pin():
    """An origin stalling 200 ms every 240th response: the stall queues
    arrivals behind the single in-flight slot, so the intended-time p99
    sees it while the naive service-time p99 — which only times
    send-to-response — hides it.  The service-time capture only admits
    the stall at p999 (the stalled requests themselves).

    720 arrivals with ~3 stalls keeps the stall fraction (0.4%) well
    under the p99 index (8th-worst sample) — the service-p99 bound must
    not flip on a couple of host-jitter outliers on a 1-core box."""
    cfg = mock_origin.OriginConfig(slow_every=240, slow_ms=200)
    with loadrig.spawn_origin("http", ["/tiny=4096:3"], cfg) as org:
        fn = loadrig.http_request_fn(org.uri("/tiny"))
        r = loadrig.open_loop(fn, qps=120, duration_s=6, max_inflight=1)
    assert r["errors"] == 0 and r["completed"] == r["arrivals"]
    intended_p99 = r["intended_us"]["p99"]
    service_p99 = r["service_us"]["p99"]
    assert intended_p99 >= 131072, \
        f"intended p99 {intended_p99}us misses the 200ms stall queue"
    assert service_p99 <= 65536, \
        f"service p99 {service_p99}us should hide the rare stall"
    assert intended_p99 >= 4 * service_p99
    # the stall IS in the service capture — but only at p999
    assert r["service_us"]["p999"] >= 131072


def test_open_vs_closed_loop_divergence_under_saturation():
    """A 30 ms/request origin saturates 2 closed-loop workers at ~60
    QPS: the closed loop reports that rate with healthy-looking
    latency, while the open loop — holding the 200 QPS schedule the
    closed loop silently abandoned — shows the queueing delay."""
    cfg = mock_origin.OriginConfig(latency_ms=30)
    with loadrig.spawn_origin("http", ["/tiny=4096:3"], cfg) as org:
        fn = loadrig.http_request_fn(org.uri("/tiny"))
        closed = loadrig.closed_loop(fn, workers=2, duration_s=3)
        opened = loadrig.open_loop(fn, qps=200, duration_s=3,
                                   max_inflight=2)
    assert closed["achieved_qps"] < 0.5 * 200, \
        "closed loop should cap far below the open-loop target"
    assert opened["intended_us"]["p99"] >= \
        4 * closed["service_us"]["p99"], (
            f"open-loop intended p99 {opened['intended_us']['p99']} "
            f"should dwarf closed-loop p99 "
            f"{closed['service_us']['p99']} under saturation")


def test_shed_policy_bounds_lateness():
    """With a lateness budget, an overloaded open loop sheds arrivals
    instead of queueing unboundedly — and accounts for every arrival."""
    cfg = mock_origin.OriginConfig(latency_ms=50)
    with loadrig.spawn_origin("http", ["/tiny=4096:3"], cfg) as org:
        fn = loadrig.http_request_fn(org.uri("/tiny"))
        r = loadrig.open_loop(fn, qps=100, duration_s=2, max_inflight=1,
                              shed_after_ms=100)
    assert r["shed"] > 50
    assert r["completed"] + r["shed"] == r["arrivals"]


def test_quantile_from_log2_buckets():
    """The bucket-scheme quantile the generator reports percentiles
    from: upper bounds, overflow to inf, empty to 0."""
    from dmlc_core_tpu import telemetry
    h = telemetry.Histogram("q", {})
    assert h.quantile(0.5) == 0.0
    for _ in range(99):
        h.observe(1000)       # bucket le=1024
    h.observe(3_000_000)      # bucket le=2^22
    assert h.quantile(0.5) == 1024.0
    assert h.quantile(0.99) == 1024.0
    assert h.quantile(0.999) == float(1 << 22)
    h2 = telemetry.Histogram("q2", {})
    h2.observe(float(1 << 40))
    assert h2.quantile(0.5) == float("inf")
    with pytest.raises(ValueError):
        h2.quantile(0.0)
