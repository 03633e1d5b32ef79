"""Serving lane (doc/serving.md): the batched online scoring server.

Pins the tentpole properties end to end against real sockets:

- scores from ``POST /score`` match the trainer's forward math exactly
  (libsvm and csv payloads, keep-alive connections);
- the robustness plane degrades loudly and in order: bounded queue
  (503 ``queue_full``), intended-time lateness shed (429 measured from
  ARRIVAL, not service start), circuit breaker on forward failures
  (open -> half-open probe -> closed), last-good model on failed
  reloads, draining shutdown that answers every admitted request;
- ``/readyz`` (readiness: flips 503 while draining) is split from
  ``/healthz`` (liveness: stays 200);
- bucket padding keeps the jitted forward's shape set finite:
  ``steady_new_shapes == 0`` under ragged row counts;
- the tracker's scrape surface gained the same hardening (431 for
  oversized heads, 405 for sniffed non-GET methods) when the HTTP
  plumbing was extracted into ``tracker/minihttp.py``;
- the loadrig POST plane drives ``/score`` open-loop.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.serving import batching
from dmlc_core_tpu.serving import model as serving_model
from dmlc_core_tpu.serving.server import (BREAKER_CLOSED, BREAKER_OPEN,
                                          ScoringServer, ServingConfig)
from dmlc_core_tpu.tracker import minihttp
from tests.serving_util import (AsyncReq, Client, ForwardGate,
                                expect_scores, raw_http, save_linear,
                                serving_server, sigmoid)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

import loadrig  # noqa: E402


def _shed(reason) -> int:
    return telemetry.counter("serve_shed_total",
                             {"reason": reason}).value


# ---------------------------------------------------------------------------
# scoring correctness
# ---------------------------------------------------------------------------
def test_libsvm_scores_match_trainer_math(tmp_path):
    uri, w, b = save_linear(tmp_path)
    lines = ["1 0:0.5 3:-1.25 7:2.0",
             "0 1:1.0",
             "1 2:0.25 30:0.75 31:-0.5"]
    with serving_server(uri) as srv:
        cli = Client(srv.port)
        try:
            status, body = cli.score(lines)
            assert status == 200, body
            doc = json.loads(body)
            assert doc["rows"] == 3
            assert doc["model_step"] == 1
            np.testing.assert_allclose(doc["scores"],
                                       expect_scores(lines, w, b),
                                       atol=1e-5)
        finally:
            cli.close()


def test_csv_scores_match_trainer_math(tmp_path):
    features = 8
    uri, w, b = save_linear(tmp_path, features=features)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, features)).astype(np.float32)
    lines = [",".join(f"{v:.6f}" for v in row) for row in x]
    with serving_server(uri) as srv:
        cli = Client(srv.port)
        try:
            status, body = cli.score(lines, ctype="text/csv")
            assert status == 200, body
            want = sigmoid(x.astype(np.float64) @ w.astype(np.float64)
                           + float(b))
            np.testing.assert_allclose(json.loads(body)["scores"], want,
                                       atol=1e-4)
        finally:
            cli.close()


def test_keep_alive_connection_reuse(tmp_path):
    uri, w, b = save_linear(tmp_path)
    with serving_server(uri) as srv:
        cli = Client(srv.port)
        try:
            for _ in range(3):
                status, body = cli.score(["1 0:1.0"])
                assert status == 200
            # a structured 4xx must not burn the connection either
            status, body = cli.score(["1 0:1.0"],
                                     ctype="application/json")
            assert status == 400
            status, _ = cli.score(["1 0:1.0"])
            assert status == 200
        finally:
            cli.close()


# ---------------------------------------------------------------------------
# endpoints and admission-time 4xx edges
# ---------------------------------------------------------------------------
def test_endpoints_and_4xx_edges(tmp_path):
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, rows_buckets="4",
                        max_body_bytes=4096) as srv:
        cli = Client(srv.port)
        try:
            status, body = cli.request("GET", "/healthz")
            assert status == 200
            status, body = cli.request("GET", "/readyz")
            assert status == 200 and json.loads(body)["ready"]
            status, body = cli.request("GET", "/statz")
            assert status == 200
            doc = json.loads(body)
            assert doc["rows_buckets"] == [4]
            assert doc["model"]["kind"] == "linear"
            assert doc["device"] == {"platform": "cpu",
                                     "device_kind": "cpu",
                                     "device_count": 8}
            assert doc["compile"]["backend_compiles"] >= 0
            status, body = cli.request("GET", "/metrics")
            assert status == 200
            assert b"serve_requests_total" in body
            status, body = cli.request("GET", "/nope")
            assert status == 404
            # empty payload
            status, body = cli.request(
                "POST", "/score", b"\n\n",
                {"Content-Type": "application/x-libsvm"})
            assert status == 400 and b"empty payload" in body
            # more rows than the largest bucket -> 413 at admission
            status, body = cli.score([f"1 0:{i}.0" for i in range(6)])
            assert status == 413 and b"largest" in body
            # unparseable deadline header -> 400
            status, body = cli.score(["1 0:1.0"],
                                     headers={"X-Deadline-Ms": "soon"})
            assert status == 400 and b"X-Deadline-Ms" in body
            # oversized body -> 413 before the queue ever sees it
            status, body = cli.score(
                ["1 " + " ".join(f"{j}:1.0" for j in range(3))] * 200)
            assert status == 413
        finally:
            cli.close()


def test_raw_socket_edges(tmp_path):
    """The hardening edges http.client cannot send: missing
    Content-Length (411), oversized request head (431), malformed
    request line (400), unknown method (405)."""
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri) as srv:
        before = telemetry.counter("serve_rejects_total",
                                   {"code": "431"}).value
        got = raw_http(srv.port,
                       b"POST /score HTTP/1.1\r\nHost: a\r\n\r\n")
        assert b"411" in got.split(b"\r\n")[0]
        big = (b"GET / HTTP/1.1\r\nX-Pad: " + b"a" * 9000)
        got = raw_http(srv.port, big)
        assert b"431" in got.split(b"\r\n")[0]
        assert telemetry.counter("serve_rejects_total",
                                 {"code": "431"}).value == before + 1
        got = raw_http(srv.port, b"BANANA\r\n\r\n")
        assert b"400" in got.split(b"\r\n")[0]
        got = raw_http(srv.port, b"BREW /score HTTP/1.1\r\n"
                                 b"Connection: close\r\n\r\n")
        assert b"405" in got.split(b"\r\n")[0]
        # the server is still fine after all of that
        cli = Client(srv.port)
        try:
            assert cli.request("GET", "/healthz")[0] == 200
        finally:
            cli.close()


# ---------------------------------------------------------------------------
# robustness plane
# ---------------------------------------------------------------------------
def test_bounded_queue_sheds_503(tmp_path):
    uri, _, _ = save_linear(tmp_path)
    # lateness shedding off: r2 waits out r1's gated forward, whose first
    # call may compile for longer than the 200 ms budget, and this test is
    # about the bounded queue's 503 alone
    with serving_server(uri, rows_buckets="4", queue_max=1,
                        batch_delay_ms=0.0, shed_lateness_ms=0.0,
                        breaker_threshold=1000) as srv:
        gate = ForwardGate(srv._model)
        gate.arm()
        before = _shed("queue_full")
        r1 = AsyncReq(srv.port, "POST", "/score", b"1 0:1.0\n",
                      {"Content-Type": "application/x-libsvm"})
        gate.wait_entered()             # r1 is inside the forward
        r2 = AsyncReq(srv.port, "POST", "/score", b"1 1:1.0\n",
                      {"Content-Type": "application/x-libsvm"})
        deadline = time.monotonic() + 10
        while srv.statz()["queue_depth"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        cli = Client(srv.port)
        try:
            status, body = cli.score(["1 2:1.0"])
            assert status == 503 and b"queue_full" in body
        finally:
            cli.close()
        assert _shed("queue_full") == before + 1
        gate.release()
        assert r1.result()[0] == 200
        assert r2.result()[0] == 200


def test_intended_time_lateness_shed_429(tmp_path):
    """A request that sat queued past its budget is shed 429 at
    dequeue: the clock runs from ARRIVAL, so queue time counts even
    though no service was ever attempted on it."""
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, rows_buckets="4",
                        batch_delay_ms=0.0) as srv:
        gate = ForwardGate(srv._model)
        gate.arm()
        before = _shed("late")
        r1 = AsyncReq(srv.port, "POST", "/score", b"1 0:1.0\n",
                      {"Content-Type": "application/x-libsvm"})
        gate.wait_entered()
        r2 = AsyncReq(srv.port, "POST", "/score", b"1 1:1.0\n",
                      {"Content-Type": "application/x-libsvm",
                       "X-Deadline-Ms": "1"})
        deadline = time.monotonic() + 10
        while srv.statz()["queue_depth"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.05)                # r2 ages past its 1ms budget
        gate.release()
        assert r1.result()[0] == 200
        status, body = r2.result()
        assert status == 429 and b"lateness budget" in body
        assert _shed("late") == before + 1


def test_breaker_opens_half_opens_recovers(tmp_path):
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, rows_buckets="4", batch_delay_ms=0.0,
                        breaker_threshold=2,
                        breaker_cooldown_ms=300.0) as srv:
        real = srv._model.scores

        def boom(row, col, val, num_rows):
            raise RuntimeError("injected forward fault")

        srv._model.scores = boom
        before = _shed("breaker")
        cli = Client(srv.port)
        try:
            for _ in range(2):
                status, body = cli.score(["1 0:1.0"])
                assert status == 500 and b"forward failed" in body
            assert telemetry.gauge("serve_breaker_state").value \
                == BREAKER_OPEN
            # while open, admission sheds without touching the model
            status, body = cli.score(["1 0:1.0"])
            assert status == 503 and b"breaker" in body
            assert _shed("breaker") == before + 1
            status, body = cli.request("GET", "/readyz")
            assert status == 200     # breaker alone is not unreadiness
            assert json.loads(body)["breaker"] == BREAKER_OPEN
            # cooldown lapses; the half-open probe succeeds and closes
            srv._model.scores = real
            time.sleep(0.35)
            status, body = cli.score(["1 0:1.0"])
            assert status == 200, body
            assert telemetry.gauge("serve_breaker_state").value \
                == BREAKER_CLOSED
        finally:
            cli.close()


def test_reload_swap_and_last_good_fallback(tmp_path):
    uri1, w1, b1 = save_linear(tmp_path, step=1, seed=5)
    uri2, w2, b2 = save_linear(tmp_path, step=2, seed=11)
    lines = ["1 0:0.5 4:-1.0"]
    with serving_server(uri1) as srv:
        cli = Client(srv.port)
        try:
            status, body = cli.score(lines)
            np.testing.assert_allclose(json.loads(body)["scores"],
                                       expect_scores(lines, w1, b1),
                                       atol=1e-5)
            ok_before = telemetry.counter(
                "serve_model_reloads_total").value
            status, body = cli.request(
                "POST", "/reload",
                json.dumps({"uri": uri2}).encode())
            assert status == 200 and json.loads(body)["step"] == 2
            assert telemetry.counter(
                "serve_model_reloads_total").value == ok_before + 1
            status, body = cli.score(lines)
            doc = json.loads(body)
            assert doc["model_step"] == 2
            np.testing.assert_allclose(doc["scores"],
                                       expect_scores(lines, w2, b2),
                                       atol=1e-5)
            # a corrupt artifact fails the reload but NOT the service:
            # last-good (step 2) keeps answering, counted and evented
            bad = tmp_path / "corrupt.ckpt"
            bad.write_bytes(b"\x00garbage, not a checkpoint\xff" * 8)
            fail_before = telemetry.counter(
                "serve_model_reload_failures_total").value
            status, body = cli.request(
                "POST", "/reload",
                json.dumps({"uri": str(bad)}).encode())
            assert status == 503
            doc = json.loads(body)
            assert "reload failed" in doc["error"]
            assert doc["fallback"]["step"] == 2
            assert telemetry.counter(
                "serve_model_reload_failures_total").value \
                == fail_before + 1
            assert any(e.get("event") == "serve-reload-failed"
                       for e in telemetry.events())
            status, body = cli.score(lines)
            assert status == 200
            assert json.loads(body)["model_step"] == 2
            # bad reload body is a 400, not a queue entry
            status, body = cli.request("POST", "/reload", b"not json")
            assert status == 400
        finally:
            cli.close()


def test_draining_answers_admitted_sheds_rest(tmp_path):
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, rows_buckets="4",
                        batch_delay_ms=0.0) as srv:
        gate = ForwardGate(srv._model)
        gate.arm()
        r1 = AsyncReq(srv.port, "POST", "/score", b"1 0:1.0\n",
                      {"Content-Type": "application/x-libsvm"})
        gate.wait_entered()
        r2 = AsyncReq(srv.port, "POST", "/score", b"1 1:1.0\n",
                      {"Content-Type": "application/x-libsvm"})
        deadline = time.monotonic() + 10
        while srv.statz()["queue_depth"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        stopper = threading.Thread(
            target=lambda: srv.stop(drain=True, grace_s=15.0),
            daemon=True)
        stopper.start()
        deadline = time.monotonic() + 10
        while not srv.statz()["draining"]:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # readiness flips; liveness does not; new traffic is shed
        cli = Client(srv.port)
        try:
            assert cli.request("GET", "/healthz")[0] == 200
            status, body = cli.request("GET", "/readyz")
            assert status == 503 and json.loads(body)["draining"]
            status, body = cli.score(["1 2:1.0"])
            assert status == 503 and b"draining" in body
        finally:
            cli.close()
        gate.release()
        # every admitted request is answered, never dropped mid-drain
        assert r1.result()[0] == 200
        assert r2.result()[0] == 200
        stopper.join(30)
        assert not stopper.is_alive()


# ---------------------------------------------------------------------------
# bucket padding / compile-churn census
# ---------------------------------------------------------------------------
def test_warm_compiles_the_ladder_before_start(tmp_path):
    """Ready means ready: warm() runs the forward at every shape of the
    ladder, so traffic no denser than the floor shape meets no new
    shape — on the chip a first-sight compile costs more than the
    lateness budget and the latency objective allow."""
    assert batching.warm_shapes((4, 16), 32) == [
        (4, 32), (16, 32), (16, 64), (16, 128)]
    uri, w, b = save_linear(tmp_path)
    srv = ScoringServer(model_uri=uri, config=ServingConfig(
        rows_buckets="4,16", min_nnz_bucket=32, batch_delay_ms=0.0))
    serving_model._reset_shape_census()
    assert srv.warm() == 4
    assert serving_model.distinct_shapes() == 4
    srv.start()
    try:
        cli = Client(srv.port)
        try:
            rng = np.random.default_rng(3)
            for rows in (1, 4, 5, 16, 9):       # 8 nonzeros per row
                lines = ["1 " + " ".join(
                    f"{j}:{rng.uniform(-1, 1):.4f}"
                    for j in sorted(rng.choice(32, 8, replace=False)))
                    for _ in range(rows)]
                status, body = cli.score(lines)
                assert status == 200
                np.testing.assert_allclose(json.loads(body)["scores"],
                                           expect_scores(lines, w, b),
                                           atol=1e-5)
            assert serving_model.distinct_shapes() == 4
        finally:
            cli.close()
    finally:
        srv.stop(drain=False, grace_s=3.0)


def test_ragged_traffic_steady_new_shapes_zero(tmp_path):
    """After one warmup per bucket, ragged row counts produce ZERO new
    forward shapes: the serving analogue of the PR 15 device-lane
    compile census (padding to the ladder makes the shape set finite)."""
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, rows_buckets="4,16",
                        min_nnz_bucket=32,
                        batch_delay_ms=0.0) as srv:
        cli = Client(srv.port)
        try:
            for rows in (1, 5):         # one warmup per rows bucket
                assert cli.score([f"1 {i}:0.5" for i in range(rows)]
                                 )[0] == 200
            warm = serving_model.distinct_shapes()
            assert warm >= 2
            rng = np.random.default_rng(17)
            for _ in range(24):
                rows = int(rng.integers(1, 17))
                lines = [f"1 {int(rng.integers(0, 32))}:0.25"
                         for _ in range(rows)]
                assert cli.score(lines)[0] == 200
            assert serving_model.distinct_shapes() == warm, \
                "ragged traffic leaked past the bucket ladder"
            assert telemetry.gauge(
                "serve_distinct_shapes").value == warm
        finally:
            cli.close()


def test_padding_never_leaks_into_scores(tmp_path):
    """The same row scores identically whether it shares its padded
    batch with 0 or 3 co-rows (sacrificial-segment isolation)."""
    uri, w, b = save_linear(tmp_path)
    line = "1 0:0.5 3:-1.25"
    with serving_server(uri, rows_buckets="4", min_nnz_bucket=16) as srv:
        cli = Client(srv.port)
        try:
            _, body1 = cli.score([line])
            _, body4 = cli.score([line, "0 1:1.0", "0 2:1.0",
                                  "1 5:0.5"])
            s1 = json.loads(body1)["scores"][0]
            s4 = json.loads(body4)["scores"][0]
            assert abs(s1 - s4) < 1e-6
            np.testing.assert_allclose(
                s1, expect_scores([line], w, b)[0], atol=1e-5)
        finally:
            cli.close()


# ---------------------------------------------------------------------------
# batching unit seams
# ---------------------------------------------------------------------------
def test_parse_buckets_validation():
    assert batching.parse_buckets("16,4,256") == (4, 16, 256)
    from dmlc_core_tpu.base import DMLCError
    for bad in ("", "a,b", "0,4", "-2"):
        with pytest.raises(DMLCError):
            batching.parse_buckets(bad)


def test_payload_format_mapping():
    assert batching.payload_format("application/x-libsvm") == "libsvm"
    assert batching.payload_format("text/csv; charset=utf-8") == "csv"
    assert batching.payload_format("") == "libsvm"
    with pytest.raises(minihttp.HttpError) as ei:
        batching.payload_format("application/json")
    assert ei.value.status == 400


def test_parse_group_isolates_bad_payload(tmp_path):
    good = b"1 0:0.5 2:1.0\n0 1:0.25\n"
    bad = b"not_a_label 0:1.0\n"
    group = batching.parse_group([good, bad, good], "libsvm",
                                 str(tmp_path))
    assert group.errors[0] is None and group.errors[2] is None
    assert group.errors[1] is not None
    assert group.errors[1].status == 400
    assert group.num_rows == 4
    assert group.slices[0] == (0, 2) and group.slices[2] == (2, 4)


# ---------------------------------------------------------------------------
# tracker hardening (extracted minihttp discipline)
# ---------------------------------------------------------------------------
def test_tracker_sniffed_method_405_and_head_431():
    from dmlc_core_tpu.tracker.client import RendezvousClient
    from dmlc_core_tpu.tracker.rendezvous import RabitTracker
    tracker = RabitTracker("127.0.0.1", 1)
    tracker.start()
    got = raw_http(tracker.port,
                   b"POST /metrics HTTP/1.1\r\nHost: x\r\n"
                   b"Content-Length: 0\r\n\r\n")
    assert b"405" in got.split(b"\r\n")[0]
    assert b"GET only" in got
    got = raw_http(tracker.port,
                   b"GET /metrics HTTP/1.1\r\nX-Pad: " + b"a" * 9000)
    assert b"431" in got.split(b"\r\n")[0]
    # the tracker survived both and still completes a real job
    c = RendezvousClient("127.0.0.1", tracker.port)
    a = c.start()
    assert a.rank == 0
    c.shutdown(a.rank)
    tracker.join(timeout=30)


# ---------------------------------------------------------------------------
# loadrig POST plane
# ---------------------------------------------------------------------------
def test_corpus_spec_grammar():
    opts = loadrig.parse_corpus_spec(
        "libsvm:rows=2,rows_max=8,features=64,nnz=4,seed=9")
    assert opts == {"fmt": "libsvm", "rows": 2, "rows_max": 8,
                    "features": 64, "nnz": 4, "seed": 9}
    assert loadrig.parse_corpus_spec("csv")["fmt"] == "csv"
    for bad in ("tsv", "libsvm:rows=0", "libsvm:bogus=3",
                "libsvm:rows"):
        with pytest.raises(ValueError):
            loadrig.parse_corpus_spec(bad)


def test_score_payloads_deterministic_and_ragged():
    spec = "libsvm:rows=2,rows_max=5,features=32,nnz=3,seed=4"
    fn_a, ctype = loadrig.score_payload_fn(spec)
    fn_b, _ = loadrig.score_payload_fn(spec)
    assert ctype == "application/x-libsvm"
    a = [fn_a() for _ in range(12)]
    b = [fn_b() for _ in range(12)]
    assert a == b, "same spec + same request index must be byte-equal"
    sizes = {p.count(b"\n") for p in a}
    assert sizes == {2, 3, 4, 5}, sizes
    _, ctype = loadrig.score_payload_fn("csv:rows=1,features=4")
    assert ctype == "text/csv"


def test_open_loop_post_against_live_server(tmp_path):
    uri, _, _ = save_linear(tmp_path, features=64)
    with serving_server(uri, rows_buckets="8",
                        min_nnz_bucket=64) as srv:
        payload_fn, ctype = loadrig.score_payload_fn(
            "libsvm:rows=1,rows_max=4,features=64,nnz=4,seed=2")
        statuses = []
        fn = loadrig.http_request_fn(
            f"http://127.0.0.1:{srv.port}/score", method="POST",
            headers={"Content-Type": ctype}, payload_fn=payload_fn,
            on_status=statuses.append)
        fn()                            # jit warmup outside the window
        out = loadrig.open_loop(fn, qps=60, duration_s=0.7,
                                max_inflight=16)
        assert out["completed"] > 0
        assert out["errors"] == 0, out
        assert all(s == 200 for s in statuses)
        assert out["intended_us"]["p99"] >= out["service_us"]["p99"] \
            or out["intended_us"]["p99"] > 0


# ---------------------------------------------------------------------------
def test_access_log_and_breaker_flight_dump(tmp_path, monkeypatch):
    """Observability satellites: every answered/shed request lands one
    structured JSONL access-log line (request id, status, intended-time
    latency, cause), and a breaker trip is a flight-recorder trigger —
    the dump reason names the consecutive-failure count vs the
    threshold (doc/observability.md flight-recorder table)."""
    dump_dir = tmp_path / "dumps"
    monkeypatch.setenv("DMLC_TRACE_DUMP", str(dump_dir))
    alog = tmp_path / "access.jsonl"
    uri, _, _ = save_linear(tmp_path)
    with serving_server(uri, access_log=str(alog), batch_delay_ms=0.0,
                        breaker_threshold=2,
                        breaker_cooldown_ms=60000.0) as srv:
        cli = Client(srv.port)
        try:
            status, _ = cli.score(["1 0:1.0"],
                                  headers={"X-Request-Id": "acc-1"})
            assert status == 200

            def boom(row, col, val, num_rows):
                raise RuntimeError("injected forward fault")

            srv._model.scores = boom
            for _ in range(2):
                status, _ = cli.score(["1 0:1.0"])
                assert status == 500
            status, body = cli.score(["1 0:1.0"])  # open: admission shed
            assert status == 503 and b"breaker" in body
        finally:
            cli.close()

    dumps = [json.load(open(dump_dir / f)) for f in os.listdir(dump_dir)]
    trips = [d for d in dumps
             if d["reason"].startswith("serve-breaker-open")]
    assert trips, [d["reason"] for d in dumps]
    assert "2 consecutive" in trips[0]["reason"]

    lines = [json.loads(ln) for ln in alog.read_text().splitlines()
             if ln]
    by_cause = {}
    for rec in lines:
        assert {"ts", "request_id", "status", "latency_ms",
                "cause"} <= set(rec), rec
        by_cause.setdefault(rec["cause"], []).append(rec)
    assert by_cause["scored"][0]["request_id"] == "acc-1"
    assert by_cause["scored"][0]["status"] == 200
    assert [r["status"] for r in by_cause["error"]] == [500, 500]
    assert by_cause["breaker"][0]["status"] == 503
