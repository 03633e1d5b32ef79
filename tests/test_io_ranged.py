"""Parallel ranged remote reads + adaptive readahead (cpp/src/range_reader.h).

Live-backend coverage of the concurrent range-reader engine behind every
remote filesystem (the deterministic in-memory engine suite is
``test_core --range``):

- byte-identity across all four backends with the ranged lane FORCED
  (small ranges, 4-way concurrency) — the head-of-line delivery guarantee;
- the parse pipeline riding the ranged lane end to end (RowBlocks from an
  s3:// libsvm source identical to the local-file parse);
- degrade-to-sequential when an origin ignores Range and answers 200,
  counted in ``io_range_degraded_200_total``;
- the 206 Content-Range regression: a misaligned window is a retryable
  error for the ranged AND sequential lanes, never silently spliced bytes;
- per-open ``?io_range*=`` URI knobs, env knobs, and checked parsing;
- the ``latency_ms`` mock knob making range concurrency observable:
  against a latency-capped origin the ranged lane must beat sequential.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager

import numpy as np
import pytest

from test_s3 import _STATE as S3_STATE, put as s3_put  # noqa: E402
from test_azure import _STATE as AZ_STATE, put as az_put  # noqa: E402
from test_webhdfs import _STATE as HD_STATE, uri as hdfs_uri  # noqa: E402
from test_io_resilience import (_reset_backend_faults,  # noqa: E402
                                pseudo_bytes)

import tests.mock_origin as mock_origin  # noqa: E402

from dmlc_core_tpu import telemetry  # noqa: E402
from dmlc_core_tpu.base import DMLCError  # noqa: E402
from dmlc_core_tpu.io import native  # noqa: E402
from dmlc_core_tpu.io.native import NativeParser, NativeStream  # noqa: E402

# force the ranged lane regardless of object size: 64 KiB ranges, 4 workers
RANGED_ENV = {
    "DMLC_IO_RANGE": "1",
    "DMLC_IO_RANGE_MIN_BYTES": "65536",
    "DMLC_IO_RANGE_MAX_BYTES": "262144",
    "DMLC_IO_RANGE_CONCURRENCY": "4",
}


@contextmanager
def env(**kv):
    old = {}
    try:
        for k, v in kv.items():
            old[k] = os.environ.get(k)
            os.environ[k] = str(v)
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def counter(name: str) -> int:
    snap = telemetry.snapshot()
    return sum(c["value"] for c in snap["counters"] if c["name"] == name)


@pytest.fixture(autouse=True)
def clean_ranged_state():
    _reset_backend_faults()
    native.set_io_fault_plan("")
    native.set_io_timeout_ms(0)
    native.reset_io_retry_stats()
    yield
    _reset_backend_faults()
    native.set_io_fault_plan("")
    native.set_io_timeout_ms(0)


@pytest.fixture()
def http_origin():
    # the shared launcher (tests/mock_origin.py): deep accept backlog by
    # default — the 12-way connect bursts need it
    state, port, shutdown = mock_origin.serve_backend("http")
    yield state, f"http://127.0.0.1:{port}"
    shutdown()


def _read(uri: str) -> bytes:
    with NativeStream(uri, "r") as s:
        return s.read_all()


def _gets(state) -> list:
    return [p for m, p in state.requests if m == "GET"]


# -- head-of-line delivery: byte-identical across every backend ---------------
def test_ranged_read_byte_identical_all_backends(http_origin):
    hstate, hbase = http_origin
    payload = pseudo_bytes(3 << 20, seed=31)
    s3_put("ranged/blob.bin", payload)
    az_put("ranged/blob.bin", payload)
    HD_STATE.files["/ranged/blob.bin"] = payload
    hstate.objects["/ranged-blob.bin"] = payload

    uris = {
        "s3": (S3_STATE, "s3://bkt/ranged/blob.bin"),
        "azure": (AZ_STATE, "azure://ctr/ranged/blob.bin"),
        "webhdfs": (HD_STATE, hdfs_uri("/ranged/blob.bin")),
        "http": (hstate, hbase + "/ranged-blob.bin"),
    }
    with env(**RANGED_ENV):
        before = counter("io_range_issued_total")
        for backend, (state, uri) in uris.items():
            state.requests.clear()
            assert _read(uri) == payload, f"{backend} corrupted ranged data"
            # a 3 MiB object in <=256 KiB ranges: many data requests, not
            # one streaming GET
            assert len(_gets(state)) >= 6, (
                f"{backend} did not issue parallel ranged requests: "
                f"{state.requests[:10]}")
    assert counter("io_range_issued_total") - before >= 4 * 12
    # the webhdfs lane must have used bounded OPENs
    assert any("length=" in p for p in _gets(HD_STATE))


# -- the parse pipeline rides the ranged lane ---------------------------------
def test_parse_pipeline_rides_ranged_lane(tmp_path):
    rng = np.random.default_rng(7)
    lines = []
    for i in range(20000):
        nnz = rng.integers(1, 6)
        feats = " ".join(
            f"{int(j)}:{float(v):.3f}"
            for j, v in zip(rng.integers(0, 100, nnz),
                            rng.random(nnz)))
        lines.append(f"{i % 2} {feats}")
    text = ("\n".join(lines) + "\n").encode()
    local = tmp_path / "ranged.libsvm"
    local.write_bytes(text)
    s3_put("ranged/data.libsvm", text)

    def blocks(uri):
        p = NativeParser(uri, fmt="libsvm")
        out = []
        while True:
            b = p.next_block()
            if b is None:
                break
            # views expire on the next call: copy out
            out.append((b.label.copy(), b.index.copy(), b.value.copy()))
        p.close()
        return out

    with env(**RANGED_ENV):
        remote = blocks("s3://bkt/ranged/data.libsvm")
    want = blocks(str(local))
    for part in range(3):
        got = np.concatenate([b[part] for b in remote])
        ref = np.concatenate([b[part] for b in want])
        np.testing.assert_array_equal(got, ref)


# -- degrade: a server that ignores Range answers 200 -------------------------
def test_degrade_on_200_byte_identical():
    payload = pseudo_bytes(1 << 20, seed=33)
    s3_put("deg/blob.bin", payload)
    S3_STATE.ignore_range = True
    with env(**RANGED_ENV):
        before = counter("io_range_degraded_200_total")
        assert _read("s3://bkt/deg/blob.bin") == payload
        assert counter("io_range_degraded_200_total") - before >= 1


# -- 206 Content-Range regression --------------------------------------------
def test_content_range_mismatch_is_retried_not_spliced():
    payload = pseudo_bytes(2 << 20, seed=35)
    s3_put("badcr/blob.bin", payload)
    # every 3rd ranged GET answers a 206 whose window (header AND body) is
    # shifted +64 bytes from the request: a client that trusts the body
    # without validating Content-Range splices wrong bytes SILENTLY; ours
    # must retry those ranges and still deliver identical data
    S3_STATE.bad_content_range_every = 3
    with env(**RANGED_ENV):
        assert _read("s3://bkt/badcr/blob.bin?io_backoff_base_ms=1") == (
            payload)
    assert native.io_retry_stats()["retries"] > 0


def test_content_range_mismatch_sequential_lane_detects_too():
    # the sequential reader (Range: bytes=N- resume) validates the same
    # header: an origin that ALWAYS misaligns must fail loudly, not
    # corrupt (small object + io_range=0 keep this on the sequential lane)
    payload = pseudo_bytes(256 << 10, seed=36)
    s3_put("badcr/seq.bin", payload)
    S3_STATE.bad_content_range_every = 1
    with pytest.raises(DMLCError, match="Content-Range"):
        _read("s3://bkt/badcr/seq.bin"
              "?io_range=0&io_max_retry=2&io_backoff_base_ms=1")


# -- knobs --------------------------------------------------------------------
def test_uri_and_env_knobs():
    payload = pseudo_bytes(1 << 20, seed=37)
    s3_put("knobs/blob.bin", payload)

    # kill switch per open: one streaming GET (plus the metadata probe,
    # which lists by prefix= and is excluded below)
    with env(**RANGED_ENV):
        S3_STATE.requests.clear()
        assert _read("s3://bkt/knobs/blob.bin?io_range=0") == payload
        data_gets = [p for p in _gets(S3_STATE)
                     if "knobs" in p and "prefix" not in p]
        assert len(data_gets) == 1, data_gets

        # garbage knob values are checked-parse errors, never silent
        with pytest.raises(DMLCError, match="invalid integer"):
            _read("s3://bkt/knobs/blob.bin?io_range_concurrency=banana")
        with pytest.raises(DMLCError, match="io_range"):
            _read("s3://bkt/knobs/blob.bin?io_rangee=1")  # typo: loud

    with env(DMLC_IO_RANGE_MIN_BYTES="banana"):
        with pytest.raises(DMLCError, match="invalid integer"):
            _read("s3://bkt/knobs/blob.bin")

    # global kill switch
    with env(DMLC_IO_RANGE="0"):
        S3_STATE.requests.clear()
        assert _read("s3://bkt/knobs/blob.bin") == payload
        data_gets = [p for p in _gets(S3_STATE)
                     if "knobs" in p and "prefix" not in p]
        assert len(data_gets) == 1, data_gets


# -- the scheduler against a latency-capped origin ----------------------------
def test_latency_capped_origin_ranged_beats_sequential():
    """With latency_ms injected (per request AND per 256 KiB body block —
    a latency-bandwidth-capped connection), N concurrent ranges must beat
    one sequential stream by a wide margin. This is the observable proof
    that range concurrency actually happens."""
    payload = pseudo_bytes(4 << 20, seed=39)
    s3_put("lat/blob.bin", payload)
    S3_STATE.latency_ms = 25

    with env(**RANGED_ENV):
        t0 = time.monotonic()
        got = _read("s3://bkt/lat/blob.bin?io_range=0")
        seq_s = time.monotonic() - t0
        assert got == payload

        t0 = time.monotonic()
        got = _read(
            "s3://bkt/lat/blob.bin?io_range_min_bytes=262144"
            "&io_range_max_bytes=1048576&io_range_concurrency=4")
        ranged_s = time.monotonic() - t0
        assert got == payload

    # sequential: ~17 x 25 ms of serialized block delay; ranged: 4-way
    # overlap. Generous 0.8 bound — sleep-dominated, stable on slow hosts.
    assert ranged_s < seq_s * 0.8, (
        f"ranged {ranged_s:.2f}s not faster than sequential {seq_s:.2f}s")


# -- scheduler telemetry surfaces ---------------------------------------------
def test_range_scheduler_telemetry():
    payload = pseudo_bytes(2 << 20, seed=41)
    s3_put("tel/blob.bin", payload)
    with env(**RANGED_ENV):
        before_issued = counter("io_range_issued_total")
        assert _read("s3://bkt/tel/blob.bin") == payload
    snap = telemetry.snapshot()
    issued = counter("io_range_issued_total") - before_issued
    assert issued >= 8  # 2 MiB in <=256 KiB ranges
    hists = {(h["name"], h["labels"].get("backend")): h
             for h in snap["histograms"]}
    hb = hists[("io_range_bytes", "s3")]
    assert hb["count"] >= 8
    assert hb["sum"] >= len(payload)
    assert ("io_range_wait_us", "s3") in hists
    gauges = {g["name"]: g["value"] for g in snap["gauges"]}
    assert gauges.get("io_range_sched_bytes", 0) >= 65536
    assert gauges.get("io_range_sched_concurrency", 0) >= 1
