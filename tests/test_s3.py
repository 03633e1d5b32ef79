"""S3 filesystem tests against the in-process mock server (SIG4-verified).

Covers the reference S3 behavior surface (src/io/s3_filesys.cc): signed
reads/writes/listing, ranged reads with seek, reconnect-on-short-read
retries, multipart upload, and the InputSplit/parser composition over
s3:// URIs.
"""

import os

import pytest

# one mock S3 a test process: the native S3 singleton reads its environment
# once, so every test file that reads s3:// shares tests/s3_shared.py's
from tests.s3_shared import PORT as _PORT, STATE as _STATE  # noqa: E402

from dmlc_core_tpu.base import DMLCError  # noqa: E402
from dmlc_core_tpu.io.native import (NativeInputSplit, NativeParser,  # noqa: E402
                                     NativeStream, list_directory, path_info)


@pytest.fixture(autouse=True)
def clean_state():
    _STATE.objects.clear()
    _STATE.uploads.clear()
    _STATE.fail_reads_after = None
    _STATE.requests.clear()
    yield


def put(key, data: bytes, bucket="bkt"):
    _STATE.objects[(bucket, key)] = data


def test_signed_read():
    put("a/hello.txt", b"hello s3 world")
    with NativeStream("s3://bkt/a/hello.txt", "r") as s:
        assert s.read_all() == b"hello s3 world"


def test_bad_signature_rejected(monkeypatch):
    # a wrong secret must produce a 403 from the verifying mock
    put("k", b"data")
    # the C++ singleton caches FromEnv at first use; use a tampered payload
    # instead: corrupt the object and check integrity via size mismatch is
    # not applicable — instead verify the server actually checks signatures
    # by asserting our *valid* requests pass while a raw unsigned one fails.
    import urllib.request
    import urllib.error
    req = urllib.request.Request(
        f"http://127.0.0.1:{_PORT}/bkt/k", method="GET")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req)
    assert e.value.code == 403


def test_ranged_read_and_seek():
    put("big.bin", bytes(range(256)) * 64)  # 16 KB
    # exercise Seek via the recordio-independent split path below; here use
    # stream read after fresh open (stream always starts at 0)
    with NativeStream("s3://bkt/big.bin", "r") as s:
        data = s.read_all()
    assert data == bytes(range(256)) * 64


def test_write_small_object_single_put():
    with NativeStream("s3://bkt/out/small.txt", "w") as s:
        s.write(b"tiny payload")
    assert _STATE.objects[("bkt", "out/small.txt")] == b"tiny payload"
    # exactly one PUT, no multipart
    assert not any("uploads" in p for m, p in _STATE.requests if m == "POST")


def test_write_multipart_large_object():
    chunk = os.urandom(1 << 20)
    big = chunk * 11  # 11 MB -> 2 full parts + remainder
    with NativeStream("s3://bkt/out/big.bin", "w") as s:
        for i in range(0, len(big), 1 << 20):
            s.write(big[i:i + (1 << 20)])
    assert _STATE.objects[("bkt", "out/big.bin")] == big
    posts = [p for m, p in _STATE.requests if m == "POST"]
    assert any("uploads" in p for p in posts)     # initiated
    assert any("uploadId" in p for p in posts)    # completed


def test_list_directory():
    put("data/a.txt", b"1")
    put("data/b.txt", b"22")
    put("data/sub/c.txt", b"333")
    put("other/x.txt", b"4")
    entries = list_directory("s3://bkt/data")
    names = {e[0]: e for e in entries}
    assert names["s3://bkt/data/a.txt"][1] == 1
    assert names["s3://bkt/data/b.txt"][1] == 2
    assert names["s3://bkt/data/sub"][2] == "d"
    assert "s3://bkt/other/x.txt" not in names


def test_path_info():
    put("p/file.bin", b"12345")
    assert path_info("s3://bkt/p/file.bin") == (5, False)
    assert path_info("s3://bkt/p")[1] is True
    with pytest.raises(DMLCError, match="does not exist"):
        path_info("s3://bkt/missing/file")


def test_path_info_prefix_collision_is_not_a_directory():
    # a key that shares the name as a string prefix must not make the
    # shorter name look like an existing directory
    put("database.csv", b"rows")
    with pytest.raises(DMLCError, match="does not exist"):
        path_info("s3://bkt/data")


def test_key_with_xml_entities():
    put("data/a&b.txt", b"ampersand")
    entries = list_directory("s3://bkt/data")
    assert entries == [("s3://bkt/data/a&b.txt", 9, "f")]
    assert path_info("s3://bkt/data/a&b.txt") == (9, False)


def test_read_retry_on_short_reads():
    # server sends truncated bodies; client must reconnect at offset and
    # finish (reference retry loop, s3_filesys.cc:522-546)
    payload = os.urandom(8192)
    put("flaky.bin", payload)
    _STATE.fail_reads_after = 1000
    with NativeStream("s3://bkt/flaky.bin", "r") as s:
        got = s.read_all()
    assert got == payload
    gets = [p for m, p in _STATE.requests if m == "GET" and "flaky" in p]
    assert len(gets) > 1  # reconnected at least once


def test_input_split_over_s3():
    lines = [f"row-{i}".encode() for i in range(500)]
    put("ds/part-000", b"\n".join(lines[:250]) + b"\n")
    put("ds/part-001", b"\n".join(lines[250:]) + b"\n")
    got = []
    for part in range(3):
        with NativeInputSplit("s3://bkt/ds/", part, 3, "text") as s:
            got.extend(s)
    assert got == lines


def test_parser_over_s3():
    text = "".join(f"{i % 2} 0:{i}.5 1:{i}.25\n" for i in range(300))
    put("train/data.libsvm", text.encode())
    with NativeParser("s3://bkt/train/data.libsvm") as p:
        rows = sum(b.num_rows for b in p)
    assert rows == 300


def test_sha256_matches_hashlib():
    """The C++ SHA-256 is exercised through SIG4 on every request above;
    this is the direct probe: an object PUT whose payload hash the mock
    verifies with hashlib (payload_hash != UNSIGNED-PAYLOAD on writes)."""
    body = os.urandom(70000)  # multi-block, non-aligned length
    with NativeStream("s3://bkt/hash/probe.bin", "w") as s:
        s.write(body)
    assert _STATE.objects[("bkt", "hash/probe.bin")] == body
    # if the C++ sha256(body) differed from hashlib's, the mock would have
    # rejected the PUT with 403 and the write would have raised


def test_binary_lanes_over_s3(tmp_path):
    """The round-3 binary ingest lanes compose with remote filesystems:
    convert locally, upload through the native s3:// stream, ingest the
    rec and recd lanes straight from s3:// (split/prefetch included)."""
    import numpy as np
    from dmlc_core_tpu.io.convert import (rows_to_dense_recordio,
                                          rows_to_recordio)
    from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter

    rng = np.random.default_rng(17)
    src = tmp_path / "s.libsvm"
    with open(src, "w") as f:
        for i in range(1500):
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.uniform():.4f}" for j in range(8)) + "\n")
    # converters write THROUGH the stream layer: s3:// destinations work
    rows_to_recordio(str(src), "s3://bkt/data/a.rec", rows_per_record=128)
    rows_to_dense_recordio(str(src), "s3://bkt/data/a.drec",
                           rows_per_record=128)
    for uri, fmt in (("s3://bkt/data/a.rec", "rec"),
                     ("s3://bkt/data/a.drec", "recd")):
        got = 0
        with DeviceRowBlockIter(uri, fmt=fmt, batch_rows=256,
                                to_device=False, dense_dtype="bf16") as it:
            for b in it:
                got += b.total_rows
        assert got == 1500, (uri, got)
    # partitioned remote read: exact cover
    got = 0
    for k in range(3):
        with DeviceRowBlockIter("s3://bkt/data/a.rec", fmt="rec", part=k,
                                npart=3, batch_rows=256,
                                to_device=False) as it:
            got += sum(b.total_rows for b in it)
    assert got == 1500
