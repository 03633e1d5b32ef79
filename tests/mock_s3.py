"""In-process mock S3 server for testing the native S3 client.

Implements the slice of the S3 REST API the client uses — object GET with
Range, PUT, multipart upload (create/part/complete), ListObjects — and
**recomputes the AWS SIG4 signature for every request** with Python
hashlib/hmac, rejecting mismatches with 403. This cross-validates the C++
SHA-256/HMAC/signing implementation (cpp/src/sha256.h, s3_filesys.cc)
against an independent one. The reference tests S3 only with manual soak
scripts against real AWS (reference test/README.md:3-30).
"""

from __future__ import annotations

import hashlib
import hmac
import re
import socket
import struct
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

ACCESS_KEY = "TESTACCESSKEY"
SECRET_KEY = "testSecretKey123"
REGION = "us-test-1"


def _sign(secret, date, region, string_to_sign):
    k = hmac.new(("AWS4" + secret).encode(), date.encode(),
                 hashlib.sha256).digest()
    k = hmac.new(k, region.encode(), hashlib.sha256).digest()
    k = hmac.new(k, b"s3", hashlib.sha256).digest()
    k = hmac.new(k, b"aws4_request", hashlib.sha256).digest()
    return hmac.new(k, string_to_sign.encode(), hashlib.sha256).hexdigest()


class DeepBacklogHTTPServer(ThreadingHTTPServer):
    """Shared by every backend mock: the parallel ranged readers open many
    connections at once, and socketserver's default backlog of 5 drops
    SYNs — each drop costs the client a ~1 s kernel retransmit."""

    request_queue_size = 128


class FaultCounterMixin:
    """Every-Nth fault scheduling shared by the backend mocks: each fault
    kind keeps a lock-guarded counter; ``_tick(kind, every)`` says whether
    this request draws the fault."""

    def _init_fault_counters(self, *kinds):
        self._fault_lock = threading.Lock()
        self._counters = {k: 0 for k in kinds}

    def _tick(self, kind, every):
        if not every:
            return False
        with self._fault_lock:
            self._counters[kind] += 1
            return self._counters[kind] % every == 0


class MockS3State(FaultCounterMixin):
    def __init__(self):
        self.objects = {}        # (bucket, key) -> bytes
        self.uploads = {}        # upload_id -> {num: bytes}
        self.next_upload = [0]
        self.fail_reads_after = None  # int: truncate GET bodies (retry test)
        self.requests = []       # (method, path) log
        # -- fault-injection plan (the automated md5 soak, reference
        #    test/README.md:3-30; faults apply AFTER signature checks) --
        self.get_truncate_every = 0   # every Nth GET: body cut mid-stream
        self.get_500_every = 0        # every Nth GET: 500 before body
        self.part_500_every = 0       # every Nth part PUT: 500
        self.complete_truncate_once = False  # one truncated Complete XML
        # hung-server faults (object GETs only, like the knobs above):
        # stall_every: accept, then sleep stall_seconds — past the client's
        # per-attempt timeout — before closing without a response;
        # reset_every: RST the connection mid-header (SO_LINGER 0)
        self.stall_every = 0
        self.stall_seconds = 3.0
        self.reset_every = 0
        # -- ranged-read knobs (cpp/src/range_reader.h lane) --
        self.latency_ms = 0        # per-request + per-block delay
        self.latency_block = LATENCY_BLOCK  # bytes per latency "burst"
        # the delay before the response head when it is not latency_ms:
        # an object store's time to the first byte (None: latency_ms)
        self.first_byte_ms = None
        self.ignore_range = False  # answer 200 full-body (Range ignored)
        # every Nth ranged GET: 206 whose Content-Range window (header AND
        # body, consistent with each other) is shifted +64 bytes from the
        # REQUEST — a client that skips Content-Range validation splices
        # wrong bytes silently instead of retrying
        self.bad_content_range_every = 0
        self._init_fault_counters("get500", "gettrunc", "part", "stall",
                                  "reset", "badcr")


# body bytes per latency "burst": with latency_ms set, a connection's
# throughput caps at LATENCY_BLOCK / latency_ms — the latency-bandwidth
# product of a long-haul link, reproduced on localhost
LATENCY_BLOCK = 256 * 1024


def send_with_latency(handler, status, data, headers=None, latency_ms=0,
                      block=LATENCY_BLOCK, first_byte_ms=None):
    """Send a response; with ``latency_ms`` the mock sleeps once before the
    response head and once per ``block`` bytes of body, emulating a remote
    origin whose per-connection throughput is capped by its
    latency-bandwidth product (block/latency per connection). This is what
    makes parallel ranged reads (cpp/src/range_reader.h) observable and
    benchable on localhost: one connection is capped, N concurrent ranges
    get ~N times the bandwidth. With ``first_byte_ms`` the sleep before
    the head is that, and ``latency_ms`` paces the body alone: 100 ms to
    the first byte, then 256 KiB every 3 ms, is an object store that
    answers late and then streams at 87 MB/s a connection."""
    head = latency_ms if first_byte_ms is None else first_byte_ms
    if head:
        time.sleep(head / 1000.0)
    handler.send_response(status)
    for k, v in (headers or {}).items():
        handler.send_header(k, v)
    handler.send_header("Content-Length", str(len(data)))
    handler.end_headers()
    if not latency_ms:
        handler.wfile.write(data)
        return
    for i in range(0, len(data), block):
        if i:
            time.sleep(latency_ms / 1000.0)
        handler.wfile.write(data[i:i + block])


def truncate_body(handler, status, data):
    """Mid-stream truncation: declared full length, half the body, then
    the connection is cut — the client must reconnect at offset."""
    out = data[: max(len(data) // 2, 1)]
    handler.send_response(status)
    handler.send_header("Content-Length", str(len(data)))
    handler.end_headers()
    handler.wfile.write(out)
    handler.close_connection = True


def stall_connection(handler, seconds):
    """Hold the accepted connection silent past the client deadline, then
    close with no response — the hung-server shape the socket timeouts in
    cpp/src/http.cc exist for."""
    time.sleep(seconds)
    handler.close_connection = True


def reset_connection(handler):
    """Close the socket mid-header with RST (SO_LINGER 0): the client sees
    a partial response head and a hard transport error."""
    try:
        handler.wfile.write(b"HTTP/1.1 200 OK\r\nContent-Le")
        handler.wfile.flush()
        handler.connection.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                      struct.pack("ii", 1, 0))
    except OSError:
        pass
    handler.close_connection = True


class MockS3Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    state: MockS3State = None  # set by serve()

    def log_message(self, *args):
        pass

    # -- SIG4 verification --------------------------------------------------
    def _verify_sig(self, body: bytes) -> bool:
        auth = self.headers.get("Authorization", "")
        m = re.match(
            r"AWS4-HMAC-SHA256 Credential=([^/]+)/(\d{8})/([^/]+)/s3/"
            r"aws4_request, SignedHeaders=([^,]+), Signature=([0-9a-f]+)",
            auth)
        if not m:
            return False
        access, date, region, signed_headers, signature = m.groups()
        if access != ACCESS_KEY or region != REGION:
            return False
        amz_date = self.headers["x-amz-date"]
        payload_hash = self.headers["x-amz-content-sha256"]
        if payload_hash != "UNSIGNED-PAYLOAD":
            if hashlib.sha256(body).hexdigest() != payload_hash:
                return False
        parsed = urllib.parse.urlsplit(self.path)
        pairs = urllib.parse.parse_qsl(parsed.query,
                                       keep_blank_values=True)
        enc = lambda s: urllib.parse.quote(s, safe="-_.~")
        cq = "&".join(f"{k}={v}" for k, v in
                      sorted((enc(k), enc(v)) for k, v in pairs))
        # reconstruct from the *declared* signed headers
        ch = ""
        for name in signed_headers.split(";"):
            ch += f"{name}:{self.headers[name]}\n"
        canonical = "\n".join([
            self.command,
            urllib.parse.quote(parsed.path, safe="/-_.~"),
            cq, ch, signed_headers, payload_hash])
        string_to_sign = "\n".join([
            "AWS4-HMAC-SHA256", amz_date,
            f"{date}/{region}/s3/aws4_request",
            hashlib.sha256(canonical.encode()).hexdigest()])
        expect = _sign(SECRET_KEY, date, region, string_to_sign)
        return hmac.compare_digest(expect, signature)

    def _reject(self, code, msg):
        body = msg.encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> bytes:
        n = int(self.headers.get("Content-Length", "0"))
        return self.rfile.read(n) if n else b""

    def _bucket_key(self):
        path = urllib.parse.urlsplit(self.path).path
        parts = path.lstrip("/").split("/", 1)
        bucket = parts[0]
        key = parts[1] if len(parts) > 1 else ""
        return bucket, key

    # -- handlers -----------------------------------------------------------
    def do_GET(self):
        st = self.state
        st.requests.append(("GET", self.path))
        if not self._verify_sig(b""):
            return self._reject(403, "SignatureDoesNotMatch")
        bucket, key = self._bucket_key()
        q = dict(urllib.parse.parse_qsl(
            urllib.parse.urlsplit(self.path).query, keep_blank_values=True))
        if "prefix" in q or key == "":
            return self._list(bucket, q)
        data = st.objects.get((bucket, key))
        if data is None:
            return self._reject(404, "NoSuchKey")
        rng = self.headers.get("Range")
        status = 200
        lo = 0
        headers = {}
        total = len(data)
        if rng and not st.ignore_range:
            m = re.match(r"bytes=(\d+)-(\d*)", rng)
            lo = int(m.group(1))
            hi = int(m.group(2)) + 1 if m.group(2) else total
            hi = min(hi, total)
            status = 206
            if st._tick("badcr", st.bad_content_range_every):
                lo = min(lo + 64, total)
                hi = min(hi + 64, total)
            headers["Content-Range"] = (
                f"bytes {lo}-{max(hi - 1, lo)}/{total}")
            data = data[lo:hi]
        if st._tick("stall", st.stall_every):
            return stall_connection(self, st.stall_seconds)
        if st._tick("reset", st.reset_every):
            return reset_connection(self)
        if st._tick("get500", st.get_500_every):
            return self._reject(500, "InternalError")
        if st._tick("gettrunc", st.get_truncate_every):
            return truncate_body(self, status, data)
        if st.fail_reads_after is not None and len(data) > st.fail_reads_after:
            # simulate a flaky connection: send a truncated body
            out = data[: st.fail_reads_after]
            self.send_response(status)
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(out)
            self.close_connection = True
            return
        send_with_latency(self, status, data, headers, st.latency_ms,
                          st.latency_block, st.first_byte_ms)

    def _list(self, bucket, q):
        st = self.state
        prefix = q.get("prefix", "")
        delim = q.get("delimiter", "")
        marker = q.get("marker", "")
        keys = sorted(k for (b, k) in st.objects if b == bucket
                      and k.startswith(prefix) and k > marker)
        contents, prefixes = [], []
        for k in keys:
            rest = k[len(prefix):]
            if delim and delim in rest:
                p = prefix + rest.split(delim)[0] + delim
                if p not in prefixes:
                    prefixes.append(p)
            else:
                contents.append(k)
        from xml.sax.saxutils import escape
        xml = ["<?xml version='1.0'?><ListBucketResult>",
               "<IsTruncated>false</IsTruncated>"]
        for k in contents:
            xml.append(f"<Contents><Key>{escape(k)}</Key>"
                       f"<Size>{len(st.objects[(bucket, k)])}</Size>"
                       f"</Contents>")
        for p in prefixes:
            xml.append(f"<CommonPrefixes><Prefix>{escape(p)}</Prefix>"
                       f"</CommonPrefixes>")
        xml.append("</ListBucketResult>")
        body = "".join(xml).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_PUT(self):
        st = self.state
        st.requests.append(("PUT", self.path))
        body = self._read_body()
        if not self._verify_sig(body):
            return self._reject(403, "SignatureDoesNotMatch")
        bucket, key = self._bucket_key()
        q = dict(urllib.parse.parse_qsl(
            urllib.parse.urlsplit(self.path).query, keep_blank_values=True))
        if "uploadId" in q:
            if st._tick("part", st.part_500_every):
                return self._reject(500, "InternalError")
            st.uploads[q["uploadId"]][int(q["partNumber"])] = body
            etag = hashlib.md5(body).hexdigest()
            self.send_response(200)
            self.send_header("ETag", f'"{etag}"')
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        st.objects[(bucket, key)] = body
        self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def do_POST(self):
        st = self.state
        st.requests.append(("POST", self.path))
        body = self._read_body()
        if not self._verify_sig(body):
            return self._reject(403, "SignatureDoesNotMatch")
        bucket, key = self._bucket_key()
        q = dict(urllib.parse.parse_qsl(
            urllib.parse.urlsplit(self.path).query, keep_blank_values=True))
        if "uploads" in q:
            st.next_upload[0] += 1
            uid = f"upload-{st.next_upload[0]}"
            st.uploads[uid] = {}
            xml = (f"<?xml version='1.0'?><InitiateMultipartUploadResult>"
                   f"<UploadId>{uid}</UploadId>"
                   f"</InitiateMultipartUploadResult>").encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(xml)))
            self.end_headers()
            self.wfile.write(xml)
            return
        if "uploadId" in q:
            xml = b"<?xml version='1.0'?><CompleteMultipartUploadResult/>"
            if st.complete_truncate_once:
                # truncated response mid-stream; parts stay staged so the
                # client's retried Complete succeeds
                st.complete_truncate_once = False
                self.send_response(200)
                self.send_header("Content-Length", str(len(xml)))
                self.end_headers()
                self.wfile.write(xml[: len(xml) // 2])
                self.close_connection = True
                return
            parts = st.uploads.pop(q["uploadId"])
            st.objects[(bucket, key)] = b"".join(
                parts[i] for i in sorted(parts))
            self.send_response(200)
            self.send_header("Content-Length", str(len(xml)))
            self.end_headers()
            self.wfile.write(xml)
            return
        self._reject(400, "BadRequest")


def serve(ssl_context=None, config=None):
    """Start the mock server; returns (state, port, shutdown_fn).

    With `ssl_context` (an SSLContext loaded with a cert chain) the mock
    speaks TLS — the S3-over-https lane's stand-in for real AWS.
    ``config`` (tests/mock_origin.OriginConfig) applies the shared
    shaping/fault surface; the out-of-process path is
    ``scripts/loadrig.py origin --backend s3``."""
    from tests.mock_origin import serve_backend
    return serve_backend("s3", config, ssl_context)
