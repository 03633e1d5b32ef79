"""In-process mock plain-HTTP origin: a static object server with the
shared shaping/fault surface (tests/mock_origin.py).

Serves ``state.objects`` ({absolute path: bytes}) over GET with Range
and HEAD size probes — the stand-in for any http(s):// origin the
native client reads from.  Grown out of the private ``_HttpState``/
``_HttpHandler`` pair ``test_io_resilience``/``test_io_ranged`` used to
copy; it now also carries the ``slow_every``/``slow_ms`` served-stall
knob the coordinated-omission rig tests schedule (the response is
*delayed*, not killed — only an intended-time latency capture sees the
queue it causes behind a busy client)."""

from __future__ import annotations

import re
import time

from http.server import BaseHTTPRequestHandler

from tests.mock_s3 import (FaultCounterMixin, reset_connection,
                           send_with_latency, stall_connection,
                           truncate_body)


class MockHttpState(FaultCounterMixin):
    def __init__(self):
        self.objects = {}           # absolute path -> bytes
        self.requests = []          # (method, path) log
        # fault plan (shared knob names: tests/mock_origin.py)
        self.stall_first_n = 0      # the first N GETs sleep past client
        self.stall_all = False      # every GET stalls (deadline test)
        self.stall_every = 0
        self.stall_seconds = 6.0
        self.get_500_every = 0
        self.get_truncate_every = 0
        self.reset_every = 0
        self.ignore_range = False   # answer 200 full-body (Range ignored)
        # latency/bandwidth shaping (mock_s3 parity)
        self.latency_ms = 0
        self.latency_block = 256 * 1024
        self.first_byte_ms = None   # head delay when not latency_ms
        # served stall: every Nth GET is delayed slow_ms then completes
        self.slow_every = 0
        self.slow_ms = 0
        self._init_fault_counters("get", "get500", "gettrunc", "reset",
                                  "stall", "slow")


class MockHttpHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    state: MockHttpState = None  # set by the launcher

    def log_message(self, *a):
        pass

    def do_HEAD(self):
        body = self.state.objects.get(self.path)
        self.state.requests.append(("HEAD", self.path))
        self.send_response(200 if body is not None else 404)
        self.send_header("Content-Length",
                         str(len(body)) if body is not None else "0")
        self.end_headers()

    def do_GET(self):
        st = self.state
        st.requests.append(("GET", self.path))
        with st._fault_lock:
            st._counters["get"] += 1
            n = st._counters["get"]
        if st.stall_all or n <= st.stall_first_n:
            return stall_connection(self, st.stall_seconds)
        if st._tick("stall", st.stall_every):
            return stall_connection(self, st.stall_seconds)
        if st._tick("reset", st.reset_every):
            return reset_connection(self)
        body = st.objects.get(self.path)
        if body is None:
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        status, lo = 200, 0
        headers = {}
        rng = self.headers.get("Range")
        if rng and not st.ignore_range:
            m = re.match(r"bytes=(\d+)-(\d*)", rng)
            lo = int(m.group(1))
            hi = int(m.group(2)) + 1 if m.group(2) else len(body)
            total = len(body)
            body = body[lo:min(hi, total)]
            status = 206
            headers["Content-Range"] = (
                f"bytes {lo}-{max(lo + len(body) - 1, lo)}/{total}")
        if st._tick("get500", st.get_500_every):
            self.send_response(500)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        if st._tick("gettrunc", st.get_truncate_every):
            return truncate_body(self, status, body)
        if st._tick("slow", st.slow_every):
            time.sleep(st.slow_ms / 1000.0)
        send_with_latency(self, status, body, headers, st.latency_ms,
                          st.latency_block, st.first_byte_ms)


def serve(ssl_context=None, config=None):
    """Start the mock origin; returns (state, port, shutdown_fn)."""
    from tests.mock_origin import serve_backend
    state, port, shutdown = serve_backend("http", config, ssl_context)
    return state, port, shutdown
