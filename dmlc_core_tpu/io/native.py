"""ctypes binding to the native core (cpp/ → libdmlc_core_tpu.so).

The reference is consumed as a C++ library; here the native core carries the
hot host path (streams, record-aligned InputSplit, RecordIO, multithreaded
parsers — reference L3-L5 layers) and Python/JAX ride on this binding. The
shared library is auto-built from cpp/ on first import when missing or stale.

Remote-I/O resilience (retries with decorrelated-jitter backoff, deadlines,
per-attempt socket timeouts, fault injection) is configured through the
``DMLC_IO_*`` env knobs / ``?io_*=`` URI args and observed through
:func:`io_retry_stats`; see [robustness.md](robustness.md) for the model.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Iterator, List, Optional, Tuple

import numpy as np

from dmlc_core_tpu.base import DMLCError

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "dmlc_core_tpu", "_native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdmlc_core_tpu.so")
_CPP_DIR = os.path.join(_REPO_ROOT, "cpp")

_lib = None
_lib_lock = threading.Lock()


def _bf16_dtype():
    """The bfloat16 numpy dtype (ml_dtypes ships with jax)."""
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16)


class RowBlockC(ctypes.Structure):
    """Mirror of dct_rowblock_t in cpp/src/capi.cc."""
    _fields_ = [
        ("num_rows", ctypes.c_uint64),
        ("nnz", ctypes.c_uint64),
        ("offset", ctypes.POINTER(ctypes.c_uint64)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("qid", ctypes.POINTER(ctypes.c_uint64)),
        ("field", ctypes.POINTER(ctypes.c_uint32)),
        ("index", ctypes.c_void_p),
        ("value", ctypes.POINTER(ctypes.c_float)),
        ("max_index", ctypes.c_uint64),
        ("max_field", ctypes.c_uint32),
        ("index_is_64", ctypes.c_int32),
        ("value_i32", ctypes.POINTER(ctypes.c_int32)),
        ("value_i64", ctypes.POINTER(ctypes.c_int64)),
        ("value_dtype", ctypes.c_int32),
    ]


class ParsePipelineStatsC(ctypes.Structure):
    """Mirror of dct_parse_pipeline_stats_t in cpp/src/capi.cc."""
    _fields_ = [
        ("chunks_read", ctypes.c_uint64),
        ("blocks_delivered", ctypes.c_uint64),
        ("reader_waits", ctypes.c_uint64),
        ("worker_waits", ctypes.c_uint64),
        ("consumer_waits", ctypes.c_uint64),
        ("inflight_now", ctypes.c_uint64),
        ("inflight_peak", ctypes.c_uint64),
        ("inflight_sum", ctypes.c_uint64),
        ("capacity", ctypes.c_uint64),
        ("workers", ctypes.c_uint64),
        # structural-scan lane (cpp/src/simd_scan.h SimdTier):
        # 0 scalar, 1 swar, 2 sse2, 3 avx2
        ("simd_tier", ctypes.c_uint64),
    ]


class IoRetryStatsC(ctypes.Structure):
    """Mirror of dct_io_retry_stats_t in cpp/src/capi.cc."""
    _fields_ = [
        ("requests", ctypes.c_uint64),
        ("retries", ctypes.c_uint64),
        ("backoff_ms_total", ctypes.c_uint64),
        ("timeouts", ctypes.c_uint64),
        ("faults_injected", ctypes.c_uint64),
        ("giveups", ctypes.c_uint64),
        ("deadline_exhausted", ctypes.c_uint64),
    ]


def _build_native() -> None:
    """Bring the library up to date with ``make`` — a no-op when it is
    fresh; staleness is judged by make's own dependency tracking, which
    also holds in a copied tree. A failed build shows the compiler's
    output."""
    proc = subprocess.run(["make", "-C", _CPP_DIR], capture_output=True,
                          text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise DMLCError(
            f"native build failed: `make -C {_CPP_DIR}` exited "
            f"{proc.returncode} (compiler output above)")


def lib() -> ctypes.CDLL:
    """Load (building if needed) the native library."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        _build_native()
        cdll = ctypes.CDLL(_LIB_PATH)
        _declare_signatures(cdll)
        _lib = cdll
        return _lib


def _declare_signatures(cdll: ctypes.CDLL) -> None:
    """Pin (restype, argtypes) so sizes/pointers survive the 64-bit ABI.

    Every exported ``dct_*`` function carries an EXPLICIT restype — a
    binding left to ctypes' implicit ``c_int`` default silently truncates
    any future pointer/size return to 32 bits, so the analyzer's ABI
    parity pass (``scripts/analyze.py`` Pass 4, doc/analysis.md) diffs
    this table against the ``cpp/src/capi.cc`` declarations: missing or
    legacy argtypes-only rows, arity drift, and pointer/scalar width
    mismatches all fail ``make analyze``."""
    c = ctypes
    vp, sz, i, u = c.c_void_p, c.c_size_t, c.c_int, c.c_uint
    sigs = {
        "dct_last_error": (c.c_char_p, []),
        "dct_stream_create": (i, [c.c_char_p, c.c_char_p, c.POINTER(vp)]),
        "dct_stream_read": (i, [vp, vp, sz, c.POINTER(sz)]),
        "dct_stream_write": (i, [vp, c.c_char_p, sz]),
        "dct_stream_free": (i, [vp]),
        "dct_fs_list": (i, [c.c_char_p, i, c.POINTER(c.c_char_p)]),
        "dct_fs_path_info": (i, [c.c_char_p, c.POINTER(sz), c.POINTER(i)]),
        "dct_str_free": (i, [c.c_char_p]),
        "dct_split_create": (i, [c.c_char_p, u, u, c.c_char_p, i,
                                 c.POINTER(vp)]),
        "dct_split_create_ex": (i, [c.c_char_p, c.c_char_p, u, u,
                                    c.c_char_p, i, i, i, sz, c.c_char_p,
                                    u, i, c.POINTER(vp)]),
        "dct_split_next_record": (i, [vp, c.POINTER(vp), c.POINTER(sz),
                                      c.POINTER(i)]),
        "dct_split_next_chunk": (i, [vp, c.POINTER(vp), c.POINTER(sz),
                                     c.POINTER(i)]),
        "dct_split_before_first": (i, [vp]),
        "dct_split_reset_partition": (i, [vp, u, u]),
        "dct_split_total_size": (i, [vp, c.POINTER(sz)]),
        "dct_split_hint_chunk_size": (i, [vp, sz]),
        "dct_split_free": (i, [vp]),
        "dct_recordio_writer_create": (i, [c.c_char_p, c.POINTER(vp)]),
        "dct_recordio_write": (i, [vp, c.c_char_p, sz]),
        "dct_recordio_writer_free": (i, [vp]),
        "dct_recordio_reader_create": (i, [c.c_char_p, c.POINTER(vp)]),
        "dct_recordio_read": (i, [vp, c.POINTER(vp), c.POINTER(sz),
                                  c.POINTER(i)]),
        "dct_recordio_reader_free": (i, [vp]),
        "dct_parser_create": (i, [c.c_char_p, u, u, c.c_char_p, i, i, i,
                                  c.POINTER(vp)]),
        "dct_parser_create_ex": (i, [c.c_char_p, u, u, c.c_char_p, i, i,
                                     i, i, c.c_char_p, c.c_char_p,
                                     c.POINTER(vp)]),
        "dct_parser_pipeline_stats": (i, [vp,
                                          c.POINTER(ParsePipelineStatsC),
                                          c.POINTER(i)]),
        "dct_parser_next_block": (i, [vp, c.POINTER(RowBlockC),
                                      c.POINTER(i)]),
        "dct_parser_before_first": (i, [vp]),
        "dct_parser_set_epoch": (i, [vp, u, c.POINTER(c.c_int32)]),
        "dct_parser_bytes_read": (i, [vp, c.POINTER(sz)]),
        "dct_parser_free": (i, [vp]),
        "dct_webhdfs_set_delegation_token": (i, [c.c_char_p]),
        "dct_webhdfs_set_auth_header": (i, [c.c_char_p]),
        "dct_set_tls_proxy": (i, [c.c_char_p]),
        "dct_telemetry_snapshot": (i, [c.POINTER(c.c_char_p)]),
        "dct_telemetry_reset": (i, []),
        "dct_telemetry_enable": (i, [i]),
        "dct_trace_snapshot": (i, [c.POINTER(c.c_char_p)]),
        "dct_trace_reset": (i, []),
        "dct_flight_dump": (i, [c.c_char_p, c.POINTER(i)]),
        "dct_pulse_start": (i, []),
        "dct_pulse_stop": (i, []),
        "dct_pulse_max_late_us": (i, [c.c_uint64, c.c_uint64,
                                      c.POINTER(c.c_uint64),
                                      c.POINTER(c.c_uint64)]),
        "dct_io_retry_stats": (i, [c.POINTER(IoRetryStatsC)]),
        "dct_io_stats_reset": (i, []),
        "dct_io_set_fault_plan": (i, [c.c_char_p]),
        "dct_io_set_timeout_ms": (i, [i]),
        "dct_fs_set_fault_plan": (i, [c.c_char_p]),
        "dct_parser_formats_doc": (i, [c.POINTER(c.c_char_p)]),
        "dct_parser_format_names": (i, [c.POINTER(c.c_char_p)]),
        "dct_criteo_id": (i, [c.c_uint32, c.c_char_p, c.c_uint64, i,
                              c.POINTER(c.c_uint64)]),
        "dct_batcher_create": (i, [c.c_char_p, u, u, c.c_char_p, i, i,
                                   c.c_uint64, c.c_uint32, c.c_uint64,
                                   c.POINTER(vp)]),
        "dct_nnz_bucket": (i, [c.c_uint64, c.c_uint64,
                               c.POINTER(c.c_uint64)]),
        "dct_tail_rung": (i, [c.c_uint64, c.c_uint64, c.c_uint64,
                              c.c_uint64, c.POINTER(c.c_uint64)]),
        "dct_batcher_next_meta": (i, [vp, c.POINTER(c.c_uint64),
                                      c.POINTER(c.c_uint64),
                                      c.POINTER(c.c_uint64), c.POINTER(i),
                                      c.POINTER(i), c.POINTER(i)]),
        "dct_batcher_fill_csr": (i, [vp, vp, vp, vp, vp, vp, vp, vp, vp]),
        "dct_batcher_fill_dense": (i, [vp, vp, c.c_int32, c.c_uint64, vp,
                                       vp, vp, vp]),
        "dct_batcher_fill_packed": (i, [vp, vp, c.c_int32, vp, c.c_int32,
                                        vp, c.c_int32, vp]),
        "dct_batcher_fill_dense_packed": (i, [vp, vp, c.c_int32,
                                              c.c_uint64, vp, c.c_int32,
                                              vp]),
        "dct_batcher_before_first": (i, [vp]),
        "dct_batcher_set_epoch": (i, [vp, u, c.POINTER(c.c_int32)]),
        "dct_batcher_bytes_read": (i, [vp, c.POINTER(sz)]),
        "dct_batcher_batch_nnz": (i, [vp, c.POINTER(c.c_uint64)]),
        "dct_batcher_cols_meta": (i, [vp, c.POINTER(c.c_uint64),
                                      c.POINTER(c.c_uint64), c.POINTER(i)]),
        "dct_batcher_fill_cols": (i, [vp, vp, c.c_uint64]),
        "dct_batcher_set_col_owners": (i, [vp, c.c_uint32, c.c_uint64]),
        "dct_batcher_cols_owner_max": (i, [vp, c.POINTER(c.c_uint64)]),
        "dct_col_slots": (i, [vp, vp, c.c_uint32, c.c_uint64,
                                    c.c_uint64, c.c_uint32, c.c_uint64, vp,
                                    c.POINTER(c.c_uint64),
                                    c.POINTER(c.c_uint64)]),
        "dct_batcher_free": (i, [vp]),
        "dct_denserec_create": (i, [c.c_char_p, u, u, c.c_uint64,
                                    c.c_uint32, c.POINTER(vp)]),
        "dct_denserec_meta": (i, [vp, c.POINTER(c.c_uint64),
                                  c.POINTER(c.c_int32),
                                  c.POINTER(c.c_int32)]),
        "dct_denserec_fill": (i, [vp, vp, c.c_int32, c.c_uint64, vp, vp,
                                  vp, c.POINTER(c.c_uint64)]),
        "dct_denserec_fill_packed": (i, [vp, vp, c.c_int32, c.c_uint64, vp,
                                         c.c_int32, vp,
                                         c.POINTER(c.c_uint64)]),
        "dct_denserec_before_first": (i, [vp]),
        "dct_denserec_set_epoch": (i, [vp, u, c.POINTER(c.c_int32)]),
        "dct_denserec_bytes_read": (i, [vp, c.POINTER(sz)]),
        "dct_denserec_free": (i, [vp]),
        "dct_csrrec_create": (i, [c.c_char_p, u, u, c.c_uint64, c.c_uint32,
                                  c.c_uint64, c.POINTER(vp)]),
        "dct_csrrec_meta": (i, [vp, c.POINTER(c.c_uint64),
                                c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                                c.POINTER(c.c_int32)]),
        "dct_csrrec_fill": (i, [vp, vp, vp, vp, vp, vp, vp, vp, vp,
                                c.POINTER(c.c_uint64)]),
        "dct_csrrec_fill_packed": (i, [vp, vp, c.c_int32, vp, c.c_int32,
                                       vp, c.POINTER(c.c_uint64)]),
        "dct_csrrec_before_first": (i, [vp]),
        "dct_csrrec_set_epoch": (i, [vp, u, c.POINTER(c.c_int32)]),
        "dct_csrrec_bytes_read": (i, [vp, c.POINTER(sz)]),
        "dct_csrrec_batch_nnz": (i, [vp, c.POINTER(c.c_uint64)]),
        "dct_csrrec_cols_meta": (i, [vp, c.POINTER(c.c_uint64),
                                     c.POINTER(c.c_uint64), c.POINTER(i)]),
        "dct_csrrec_fill_cols": (i, [vp, vp, c.c_uint64]),
        "dct_csrrec_set_col_owners": (i, [vp, c.c_uint32, c.c_uint64]),
        "dct_csrrec_cols_owner_max": (i, [vp, c.POINTER(c.c_uint64)]),
        "dct_csrrec_free": (i, [vp]),
        "dct_bf16_convert": (i, [vp, vp, c.c_uint64]),
        "dct_bf16_upcast": (i, [vp, vp, c.c_uint64]),
    }
    for name, (restype, argtypes) in sigs.items():
        fn = getattr(cdll, name)
        fn.argtypes = argtypes
        fn.restype = restype


def _check(status: int) -> None:
    if status != 0:
        raise DMLCError(lib().dct_last_error().decode("utf-8", "replace"))


def _uri_needs_tls(uri: str) -> bool:
    """Whether any member of this (possibly ';'-separated) URI reaches an
    https origin under the native clients' env rules: https:// directly;
    s3:// and azure:// whenever their endpoint env is https or UNSET (the
    no-endpoint default is the real TLS-only cloud service,
    cpp/src/{s3,azure}_filesys.cc ResolveTarget); hdfs:// under an https
    WEBHDFS_NAMENODE (secure WebHDFS).

    Matching is per-';'-member startswith on the scheme — a local path
    whose query string merely EMBEDS "https://" (e.g.
    ``/data/f.libsvm?note=https://origin``) must not spawn the TLS helper
    singleton."""

    def env(*names: str) -> str:
        for n in names:
            v = os.environ.get(n)
            if v:
                return v
        return ""

    for member in uri.split(";"):
        member = member.strip()
        if member.startswith("https://"):
            return True
        if member.startswith("s3://"):
            ep = env("S3_ENDPOINT", "AWS_ENDPOINT")
            if not ep or ep.startswith("https://"):
                return True
        elif member.startswith("azure://"):
            ep = env("AZURE_ENDPOINT")
            if not ep or ep.startswith("https://"):
                return True
        elif member.startswith(("hdfs://", "viewfs://")):
            if env("WEBHDFS_NAMENODE").startswith("https://"):
                return True
    return False


def _route_https(uri: str) -> str:
    """Make https-origin URIs reachable before handing them to the native
    lib.

    The native client is plain-HTTP; https origins route through the local
    TLS-terminating helper (io/tls_proxy.py). When the operator configured
    none (DCT_TLS_PROXY unset), start the in-process singleton and publish
    its address to the native router through the explicit C-ABI setter
    (dct_set_tls_proxy) — NEVER by mutating os.environ: other native
    handles may already be running request threads whose per-request
    getenv (endpoint/credential env reads) a setenv would race (glibc
    setenv/getenv are mutually unsafe). When the operator DID configure a
    helper (env set before launch) or opted out (DCT_TLS_AUTO=0), any
    earlier auto-start override is cleared so the env — or the native
    guidance error — stays authoritative. Returns the uri unchanged
    (routing is by the published address)."""
    if not _uri_needs_tls(uri):
        return uri
    if (os.environ.get("DCT_TLS_PROXY")
            or os.environ.get("DCT_TLS_AUTO") == "0"):
        _check(lib().dct_set_tls_proxy(b""))
        return uri
    from dmlc_core_tpu.io.tls_proxy import ensure_tls_proxy
    addr = ensure_tls_proxy(export_env=False)
    _check(lib().dct_set_tls_proxy(addr.encode()))
    return uri


# -- streams ----------------------------------------------------------------
class NativeStream:
    """URI-dispatched byte stream (reference Stream::Create, io.h:57)."""

    def __init__(self, uri: str, mode: str = "r"):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        _check(lib().dct_stream_create(uri.encode(), mode.encode(),
                                       ctypes.byref(self._h)))

    def read(self, size: int = 1 << 20) -> bytes:
        """Read up to `size` bytes (empty bytes at end of stream)."""
        buf = ctypes.create_string_buffer(size)
        nread = ctypes.c_size_t()
        _check(lib().dct_stream_read(self._h, buf, size, ctypes.byref(nread)))
        return buf.raw[: nread.value]

    def read_all(self) -> bytes:
        """Read the remainder of the stream into one bytes object."""
        chunks = []
        while True:
            c = self.read()
            if not c:
                break
            chunks.append(c)
        return b"".join(chunks)

    def write(self, data: bytes) -> None:
        """Write all of `data` to the stream."""
        _check(lib().dct_stream_write(self._h, data, len(data)))

    def close(self) -> None:
        """Finish and free the native stream (idempotent; raises if the final
        flush fails)."""
        if self._h:
            # the handle is freed even when Finish fails; drop it before
            # raising so a later close/__del__ cannot double-free
            h, self._h = self._h, ctypes.c_void_p()
            _check(lib().dct_stream_free(h))

    def __enter__(self) -> "NativeStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- filesystem -------------------------------------------------------------
def list_directory(uri: str, recursive: bool = False
                   ) -> List[Tuple[str, int, str]]:
    """List (path, size, 'f'|'d') entries (reference FileSystem, io.h:591)."""
    uri = _route_https(uri)
    out = ctypes.c_char_p()
    _check(lib().dct_fs_list(uri.encode(), 1 if recursive else 0,
                             ctypes.byref(out)))
    try:
        text = ctypes.string_at(out).decode()
    finally:
        lib().dct_str_free(out)
    entries = []
    for line in text.splitlines():
        path, size, ftype = line.rsplit("\t", 2)
        entries.append((path, int(size), ftype))
    return entries


def path_info(uri: str) -> Tuple[int, bool]:
    """Return (size, is_dir)."""
    uri = _route_https(uri)
    size = ctypes.c_size_t()
    is_dir = ctypes.c_int()
    _check(lib().dct_fs_path_info(uri.encode(), ctypes.byref(size),
                                  ctypes.byref(is_dir)))
    return size.value, bool(is_dir.value)


def parser_formats_doc() -> str:
    """Markdown documentation of every registered native data format and
    its reflection parameters (the doc lane's source of truth; reference
    doc/parameter.md covers the same surface)."""
    out = ctypes.c_char_p()
    _check(lib().dct_parser_formats_doc(ctypes.byref(out)))
    try:
        return ctypes.string_at(out).decode()
    finally:
        lib().dct_str_free(out)


def parser_format_names() -> tuple:
    """The formats of the native parser registry (cpp/src/parser.cc
    RegisterBuiltinParsers), in its order: where a format is registered."""
    out = ctypes.c_char_p()
    _check(lib().dct_parser_format_names(ctypes.byref(out)))
    try:
        return tuple(ctypes.string_at(out).decode().split(","))
    finally:
        lib().dct_str_free(out)


def native_criteo_id(column: int, cell: bytes, hash_bits: int) -> int:
    """The native statement of the ``criteo`` format's rule
    (cpp/src/criteo_hash.h; the numpy one is dmlc_core_tpu.data.criteo):
    the feature id of ``cell`` in feature column ``column`` (0..38)."""
    out = ctypes.c_uint64()
    _check(lib().dct_criteo_id(column, cell, len(cell), hash_bits,
                               ctypes.byref(out)))
    return out.value


# -- telemetry ---------------------------------------------------------------
def native_telemetry_snapshot() -> dict:
    """The native registry's versioned snapshot document
    (``dct_telemetry_snapshot``, cpp/src/telemetry.h): ``{"version",
    "enabled", "counters": [{"name", "labels", "value"}], "gauges": [...],
    "histograms": [{"name", "labels", "count", "sum", "buckets"}]}``.
    Prefer :func:`dmlc_core_tpu.telemetry.snapshot`, which merges this
    with the Python-side registry; metric catalog in
    [observability.md](observability.md)."""
    import json
    out = ctypes.c_char_p()
    _check(lib().dct_telemetry_snapshot(ctypes.byref(out)))
    try:
        return json.loads(ctypes.string_at(out).decode())
    finally:
        lib().dct_str_free(out)


def native_telemetry_reset() -> None:
    """Zero every metric in the native registry (owned and adopted IoStats
    counters alike; ``dct_telemetry_reset``)."""
    _check(lib().dct_telemetry_reset())


def native_telemetry_enable(on: bool) -> None:
    """Gate the native side's timed-span instrumentation at runtime
    (``dct_telemetry_enable``; overrides DMLC_TELEMETRY). Counters keep
    counting either way."""
    _check(lib().dct_telemetry_enable(1 if on else 0))


def native_trace_snapshot() -> dict:
    """The native span-ring trace document (``dct_trace_snapshot``,
    cpp/src/telemetry.h): ``{"version", "pid", "anchor": {"wall_us",
    "steady_us"}, "emitted", "dropped", "spans": [{"name", "id",
    "parent", "tid", "ts", "dur", "arg"}]}`` — steady-clock timestamps,
    mergeable onto the wall clock via the anchor pair. Prefer
    :func:`dmlc_core_tpu.telemetry.trace_snapshot`, which merges both
    halves ([observability.md](observability.md) "Distributed
    tracing")."""
    import json
    out = ctypes.c_char_p()
    _check(lib().dct_trace_snapshot(ctypes.byref(out)))
    try:
        return json.loads(ctypes.string_at(out).decode())
    finally:
        lib().dct_str_free(out)


def native_trace_reset() -> None:
    """Drop every buffered native span and restart the trace sequence
    (``dct_trace_reset``; also implied by ``dct_telemetry_reset``)."""
    _check(lib().dct_trace_reset())


def native_flight_dump(reason: str) -> bool:
    """Best-effort native flight-recorder dump (``dct_flight_dump``):
    writes the native span ring + metric snapshot to the
    ``DMLC_TRACE_DUMP`` directory. Returns True only when a dump file
    actually landed (False when the env knob is unset or the write
    failed)."""
    written = ctypes.c_int(0)
    _check(lib().dct_flight_dump(reason.encode(), ctypes.byref(written)))
    return written.value != 0


# -- remote-I/O resilience ---------------------------------------------------
# legacy io_retry_stats() key -> canonical telemetry counter name
_LEGACY_IO_STAT_NAMES = (
    ("requests", "io_requests_total"),
    ("retries", "io_retries_total"),
    ("backoff_ms_total", "io_backoff_ms_total"),
    ("timeouts", "io_timeouts_total"),
    ("faults_injected", "io_faults_injected_total"),
    ("giveups", "io_giveups_total"),
    ("deadline_exhausted", "io_deadline_exhausted_total"),
)


def io_retry_stats() -> dict:
    """Process-global remote-I/O resilience counters (cpp/src/retry.h
    IoStats, shared by every s3/azure/hdfs/http request): ``requests``
    (HTTP requests sent), ``retries`` (backoff sleeps taken),
    ``backoff_ms_total``, ``timeouts`` (per-attempt socket timeout
    expiries), ``faults_injected`` (fault-plan firings), ``giveups``
    (retry loops that exhausted their budget) and ``deadline_exhausted``
    (the subset of giveups caused by the per-operation deadline). See
    [robustness.md](robustness.md) for the retry model.

    Deprecation shim (one release of back-compat): since the telemetry
    layer these counters live in the unified registry under ``io_*_total``
    names and this dict is a THIN VIEW over the native snapshot — same
    storage, legacy key spelling. New code should read
    ``dmlc_core_tpu.telemetry.snapshot()`` /
    [observability.md](observability.md) instead."""
    counters = {c["name"]: c["value"]
                for c in native_telemetry_snapshot().get("counters", [])}
    return {legacy: int(counters.get(name, 0))
            for legacy, name in _LEGACY_IO_STAT_NAMES}


def reset_io_retry_stats() -> None:
    """Zero the global io_retry_stats() counters (test isolation / epoch
    accounting)."""
    _check(lib().dct_io_stats_reset())


def set_io_fault_plan(plan: str) -> None:
    """Install a deterministic fault-injection plan inside the native HTTP
    client — BELOW every mock server and every backend, so chaos tests
    exercise the real retry machinery. Grammar (cpp/src/retry.h), rules
    ';'-separated::

        reset:every=3;stall:every=5,ms=80;5xx:every=7,status=503

    kinds: ``reset`` (transport drop), ``stall`` (sleep ``ms`` then time
    out), ``5xx`` (HTTP ``status``); ``every=N`` fires on every Nth
    request, ``p=0.1`` fires with seeded probability (DMLC_IO_FAULT_SEED).
    Empty string clears. Raises on bad grammar. Prefer this setter over
    mutating DMLC_IO_FAULT_PLAN after native threads exist (same race rule
    as the TLS-proxy override)."""
    _check(lib().dct_io_set_fault_plan(plan.encode()))


def set_fs_fault_plan(plan: str) -> None:
    """Install a deterministic LOCAL-filesystem fault plan inside the
    native syscall wrappers (cpp/src/fs_fault.h) — below every mock, so
    the durability chaos suites exercise the real quarantine/degradation
    machinery. Grammar, rules ';'-separated::

        write:fault=enospc,every=3;rename:fault=torn_rename,p=0.5

    ops: ``open``, ``read``, ``write``, ``fsync``, ``rename``, ``mmap``;
    faults: ``eio``, ``enospc``, ``short_write`` (half the bytes really
    land, then ENOSPC), ``fsync_fail``, ``torn_rename`` (destination gets
    a truncated half-copy, source is gone, call fails); selectors
    ``every=N`` or seeded ``p=`` (DMLC_FS_FAULT_SEED). Empty string
    clears; an explicit clear beats DMLC_FS_FAULT_PLAN. Raises on bad
    grammar or an impossible op/fault combination. The PYTHON-side file
    ops (checkpoint, tracker event log) share this grammar via
    :mod:`dmlc_core_tpu.utils.fs_fault`; this setter drives the native
    half only."""
    _check(lib().dct_fs_set_fault_plan(plan.encode()))


def set_io_timeout_ms(ms: int) -> None:
    """Override the per-attempt socket timeout (connect/recv/send bound in
    milliseconds) for all native remote I/O; ``ms <= 0`` reverts to
    DMLC_IO_TIMEOUT_MS / the 60 s default. Per-open ``?io_timeout_ms=``
    URI args override this for one stream."""
    _check(lib().dct_io_set_timeout_ms(ms))


def set_webhdfs_delegation_token(token: str) -> None:
    """Rotate the hdfs:// delegation token at runtime: subsequent WebHDFS
    ops carry `delegation=<token>` (and omit user.name) — the secure-HDFS
    auth path; empty string reverts to user.name auth. Initial value comes
    from WEBHDFS_DELEGATION_TOKEN (cpp/src/hdfs_filesys.cc FromEnv)."""
    _check(lib().dct_webhdfs_set_delegation_token(token.encode()))


def set_webhdfs_auth_header(header: str) -> None:
    """Inject/rotate a verbatim Authorization header for hdfs:// ops — the
    SPNEGO/Kerberos hook: an external kinit-based helper (or a Knox
    gateway credential) supplies e.g. "Negotiate <b64-gss-token>", which
    rides on every WebHDFS request (user.name is then omitted; the server
    derives identity from the credential). Empty string reverts to
    user.name / delegation auth. Initial value comes from
    WEBHDFS_AUTH_HEADER. The GSSAPI negotiation loop itself is out of
    scope by design (PARITY.md)."""
    _check(lib().dct_webhdfs_set_auth_header(header.encode()))


# -- input split ------------------------------------------------------------
class NativeInputSplit:
    """Record-aligned partitioned reader (reference InputSplit, io.h:155-302).

    Each (part_index, num_parts) instance yields a disjoint, exactly-covering
    set of records — the data-parallel sharding contract consumed by
    per-process loaders (SURVEY §2.5 DP)."""

    def __init__(self, uri: str, part: int = 0, nsplit: int = 1,
                 split_type: str = "text", threaded: bool = True,
                 index_uri: str = "", shuffle: bool = False, seed: int = 0,
                 batch_size: int = 256, cache_file: str = "",
                 shuffle_parts: int = 0, recurse: bool = False):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        if (index_uri or shuffle or cache_file or shuffle_parts or recurse
                or split_type == "indexed_recordio"):
            _check(lib().dct_split_create_ex(
                uri.encode(), index_uri.encode(), part, nsplit,
                split_type.encode(), 1 if threaded else 0,
                1 if shuffle else 0, seed, batch_size, cache_file.encode(),
                shuffle_parts, 1 if recurse else 0, ctypes.byref(self._h)))
        else:
            _check(lib().dct_split_create(uri.encode(), part, nsplit,
                                          split_type.encode(),
                                          1 if threaded else 0,
                                          ctypes.byref(self._h)))

    def next_record(self) -> Optional[bytes]:
        """Next whole record, or None at end (reference
        InputSplit::NextRecord)."""
        data = ctypes.c_void_p()
        size = ctypes.c_size_t()
        has = ctypes.c_int()
        _check(lib().dct_split_next_record(self._h, ctypes.byref(data),
                                           ctypes.byref(size),
                                           ctypes.byref(has)))
        if not has.value:
            return None
        if size.value == 0:
            return b""
        return ctypes.string_at(data, size.value)

    def next_chunk(self) -> Optional[bytes]:
        """Next record-aligned chunk of raw bytes, or None at end (reference
        InputSplit::NextChunk)."""
        data = ctypes.c_void_p()
        size = ctypes.c_size_t()
        has = ctypes.c_int()
        _check(lib().dct_split_next_chunk(self._h, ctypes.byref(data),
                                          ctypes.byref(size),
                                          ctypes.byref(has)))
        if not has.value:
            return None
        return ctypes.string_at(data, size.value)

    def __iter__(self) -> Iterator[bytes]:
        while True:
            rec = self.next_record()
            if rec is None:
                return
            yield rec

    def before_first(self) -> None:
        """Restart this partition from its first record."""
        _check(lib().dct_split_before_first(self._h))

    def reset_partition(self, part: int, nsplit: int) -> None:
        """Re-point this split at a different (part, nsplit) without reopening
        (reference ResetPartition)."""
        _check(lib().dct_split_reset_partition(self._h, part, nsplit))

    def total_size(self) -> int:
        """Total byte size of the underlying source across all partitions."""
        out = ctypes.c_size_t()
        _check(lib().dct_split_total_size(self._h, ctypes.byref(out)))
        return out.value

    def hint_chunk_size(self, nbytes: int) -> None:
        """Suggest the chunk granularity for next_chunk (reference
        InputSplit::HintChunkSize)."""
        _check(lib().dct_split_hint_chunk_size(self._h, nbytes))

    def close(self) -> None:
        """Free the native split handle (idempotent)."""
        if self._h:
            _check(lib().dct_split_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class LeasedSplit:
    """Elastic InputSplit (doc/robustness.md "Elastic data-plane"): yields
    the records of tracker-granted shard leases instead of one static
    ``(part_index, num_parts)`` fixed at open time.

    One NativeInputSplit is opened over the source and re-pointed per
    granted shard via ``reset_partition(shard, num_shards)`` — the
    reference InputSplit contract, with the partition decided by the lease
    plane at run time. ``leases`` is a ``tracker.client.HeartbeatMonitor``
    (distributed) or ``data.LocalLeases`` (single-host); each shard is
    checked out (complete) only after its records are fully drained, so a
    worker dying mid-shard leaves it for another worker."""

    def __init__(self, uri: str, leases, num_shards: int,
                 split_type: str = "text", epoch: int = 0,
                 acquire_timeout: Optional[float] = None, **split_kwargs):
        if num_shards <= 0:
            raise DMLCError("LeasedSplit needs num_shards > 0")
        self._split = NativeInputSplit(uri, 0, num_shards, split_type,
                                       **split_kwargs)
        self._leases = leases
        self.num_shards = num_shards
        self.epoch = epoch
        self._acquire_timeout = acquire_timeout
        self.consumed: list = []

    def __iter__(self) -> Iterator[bytes]:
        """Records of every shard this worker wins, shard by shard."""
        while True:
            shard = self._leases.acquire_lease(self.epoch,
                                               self._acquire_timeout)
            if shard is None:
                return
            self._split.reset_partition(shard, self.num_shards)
            while True:
                rec = self._split.next_record()
                if rec is None:
                    break
                yield rec
            self._leases.complete_lease(self.epoch, shard)
            self.consumed.append(shard)

    def set_epoch(self, epoch: int) -> None:
        """Advance to a new epoch's lease pool."""
        self.epoch = epoch
        self.consumed = []

    def close(self) -> None:
        """Free the underlying native split handle (idempotent)."""
        self._split.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- recordio ---------------------------------------------------------------
class NativeRecordIOWriter:
    """reference RecordIOWriter (recordio.h:38); format spec in recordio.h."""

    def __init__(self, uri: str):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        _check(lib().dct_recordio_writer_create(uri.encode(),
                                                ctypes.byref(self._h)))

    def write_record(self, data: bytes) -> None:
        """Append one record (< 2^29 bytes; embedded aligned magics are
        escaped)."""
        _check(lib().dct_recordio_write(self._h, data, len(data)))

    def close(self) -> None:
        """Flush and free the native writer handle (idempotent)."""
        if self._h:
            _check(lib().dct_recordio_writer_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativeRecordIOReader:
    """reference RecordIOReader (recordio.h:119)."""

    def __init__(self, uri: str):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        _check(lib().dct_recordio_reader_create(uri.encode(),
                                                ctypes.byref(self._h)))

    def next_record(self) -> Optional[bytes]:
        """Next record payload, or None at end of stream."""
        data = ctypes.c_void_p()
        size = ctypes.c_size_t()
        has = ctypes.c_int()
        _check(lib().dct_recordio_read(self._h, ctypes.byref(data),
                                       ctypes.byref(size), ctypes.byref(has)))
        if not has.value:
            return None
        if size.value == 0:
            return b""
        return ctypes.string_at(data, size.value)

    def __iter__(self) -> Iterator[bytes]:
        while True:
            rec = self.next_record()
            if rec is None:
                return
            yield rec

    def close(self) -> None:
        """Free the native reader handle (idempotent)."""
        if self._h:
            _check(lib().dct_recordio_reader_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- parser -----------------------------------------------------------------
class RowBlock:
    """A parsed CSR batch view (reference RowBlock, data.h:174-236).

    Arrays are zero-copy views into native memory valid until the next
    next_block() call on the producing parser; callers that need to keep a
    block (e.g. to pad onto device asynchronously) should .copy() —
    DeviceRowBlockIter does this as part of its padding step.
    """

    __slots__ = ("offset", "label", "weight", "qid", "field", "index",
                 "value", "max_index", "max_field")

    def __init__(self, c: RowBlockC):
        n = c.num_rows
        nnz = c.nnz
        self.offset = np.ctypeslib.as_array(c.offset, (n + 1,))
        self.label = np.ctypeslib.as_array(c.label, (n,))
        self.weight = (np.ctypeslib.as_array(c.weight, (n,))
                       if c.weight else None)
        self.qid = np.ctypeslib.as_array(c.qid, (n,)) if c.qid else None
        self.field = (np.ctypeslib.as_array(c.field, (nnz,))
                      if (c.field and nnz) else None)
        idx_dtype = np.uint64 if c.index_is_64 else np.uint32
        if nnz == 0:  # empty vectors have NULL data()
            self.index = np.empty(0, dtype=idx_dtype)
        else:
            idx_type = ctypes.c_uint64 if c.index_is_64 else ctypes.c_uint32
            self.index = np.ctypeslib.as_array(
                ctypes.cast(c.index, ctypes.POINTER(idx_type)), (nnz,))
        # typed csv values: value_dtype 0=float32, 1=int32, 2=int64
        # (reference csv_parser.h DType); exactly one array is populated
        if c.value_dtype == 1:
            vptr, vnnz = c.value_i32, nnz
        elif c.value_dtype == 2:
            vptr, vnnz = c.value_i64, nnz
        else:
            vptr, vnnz = c.value, nnz
        self.value = (np.ctypeslib.as_array(vptr, (vnnz,))
                      if (vptr and vnnz) else None)
        self.max_index = c.max_index
        self.max_field = c.max_field

    @property
    def num_rows(self) -> int:
        return len(self.label)

    @property
    def nnz(self) -> int:
        return len(self.index)


class NativeParser:
    """Multithreaded text parser producing RowBlock batches.

    reference Parser<I,D>::Create (data.h:307), pipelined like its
    ThreadedParser (src/data/parser.h:70-126) but multi-chunk: with
    ``threaded=True`` a native reader keeps up to ``chunks_in_flight``
    chunks outstanding while a pool of ``nthread`` workers claims
    (chunk, slice) work items and an ordered reassembly stage delivers
    blocks in input order (cpp/src/parser.h PipelinedParser) — output is
    byte-identical to ``nthread=1``. ``pipeline_stats()`` exposes the
    per-stage occupancy counters.
    """

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 fmt: str = "auto", nthread: int = 0, threaded: bool = True,
                 index64: bool = False, chunks_in_flight: int = 0,
                 cache_dir: str = "", cache: str = ""):
        # shard-cache knobs (doc/caching.md): cache_dir names the shard
        # directory (also reachable via `#cachefile=<dir>` URI sugar /
        # DMLC_DATA_CACHE_DIR), cache is never|auto|refresh (also
        # `?cache=` / DMLC_DATA_CACHE). Validated natively via the
        # checked-parse rule; the Python check here just fails earlier
        # with the same vocabulary.
        if cache not in ("", "never", "auto", "refresh"):
            raise DMLCError(
                f"cache must be one of never|auto|refresh, got {cache!r}")
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        _check(lib().dct_parser_create_ex(
            uri.encode(), part, npart, fmt.encode(), nthread,
            1 if threaded else 0, 1 if index64 else 0, chunks_in_flight,
            cache_dir.encode(), cache.encode(), ctypes.byref(self._h)))

    def next_block(self) -> Optional[RowBlock]:
        """Next parsed RowBlock view, or None at end of data; the view stays
        valid until the following call."""
        c = RowBlockC()
        has = ctypes.c_int()
        _check(lib().dct_parser_next_block(self._h, ctypes.byref(c),
                                           ctypes.byref(has)))
        if not has.value:
            return None
        return RowBlock(c)

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            b = self.next_block()
            if b is None:
                return
            yield b

    def before_first(self) -> None:
        """Restart parsing from the first row (new epoch)."""
        _check(lib().dct_parser_before_first(self._h))

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next before_first() samples
        (mid-epoch resume across restarts). Returns False when nothing in
        the split chain shuffles — ordering is then epoch-independent."""
        supported = ctypes.c_int32()
        _check(lib().dct_parser_set_epoch(self._h, epoch,
                                          ctypes.byref(supported)))
        return bool(supported.value)

    def bytes_read(self) -> int:
        """Bytes consumed from the underlying source so far (reference
        Parser::BytesRead)."""
        out = ctypes.c_size_t()
        _check(lib().dct_parser_bytes_read(self._h, ctypes.byref(out)))
        return out.value

    def pipeline_stats(self) -> Optional[dict]:
        """Occupancy/stall counters of the multi-chunk parse pipeline
        (cpp/src/parser.h ParsePipelineStats), or None for threaded=False
        parsers. ``occupancy_avg`` is the mean chunks-in-flight sampled at
        each admit; high ``reader_waits`` means the consumer binds, high
        ``consumer_waits`` means parsing binds.

        Back-compat note: this per-HANDLE struct stays, but the same
        counters aggregate process-wide in the unified telemetry registry
        (``parse_*_total``) alongside per-stage latency histograms
        (``parse_stage_*_us``) — see
        [observability.md](observability.md) and
        ``dmlc_core_tpu.telemetry.snapshot()``."""
        s = ParsePipelineStatsC()
        has = ctypes.c_int()
        _check(lib().dct_parser_pipeline_stats(self._h, ctypes.byref(s),
                                               ctypes.byref(has)))
        if not has.value:
            return None
        out = {name: int(getattr(s, name)) for name, _ in s._fields_}
        out["occupancy_avg"] = (round(s.inflight_sum / s.chunks_read, 3)
                                if s.chunks_read else 0.0)
        # structural-scan lane by name (doc/parsing.md): which decode tier
        # the text parsers run — scalar / swar / sse2 / avx2
        out["simd_lane"] = {0: "scalar", 1: "swar", 2: "sse2",
                            3: "avx2"}.get(int(s.simd_tier), "scalar")
        return out

    def io_stats(self) -> dict:
        """Remote-I/O resilience counters (module-level io_retry_stats —
        the counters are process-global across all native streams; local
        files never touch them)."""
        return io_retry_stats()

    def close(self) -> None:
        """Free the native parser handle (idempotent)."""
        if self._h:
            _check(lib().dct_parser_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- batcher ----------------------------------------------------------------
def native_nnz_bucket(n: int, floor: int) -> int:
    """The native statement of the nnz-capacity rule (cpp/src/nnz_bucket.h;
    the Python one is dmlc_core_tpu.tpu.device_iter.nnz_bucket)."""
    out = ctypes.c_uint64()
    _check(lib().dct_nnz_bucket(n, floor, ctypes.byref(out)))
    return out.value


def native_tail_rung(own: int, before: int, take: int,
                     batch_rows: int) -> int:
    """The native statement of the rule for a part's short last batch
    (cpp/src/nnz_bucket.h TailRung; the Python one is
    dmlc_core_tpu.tpu.device_iter.tail_rung)."""
    out = ctypes.c_uint64()
    _check(lib().dct_tail_rung(own, before, take, batch_rows,
                               ctypes.byref(out)))
    return out.value


def native_col_slots(col: np.ndarray, n, floor: int, owners: int = 1,
                     owner_rows: int = 0):
    """The native statement of the dedupe (cpp/src/col_slots.h; the Python
    one is dmlc_core_tpu.tpu.device_iter.col_slots): ``col`` [D, NNZ] int32
    with ``n[d]`` real entries in shard d becomes the slot plane in place;
    returns (cols [D, U], distinct count). With several ``owners`` of
    ``owner_rows`` ids each the lists are owner-major, ``U`` all the
    stretches together."""
    D, stride = col.shape
    n = np.ascontiguousarray(n, np.uint64)
    worst = owners * native_nnz_bucket(int(n.max(initial=0)), floor)
    cols = np.empty(D * worst, np.int32)
    cap = ctypes.c_uint64()
    distinct = ctypes.c_uint64()
    _check(lib().dct_col_slots(
        NativeBatcher._ptr(col, np.int32, D * stride),
        ctypes.c_void_p(n.ctypes.data), D, stride, floor, owners, owner_rows,
        ctypes.c_void_p(cols.ctypes.data), ctypes.byref(cap),
        ctypes.byref(distinct)))
    return cols[:D * cap.value].reshape(D, cap.value), distinct.value


# the two native batchers' distinct-column lists (cpp/src/col_slots.h):
# one pair of calls behind both classes' cols_meta() / fill_cols()
def _cols_meta(fn, handle):
    cap = ctypes.c_uint64()
    distinct = ctypes.c_uint64()
    lifted = ctypes.c_int()
    _check(fn(handle, ctypes.byref(cap), ctypes.byref(distinct),
              ctypes.byref(lifted)))
    return cap.value, distinct.value, bool(lifted.value)


def _fill_cols(fn, handle, cols: np.ndarray, num_shards: int) -> None:
    U = cols.shape[1]
    _check(fn(handle, NativeBatcher._ptr(cols, np.int32, num_shards * U), U))


def _cols_owner_max(fn, handle) -> int:
    out = ctypes.c_uint64()
    _check(fn(handle, ctypes.byref(out)))
    return out.value


class NativeBatcher:
    """Static-shape padded-batch assembly in C++ (cpp/src/batcher.h).

    Two-phase protocol: next_meta() stages a batch and returns its shape
    (take, nnz bucket, running max feature index); the caller allocates numpy
    arrays of exactly that shape and fill_csr()/fill_dense() writes them in
    one native pass — ctypes drops the GIL, so a staging thread's fill
    overlaps consumer-side work even though no numpy ops run here."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 fmt: str = "auto", nthread: int = 0, threaded: bool = True,
                 batch_rows: int = 65536, num_shards: int = 1,
                 min_nnz_bucket: int = 4096):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        self._batch_rows = batch_rows
        self._num_shards = num_shards
        self._bucket = 0  # staged by next_meta; sizes the fill buffers
        _check(lib().dct_batcher_create(
            uri.encode(), part, npart, fmt.encode(), nthread,
            1 if threaded else 0, batch_rows, num_shards, min_nnz_bucket,
            ctypes.byref(self._h)))

    def next_meta(self):
        """(take, bucket, max_index, has_qid, has_field) for the staged
        batch, or None at end."""
        take = ctypes.c_uint64()
        bucket = ctypes.c_uint64()
        max_index = ctypes.c_uint64()
        has_qid = ctypes.c_int()
        has_field = ctypes.c_int()
        has = ctypes.c_int()
        _check(lib().dct_batcher_next_meta(
            self._h, ctypes.byref(take), ctypes.byref(bucket),
            ctypes.byref(max_index), ctypes.byref(has_qid),
            ctypes.byref(has_field), ctypes.byref(has)))
        if not has.value:
            return None
        self._bucket = bucket.value
        return (take.value, bucket.value, max_index.value,
                bool(has_qid.value), bool(has_field.value))

    @staticmethod
    def _ptr(arr: np.ndarray, dtype, size: int) -> ctypes.c_void_p:
        # hard checks (not assert): the native side bulk-writes through this
        # pointer, so a wrong dtype/layout/size would corrupt memory
        if (arr.dtype != dtype or not arr.flags["C_CONTIGUOUS"]
                or arr.size != size):
            raise DMLCError(
                f"fill buffer must be C-contiguous {np.dtype(dtype).name} "
                f"of {size} elements, got {arr.dtype.name} size={arr.size} "
                f"contiguous={arr.flags['C_CONTIGUOUS']}")
        return ctypes.c_void_p(arr.ctypes.data)

    def fill_csr(self, row: np.ndarray, col: np.ndarray, val: np.ndarray,
                 label: np.ndarray, weight: np.ndarray, nrows: np.ndarray,
                 qid: Optional[np.ndarray] = None,
                 field: Optional[np.ndarray] = None) -> None:
        """Write the staged batch into caller CSR buffers ([D, bucket] planes;
        see batcher.h FillCSR) with the GIL released."""
        nz = self._num_shards * self._bucket
        _check(lib().dct_batcher_fill_csr(
            self._h, self._ptr(row, np.int32, nz),
            self._ptr(col, np.int32, nz), self._ptr(val, np.float32, nz),
            self._ptr(label, np.float32, self._batch_rows),
            self._ptr(weight, np.float32, self._batch_rows),
            self._ptr(nrows, np.int32, self._num_shards),
            None if qid is None
            else self._ptr(qid, np.int32, self._batch_rows),
            None if field is None else self._ptr(field, np.int32, nz)))

    def fill_packed(self, big: np.ndarray, aux: np.ndarray,
                    nrows: np.ndarray,
                    val: Optional[np.ndarray] = None) -> None:
        """Fused shard-major fill (batcher.h FillPacked): ``big`` is
        [D, kb, bucket] int32 (row, slot, [val f32 bits], [field]; the
        shard's distinct columns follow by cols_meta()/fill_cols()), ``aux``
        is [D, ka, R] int32 (label bits, weight bits, [qid], nrows plane).
        Passing a separate bfloat16 ``val`` plane [D, bucket] converts
        values natively and drops big's f32 val plane. One GIL-free pass
        writes the transfer pack the device lane ships as-is."""
        D = self._num_shards
        R = self._batch_rows // D
        kb = big.shape[1]
        ka = aux.shape[1]
        if val is not None and val.dtype != _bf16_dtype():
            raise DMLCError(
                f"packed val plane must be bfloat16, got {val.dtype}")
        _check(lib().dct_batcher_fill_packed(
            self._h, self._ptr(big, np.int32, D * kb * self._bucket), kb,
            None if val is None
            else self._ptr(val, val.dtype, D * self._bucket),
            0 if val is None else 1,
            self._ptr(aux, np.int32, D * ka * R), ka,
            self._ptr(nrows, np.int32, D)))

    def cols_meta(self):
        """(capacity U, distinct count, tail lifted) of the distinct-column
        lists of the batch fill_packed last wrote (cpp/src/col_slots.h);
        the last says that the batch was a short one sent at the rungs of
        the batch before it (cpp/src/nnz_bucket.h TailRung)."""
        return _cols_meta(lib().dct_batcher_cols_meta, self._h)

    def fill_cols(self, cols: np.ndarray) -> None:
        """Write those lists into ``cols`` [D, U] int32."""
        _fill_cols(lib().dct_batcher_fill_cols, self._h, cols,
                   self._num_shards)

    def set_col_owners(self, owners: int, owner_rows: int) -> None:
        """Lay the lists out owner-major for ``owners`` key ranges of
        ``owner_rows`` ids each (col_slots.h "Owners"); before the first
        batch."""
        _check(lib().dct_batcher_set_col_owners(self._h, owners, owner_rows))

    def cols_owner_max(self) -> int:
        """The fullest owner's count of that batch's distinct columns."""
        return _cols_owner_max(lib().dct_batcher_cols_owner_max, self._h)

    def fill_dense_packed(self, x: np.ndarray, aux: np.ndarray,
                          nrows: np.ndarray) -> None:
        """Fused dense fill (batcher.h FillDensePacked): x as fill_dense
        ([rows, F] float32 or bfloat16 — already shard-major); label/
        weight/[qid]/nrows fused into the shard-major aux pack."""
        if x.dtype == np.float32:
            x_dtype = 0
        elif x.dtype == _bf16_dtype():
            x_dtype = 1
        else:
            raise DMLCError(
                f"dense fill dtype must be float32 or bfloat16, "
                f"got {x.dtype}")
        F = x.shape[-1]
        D = self._num_shards
        R = self._batch_rows // D
        ka = aux.shape[1]
        _check(lib().dct_batcher_fill_dense_packed(
            self._h, self._ptr(x, x.dtype, self._batch_rows * F), x_dtype,
            F, self._ptr(aux, np.int32, D * ka * R), ka,
            self._ptr(nrows, np.int32, D)))

    def fill_dense(self, x: np.ndarray, label: np.ndarray,
                   weight: np.ndarray, nrows: np.ndarray,
                   qid: Optional[np.ndarray] = None) -> None:
        # the native side writes float32 or bfloat16 storage bits directly
        # (batcher.h FillDense x_dtype) — bf16 emission halves host fill and
        # host->HBM transfer bytes and skips the numpy astype copy
        """Write the staged batch into a dense [rows, F] buffer (float32 or
        bfloat16 storage; batcher.h FillDense) with the GIL released."""
        if x.dtype == np.float32:
            x_dtype = 0
        elif x.dtype == _bf16_dtype():
            x_dtype = 1
        else:
            raise DMLCError(
                f"dense fill dtype must be float32 or bfloat16, "
                f"got {x.dtype}")
        F = x.shape[-1]
        _check(lib().dct_batcher_fill_dense(
            self._h, self._ptr(x, x.dtype, self._batch_rows * F), x_dtype, F,
            self._ptr(label, np.float32, self._batch_rows),
            self._ptr(weight, np.float32, self._batch_rows),
            self._ptr(nrows, np.int32, self._num_shards),
            None if qid is None
            else self._ptr(qid, np.int32, self._batch_rows)))

    def before_first(self) -> None:
        """Restart batching from the first row (new epoch)."""
        _check(lib().dct_batcher_before_first(self._h))

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next before_first() samples
        (mid-epoch resume across restarts). Returns False when nothing in
        the split chain shuffles — ordering is then epoch-independent."""
        supported = ctypes.c_int32()
        _check(lib().dct_batcher_set_epoch(self._h, epoch,
                                           ctypes.byref(supported)))
        return bool(supported.value)

    def bytes_read(self) -> int:
        """Bytes consumed from the underlying source so far."""
        out = ctypes.c_size_t()
        _check(lib().dct_batcher_bytes_read(self._h, ctypes.byref(out)))
        return out.value

    def batch_nnz(self) -> int:
        """Real nonzeros of the batch next_meta() last staged, all shards
        (what the staging summed to find the fullest shard)."""
        out = ctypes.c_uint64()
        _check(lib().dct_batcher_batch_nnz(self._h, ctypes.byref(out)))
        return out.value

    def close(self) -> None:
        """Free the native batcher handle (idempotent)."""
        if self._h:
            _check(lib().dct_batcher_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- csr rec ------------------------------------------------------------------
class NativeCsrRecBatcher:
    """Zero-rearrangement CSR ingest (cpp/src/csr_rec.h): records store
    col/val/row-length planes in device batch layout, so a batch fill is
    bulk memcpy + run-length row-id expansion with the GIL released.
    meta() reports the STATIC per-shard nnz bucket (derived from the
    file's global window table); fill() writes caller planes and returns
    the true row count (0 at end)."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 batch_rows: int = 65536, num_shards: int = 1,
                 min_nnz_bucket: int = 4096):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        self._batch_rows = batch_rows
        self._num_shards = num_shards
        self._bucket = 0
        _check(lib().dct_csrrec_create(uri.encode(), part, npart,
                                       batch_rows, num_shards,
                                       min_nnz_bucket,
                                       ctypes.byref(self._h)))

    def meta(self):
        """(bucket, has_weight, has_qid, has_field) — static for the whole
        epoch (one compiled device shape)."""
        bucket = ctypes.c_uint64()
        hw = ctypes.c_int32()
        hq = ctypes.c_int32()
        hf = ctypes.c_int32()
        _check(lib().dct_csrrec_meta(self._h, ctypes.byref(bucket),
                                     ctypes.byref(hw), ctypes.byref(hq),
                                     ctypes.byref(hf)))
        self._bucket = bucket.value
        return (bucket.value, bool(hw.value), bool(hq.value),
                bool(hf.value))

    def fill(self, row, col, val, label, weight, nrows, qid=None,
             field=None) -> int:
        """Fill one batch; returns the true row count (0 = end)."""
        if self._bucket == 0:
            self.meta()  # plane sizing needs the static bucket
        nz = self._num_shards * self._bucket
        take = ctypes.c_uint64()
        ptr = NativeBatcher._ptr
        _check(lib().dct_csrrec_fill(
            self._h, ptr(row, np.int32, nz), ptr(col, np.int32, nz),
            ptr(val, np.float32, nz),
            None if field is None else ptr(field, np.int32, nz),
            ptr(label, np.float32, self._batch_rows),
            ptr(weight, np.float32, self._batch_rows),
            None if qid is None else ptr(qid, np.int32, self._batch_rows),
            ptr(nrows, np.int32, self._num_shards), ctypes.byref(take)))
        return int(take.value)

    def fill_packed(self, big: np.ndarray, aux: np.ndarray,
                    nrows: np.ndarray) -> int:
        """Fused shard-major fill (csr_rec.h FillPacked): big is
        [D, kb, bucket] int32 (row, slot, val f32 bits, [field]; the
        distinct columns follow by cols_meta()/fill_cols()), aux is
        [D, ka, R] int32 (label bits, weight bits, [qid], nrows plane).
        Returns the true row count (0 = end)."""
        if self._bucket == 0:
            self.meta()  # plane sizing needs the static bucket
        D = self._num_shards
        R = self._batch_rows // D
        kb = big.shape[1]
        ka = aux.shape[1]
        take = ctypes.c_uint64()
        ptr = NativeBatcher._ptr
        _check(lib().dct_csrrec_fill_packed(
            self._h, ptr(big, np.int32, D * kb * self._bucket), kb,
            ptr(aux, np.int32, D * ka * R), ka,
            ptr(nrows, np.int32, D), ctypes.byref(take)))
        return int(take.value)

    def before_first(self) -> None:
        """Restart from the first record (new epoch)."""
        _check(lib().dct_csrrec_before_first(self._h))

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next before_first() samples."""
        supported = ctypes.c_int32()
        _check(lib().dct_csrrec_set_epoch(self._h, epoch,
                                          ctypes.byref(supported)))
        return bool(supported.value)

    def bytes_read(self) -> int:
        """Record bytes consumed from the source so far."""
        out = ctypes.c_size_t()
        _check(lib().dct_csrrec_bytes_read(self._h, ctypes.byref(out)))
        return out.value

    def cols_meta(self):
        """(capacity U, distinct count, tail lifted) of the distinct-column
        lists of the batch fill_packed last wrote (cpp/src/col_slots.h,
        cpp/src/nnz_bucket.h TailRung)."""
        return _cols_meta(lib().dct_csrrec_cols_meta, self._h)

    def fill_cols(self, cols: np.ndarray) -> None:
        """Write those lists into ``cols`` [D, U] int32."""
        _fill_cols(lib().dct_csrrec_fill_cols, self._h, cols,
                   self._num_shards)

    def set_col_owners(self, owners: int, owner_rows: int) -> None:
        """As NativeBatcher.set_col_owners."""
        _check(lib().dct_csrrec_set_col_owners(self._h, owners, owner_rows))

    def cols_owner_max(self) -> int:
        """As NativeBatcher.cols_owner_max."""
        return _cols_owner_max(lib().dct_csrrec_cols_owner_max, self._h)

    def batch_nnz(self) -> int:
        """Real nonzeros of the batch the last fill wrote, all shards
        (what the fill counted span by span)."""
        out = ctypes.c_uint64()
        _check(lib().dct_csrrec_batch_nnz(self._h, ctypes.byref(out)))
        return out.value

    def close(self) -> None:
        """Free the native handle (idempotent)."""
        if self._h:
            _check(lib().dct_csrrec_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- dense rec ----------------------------------------------------------------
class NativeDenseRecBatcher:
    """Zero-parse dense ingest (cpp/src/dense_rec.h): records store row
    matrices in device layout, so a batch fill is record framing + bulk
    memcpy with the GIL released. meta() reports the static shape; fill()
    writes caller buffers and returns the true row count (0 at end)."""

    def __init__(self, uri: str, part: int = 0, npart: int = 1,
                 batch_rows: int = 65536, num_shards: int = 1):
        uri = _route_https(uri)
        self._h = ctypes.c_void_p()
        self._batch_rows = batch_rows
        self._num_shards = num_shards
        _check(lib().dct_denserec_create(uri.encode(), part, npart,
                                         batch_rows, num_shards,
                                         ctypes.byref(self._h)))

    def meta(self):
        """(num_features, x_dtype, has_weight) pinned by the first record;
        x_dtype 0 = float32, 1 = bfloat16."""
        F = ctypes.c_uint64()
        dt = ctypes.c_int32()
        hw = ctypes.c_int32()
        _check(lib().dct_denserec_meta(self._h, ctypes.byref(F),
                                       ctypes.byref(dt), ctypes.byref(hw)))
        return F.value, dt.value, bool(hw.value)

    def fill(self, x: np.ndarray, label: np.ndarray, weight: np.ndarray,
             nrows: np.ndarray) -> int:
        """Fill one batch; returns the true row count (0 = end of data).
        x dtype selects the output storage (float32 or bfloat16)."""
        if x.dtype == np.float32:
            out_dtype = 0
        elif x.dtype == _bf16_dtype():
            out_dtype = 1
        else:
            raise DMLCError(
                f"dense fill dtype must be float32 or bfloat16, "
                f"got {x.dtype}")
        F = x.shape[-1]
        take = ctypes.c_uint64()
        _check(lib().dct_denserec_fill(
            self._h,
            NativeBatcher._ptr(x, x.dtype, self._batch_rows * F), out_dtype,
            F,  # checked natively against the file's feature width
            NativeBatcher._ptr(label, np.float32, self._batch_rows),
            NativeBatcher._ptr(weight, np.float32, self._batch_rows),
            NativeBatcher._ptr(nrows, np.int32, self._num_shards),
            ctypes.byref(take)))
        return int(take.value)

    def fill_packed(self, x: np.ndarray, aux: np.ndarray,
                    nrows: np.ndarray) -> int:
        """Fused shard-major fill (dense_rec.h FillPacked): x as fill;
        label/weight/nrows fused into aux [D, 3, R] int32. Returns the
        true row count (0 = end)."""
        if x.dtype == np.float32:
            out_dtype = 0
        elif x.dtype == _bf16_dtype():
            out_dtype = 1
        else:
            raise DMLCError(
                f"dense fill dtype must be float32 or bfloat16, "
                f"got {x.dtype}")
        F = x.shape[-1]
        D = self._num_shards
        R = self._batch_rows // D
        ka = aux.shape[1]
        take = ctypes.c_uint64()
        ptr = NativeBatcher._ptr
        _check(lib().dct_denserec_fill_packed(
            self._h, ptr(x, x.dtype, self._batch_rows * F), out_dtype, F,
            ptr(aux, np.int32, D * ka * R), ka,
            ptr(nrows, np.int32, D), ctypes.byref(take)))
        return int(take.value)

    def before_first(self) -> None:
        """Restart from the first record (new epoch)."""
        _check(lib().dct_denserec_before_first(self._h))

    def set_epoch(self, epoch: int) -> bool:
        """Pin the shuffle permutation the next before_first() samples.
        Returns False (the dense-rec lane's split does not shuffle)."""
        supported = ctypes.c_int32()
        _check(lib().dct_denserec_set_epoch(self._h, epoch,
                                            ctypes.byref(supported)))
        return bool(supported.value)

    def bytes_read(self) -> int:
        """Record bytes consumed from the source so far."""
        out = ctypes.c_size_t()
        _check(lib().dct_denserec_bytes_read(self._h, ctypes.byref(out)))
        return out.value

    def close(self) -> None:
        """Free the native handle (idempotent)."""
        if self._h:
            _check(lib().dct_denserec_free(self._h))
            self._h = ctypes.c_void_p()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- bf16 ---------------------------------------------------------------------
def bf16_convert(src: np.ndarray, dst: np.ndarray) -> None:
    """Native float32 -> bfloat16 bulk conversion (cpp/src/bf16.h).

    ``dst`` must be a C-contiguous bfloat16 array of ``src.size`` elements.
    This is the SAME round-to-nearest-even inline the packed batch fills
    use, exported so the Python parity tests can fuzz it directly against
    ``ml_dtypes.bfloat16``."""
    ptr = NativeBatcher._ptr
    _check(lib().dct_bf16_convert(ptr(src, np.float32, src.size),
                                  ptr(dst, _bf16_dtype(), src.size),
                                  src.size))


def bf16_upcast(src: np.ndarray, dst: np.ndarray) -> None:
    """Native bfloat16 -> float32 bulk upcast (cpp/src/bf16.h), the exact
    widening the device-side bitcast performs."""
    ptr = NativeBatcher._ptr
    _check(lib().dct_bf16_upcast(ptr(src, _bf16_dtype(), src.size),
                                 ptr(dst, np.float32, src.size),
                                 src.size))
