"""One configuration surface for every mock origin, in- and out-of-process.

The per-backend mocks (``tests/mock_s3.py``, ``tests/mock_azure.py``,
``tests/mock_webhdfs.py``, ``tests/mock_http.py``) used to carry three
copies of the same knob plumbing — latency shaping, fault scheduling,
accept backlog — wired up slightly differently by every test module that
spun one up.  This module is the single definition of that surface:

- :class:`OriginConfig` — every shaping/fault knob an origin understands,
  with the defaults the test suite has always used;
- :func:`make_server` / :func:`serve_backend` — the one in-process
  spin-up path (``mock_*.serve()`` delegates here), which also accepts a
  pre-bound listening socket so the out-of-process rig
  (``scripts/loadrig.py``) can pre-fork workers over one listener;
- :func:`apply_config` / :func:`reset_state` — knob application and the
  between-tests reset that ``test_io_resilience``/``test_io_ranged``
  used to hand-roll per backend;
- corpus helpers — deterministic pseudo-byte or file-backed objects
  loaded identically into any backend's store, so an out-of-process
  origin can be byte-identical to the in-process mock by construction;
- :func:`client_env` / :func:`uri_for` — what a *client* process needs
  to reach an origin on a given port.

Backend keys follow one convention: ``s3`` keys are ``bucket/key``,
``azure`` keys are ``container/blob``, ``webhdfs`` and ``http`` keys are
absolute paths (``/a/b``).
"""

from __future__ import annotations

import importlib
import threading
from dataclasses import dataclass, field, fields
from http.server import ThreadingHTTPServer

BACKENDS = ("s3", "azure", "webhdfs", "http")

# socketserver's default backlog of 5 drops SYNs under the parallel
# ranged readers' connect bursts; every origin defaults deeper
DEFAULT_BACKLOG = 128


@dataclass
class OriginConfig:
    """Every shaping and fault knob a mock origin understands.

    Backends that lack a knob (e.g. WebHDFS has no ``ignore_range`` —
    its ranges ride OPEN params, not a Range header) simply ignore it:
    :func:`apply_config` sets only the attributes the state object
    declares.
    """

    # latency/bandwidth shaping: sleep latency_ms before the response
    # head and once per latency_block body bytes (a latency-bandwidth-
    # capped connection — one connection tops out at block/latency)
    latency_ms: int = 0
    latency_block: int = 256 * 1024
    # the sleep before the response head where it is not latency_ms: an
    # object store's time to the first byte, with latency_ms left to pace
    # the body (first_byte_ms=100, latency_ms=3, latency_block=262144:
    # 100 ms, then 256 KiB every 3 ms, 87 MB/s a connection). Unset, the
    # head waits latency_ms as it always did.
    first_byte_ms: "int | None" = None
    # fault plan (every-Nth scheduling via FaultCounterMixin)
    stall_every: int = 0          # accept, sleep past client deadline
    stall_seconds: float = 3.0
    reset_every: int = 0          # RST mid-header
    get_500_every: int = 0        # 500 before body
    get_truncate_every: int = 0   # declared length, half the body, cut
    # a *served* stall: every Nth response is delayed slow_ms but
    # completes normally — the coordinated-omission probe (the request
    # succeeds; only a latency capture honest about intended start
    # times sees the queue it caused)
    slow_every: int = 0
    slow_ms: int = 0
    ignore_range: bool = False    # answer 200 full-body (Range ignored)
    bad_content_range_every: int = 0
    # server shape
    backlog: int = DEFAULT_BACKLOG
    workers: int = 1              # pre-forked processes (loadrig only)
    extra: dict = field(default_factory=dict)  # backend-specific knobs

    def cli_args(self) -> list:
        """Render the shaping knobs as ``loadrig.py origin`` flags.
        Backend-specific ``extra`` knobs have no CLI spelling — an
        out-of-process origin carrying them must fail loudly, not
        silently serve the happy path."""
        if self.extra:
            raise ValueError(
                f"extra knobs {sorted(self.extra)} are not launchable "
                f"out of process (no CLI flags); use an in-process "
                f"origin for them")
        args = []
        for f in fields(self):
            if f.name in ("extra", "workers"):
                continue
            v = getattr(self, f.name)
            d = f.default
            if v == d:
                continue
            flag = "--" + f.name.replace("_", "-")
            if isinstance(v, bool):
                args.append(flag)
            else:
                args.extend([flag, str(v)])
        args.extend(["--workers", str(self.workers)])
        return args


# knobs applied onto a state object (only those the state declares)
_KNOBS = ("latency_ms", "latency_block", "first_byte_ms", "stall_every",
          "stall_seconds",
          "reset_every", "get_500_every", "get_truncate_every",
          "slow_every", "slow_ms", "ignore_range",
          "bad_content_range_every")

# reset defaults — the shared between-tests zeroing
_KNOB_DEFAULTS = {k: getattr(OriginConfig(), k) for k in _KNOBS}
_KNOB_DEFAULTS.update({"fail_reads_after": None})


def apply_config(state, config: "OriginConfig | None") -> None:
    """Copy every knob the state declares from ``config`` onto it."""
    if config is None:
        return
    for k in _KNOBS:
        if hasattr(state, k):
            setattr(state, k, getattr(config, k))
    for k, v in config.extra.items():
        if not hasattr(state, k):
            raise AttributeError(f"origin state has no knob {k!r}")
        setattr(state, k, v)


def reset_state(state) -> None:
    """Zero every shaping/fault knob, the request log, and the fault
    counters — the shared between-tests reset (content stores are left
    alone; callers clear those)."""
    for k, v in _KNOB_DEFAULTS.items():
        if hasattr(state, k):
            setattr(state, k, v)
    state.requests.clear()
    if hasattr(state, "_counters"):
        for k in state._counters:
            state._counters[k] = 0


def make_server(handler_cls, state, config: "OriginConfig | None" = None,
                ssl_context=None, sock=None):
    """Build (but do not start) an HTTP server for a mock backend.

    With ``sock`` the server adopts a pre-bound, already-listening
    socket instead of binding its own — the pre-forked-worker path,
    where N processes accept from one shared listener."""
    config = config or OriginConfig()
    handler = type("Handler", (handler_cls,), {"state": state})
    srv_cls = type("Server", (ThreadingHTTPServer,),
                   {"request_queue_size": config.backlog})
    if sock is not None:
        server = srv_cls(("127.0.0.1", 0), handler, bind_and_activate=False)
        server.socket.close()
        server.socket = sock
        server.server_address = sock.getsockname()
    else:
        server = srv_cls(("127.0.0.1", 0), handler)
        if ssl_context is not None:
            server.socket = ssl_context.wrap_socket(server.socket,
                                                    server_side=True)
    apply_config(state, config)
    port = server.server_address[1]
    # webhdfs needs its own address to mint datanode redirects
    if hasattr(state, "port"):
        state.port = port
    if ssl_context is not None and hasattr(state, "scheme"):
        state.scheme = "https"
    return server


def start_server(server):
    """serve_forever on a daemon thread; returns a shutdown fn."""
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server.shutdown


def backend_module(name: str):
    """The mock module for a backend name (lazy — no import cycles)."""
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r} (want one of "
                         f"{BACKENDS})")
    return importlib.import_module(f"tests.mock_{name}")


def state_and_handler(name: str):
    """(state instance, handler class) for a backend name."""
    mod = backend_module(name)
    cls = {"s3": ("MockS3State", "MockS3Handler"),
           "azure": ("MockAzureState", "MockAzureHandler"),
           "webhdfs": ("MockHdfsState", "MockHdfsHandler"),
           "http": ("MockHttpState", "MockHttpHandler")}[name]
    return getattr(mod, cls[0])(), getattr(mod, cls[1])


def serve_backend(name: str, config: "OriginConfig | None" = None,
                  ssl_context=None):
    """In-process spin-up of any backend: (state, port, shutdown_fn) —
    the one path ``mock_*.serve()`` and every fixture share."""
    state, handler_cls = state_and_handler(name)
    server = make_server(handler_cls, state, config, ssl_context)
    shutdown = start_server(server)
    return state, server.server_address[1], shutdown


# -- corpus ------------------------------------------------------------------
def pseudo_bytes(size: int, seed: int) -> bytes:
    """Deterministic pseudo-random bytes (splitmix64-fed), identical in
    every process that generates the same (size, seed) — what makes an
    out-of-process origin byte-identical to the in-process mock without
    shipping the payload across."""
    out = bytearray()
    x = (seed or 1) & 0xFFFFFFFFFFFFFFFF
    while len(out) < size:
        x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
        z = x
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
        z ^= z >> 31
        out.extend(z.to_bytes(8, "little"))
    return bytes(out[:size])


def build_corpus(specs) -> dict:
    """``key=<size>:<seed>`` or ``key=@<path>`` spec strings -> bytes.

    The same spec list handed to ``loadrig.py origin`` and to an
    in-process :func:`serve_backend` produces the same objects. A path is
    read once: keys that name the same path share one buffer (24 day
    objects of one 266 MB file are 266 MB, in the launcher and, the
    corpus being built before the pre-fork, in every worker)."""
    corpus = {}
    files = {}
    for spec in specs or ():
        key, _, rhs = spec.partition("=")
        if not key or not rhs:
            raise ValueError(f"corpus spec {spec!r}: want key=@path or "
                             f"key=size:seed")
        if rhs.startswith("@"):
            if rhs not in files:
                with open(rhs[1:], "rb") as f:
                    files[rhs] = f.read()
            corpus[key] = files[rhs]
        else:
            size, _, seed = rhs.partition(":")
            corpus[key] = pseudo_bytes(int(size), int(seed or "0"))
    return corpus


def put_object(name: str, state, key: str, data: bytes) -> None:
    """Store one object under a backend's key convention."""
    if name == "s3":
        bucket, _, k = key.partition("/")
        state.objects[(bucket, k)] = data
    elif name == "azure":
        container, _, blob = key.partition("/")
        state.blobs[(container, blob)] = data
    elif name == "webhdfs":
        state.files[key if key.startswith("/") else "/" + key] = data
    elif name == "http":
        state.objects[key if key.startswith("/") else "/" + key] = data
    else:
        raise ValueError(f"unknown backend {name!r}")


def load_corpus(name: str, state, corpus: dict) -> None:
    """Load a ``{key: bytes}`` corpus into a backend state's store."""
    for key, data in corpus.items():
        put_object(name, state, key, data)


def client_env(name: str, port: int) -> dict:
    """Env vars a *client* process needs to reach an origin on ``port``
    (the native s3/azure singletons read these once, at first use —
    which is exactly why rig clients run in their own process)."""
    if name == "s3":
        s3 = backend_module("s3")
        return {"S3_ENDPOINT": f"http://127.0.0.1:{port}",
                "S3_ACCESS_KEY_ID": s3.ACCESS_KEY,
                "S3_SECRET_ACCESS_KEY": s3.SECRET_KEY,
                "S3_REGION": s3.REGION}
    if name == "azure":
        az = backend_module("azure")
        return {"AZURE_STORAGE_ACCOUNT": az.ACCOUNT,
                "AZURE_STORAGE_ACCESS_KEY": az.KEY_B64,
                "AZURE_ENDPOINT": f"http://127.0.0.1:{port}"}
    return {}


def uri_for(name: str, port: int, key: str) -> str:
    """The client-side URI for an object stored under ``key``."""
    if name == "s3":
        return f"s3://{key}"
    if name == "azure":
        return f"azure://{key}"
    if name == "webhdfs":
        return f"hdfs://127.0.0.1:{port}{key}"
    if name == "http":
        return f"http://127.0.0.1:{port}{key}"
    raise ValueError(f"unknown backend {name!r}")
