#!/usr/bin/env python3
"""Headline benchmark: HIGGS-like libsvm ingest -> HBM-resident sharded batches.

Prints ONE JSON line:
  {"metric": "higgs_libsvm_ingest_rows_per_sec", "value": N,
   "unit": "rows/s", "vs_baseline": R, "extras": {...}}

One process per chip: this parent never imports jax. It generates the
data, runs the host-only probes in-process, and runs every lane that
touches the device as a child of its own, one after another — each child
exits before the next starts. Every device lane's JSON names the
``platform``, ``device_kind``, device count and mesh it ran on, and
refuses the CPU backend: a CPU number is never written under a device
metric's name (use --parse-only for host-only metrics). A lane that fails
or times out fails the run: non-zero exit, no result line.

- value: MEDIAN of --reps (default 5) end-to-end passes through the full
  pipeline (native multithreaded parse -> static-shape padding with native
  bf16 dense emission -> device_put under a mesh sharding -> a consuming
  jitted reduction on device, overlapped via the double buffer). The
  spread (min/max) rides in extras.e2e_spread_rows_per_sec.
- vs_baseline: ratio against the reference C++ build's parse-to-host
  throughput on the same dataset/machine (bench_baseline.json; the reference
  publishes no numbers — BASELINE.md).
- extras.hbm_ingest_bw_util: (device bytes landed / wall time) divided by
  the attainable device_put bandwidth measured for the SAME pytree the
  pipeline lands per batch. The contiguous single-buffer ceiling is also
  reported (attainable_contiguous_bytes_per_sec) so both denominators are
  visible. extras.bottleneck names the binding stage.
- extras.thread_scaling: host-parse rows/s at 1/2/4/8 parse workers;
  extras.parse_pipeline_occupancy carries the multi-chunk pipeline's
  per-stage counters at each worker count (plus a "headline" entry on
  --parse-only runs) so a flat scaling row names its binding stage.
  extras.parse_simd_lane names the text parsers' structural-scan tier
  (scalar/swar/sse2/avx2; doc/parsing.md, DMLC_PARSE_SIMD).
- --format=rec|crec|recd: a binary-ingest lane as the headline. The default
  JSON line stays the libsvm headline; extras.{rec,crec,recd}_lane carry
  the binary lanes' numbers unless --no-rec-lane is given.
- extras.mesh_lane is the one lane pinned to the CPU backend, by design:
  it measures the tracker's control plane (detection, relaunch, KV-store
  collective cadence), and says ``"platform": "cpu"`` in its JSON.

Flags: --smoke (tiny dataset, CI), --rows N, --parse-only, --threads N,
--reps N, --format {libsvm,rec,crec,recd}, --dense-dtype {bf16,f32},
--no-scaling-table, --no-rec-lane, --no-ledger.
"""

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

CACHE_DIR = os.path.join(REPO, ".bench_cache")


def run_child(lane: str, argv: list, timeout: float,
              env: "dict | None" = None) -> dict:
    """Run one lane as a child of this script and return the JSON object
    on the last line of its stdout. The child owns the chip for as long
    as it lives and is gone before the next one starts. A non-zero exit
    or a timeout ends the whole run: a lane that did not measure must
    not leave a result that looks as if it had."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        raise SystemExit(f"bench: {lane} timed out after {timeout:.0f}s")
    if out.returncode != 0:
        raise SystemExit(f"bench: {lane} failed (exit {out.returncode}):\n"
                         + (out.stderr or "")[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def require_accelerator(mesh=None) -> dict:
    """What every device lane does first, in its child process: turn on
    the persistent compile cache, say where it runs, and refuse the CPU
    backend — these lanes write device-named metrics (hbm_*, device_*),
    and a CPU number must never stand under such a name. Returns the
    device report."""
    from dmlc_core_tpu.tpu.runtime import (device_banner, device_report,
                                           enable_compile_cache)
    enable_compile_cache()
    report = device_report(mesh)
    print(f"# {device_banner(report)}", file=sys.stderr)
    if report["platform"] == "cpu":
        raise SystemExit(
            "bench: this lane reports device metrics and jax found "
            "platform=cpu; run it where jax finds an accelerator, or use "
            "--parse-only for the host-only metrics")
    return report


def ensure_dataset(rows: int) -> str:
    import numpy as np
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.libsvm")
    if os.path.exists(path):
        return path
    os.makedirs(CACHE_DIR, exist_ok=True)
    rng = np.random.default_rng(7)
    F = 28
    step = min(rows, 10000)
    with open(path + ".tmp", "w") as f:
        for start in range(0, rows, step):
            n = min(step, rows - start)
            vals = rng.uniform(-3, 3, size=(n, F))
            labels = rng.integers(0, 2, size=n)
            lines = []
            for i in range(n):
                feats = " ".join(f"{j}:{vals[i, j]:.6f}" for j in range(F))
                lines.append(f"{labels[i]} {feats}")
            f.write("\n".join(lines) + "\n")
    os.replace(path + ".tmp", path)
    return path


def ensure_rec_dataset(rows: int) -> str:
    """Binary lane: the libsvm dataset converted once to RecordIO-framed
    row blocks (the pre-parsed ingest format, reference recordio.h:166
    ChunkReader rationale — binary ingest can feed what text parse cannot)."""
    from dmlc_core_tpu.io.convert import rows_to_recordio
    src = ensure_dataset(rows)
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.rec")
    if os.path.exists(path):
        return path
    rows_to_recordio(src, path + ".tmp", fmt="libsvm")
    os.replace(path + ".tmp", path)
    return path


def ensure_drec_dataset(rows: int) -> str:
    """Zero-parse lane: dense bf16 row matrices in device layout
    (cpp/src/dense_rec.h) — ingest is record framing + memcpy, the bytes on
    disk are the bytes the MXU wants."""
    from dmlc_core_tpu.io.convert import rows_to_dense_recordio
    src = ensure_dataset(rows)
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.drec")
    if os.path.exists(path):
        return path
    rows_to_dense_recordio(src, path + ".tmp", fmt="libsvm", dtype="bf16")
    os.replace(path + ".tmp", path)
    return path


def ensure_crec_dataset(rows: int) -> str:
    """Zero-rearrangement CSR lane: col/val/row-length planes in device
    layout (cpp/src/csr_rec.h) — ingest is bulk memcpy + row-id expansion,
    one pass, static nnz bucket."""
    from dmlc_core_tpu.io.convert import rows_to_csr_recordio
    src = ensure_dataset(rows)
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.crec")
    if os.path.exists(path):
        return path
    rows_to_csr_recordio(src, path + ".tmp", fmt="libsvm")
    os.replace(path + ".tmp", path)
    return path


def ensure_csv_dataset(rows: int) -> str:
    """The same HIGGS-shaped data as dense csv (label first column)."""
    import numpy as np
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.csv")
    if os.path.exists(path):
        return path
    os.makedirs(CACHE_DIR, exist_ok=True)
    rng = np.random.default_rng(7)
    F = 28
    step = min(rows, 10000)
    with open(path + ".tmp", "w") as f:
        for start in range(0, rows, step):
            n = min(step, rows - start)
            vals = rng.uniform(-3, 3, size=(n, F))
            labels = rng.integers(0, 2, size=n)
            f.write("\n".join(
                f"{labels[i]}," + ",".join(f"{v:.6f}" for v in vals[i])
                for i in range(n)) + "\n")
    os.replace(path + ".tmp", path)
    return path


def ensure_libfm_dataset(rows: int) -> str:
    """KDD-shaped factorization rows: `label field:feature:value`."""
    import numpy as np
    path = os.path.join(CACHE_DIR, f"higgs_{rows}.libfm")
    if os.path.exists(path):
        return path
    os.makedirs(CACHE_DIR, exist_ok=True)
    rng = np.random.default_rng(7)
    F = 28
    step = min(rows, 10000)
    with open(path + ".tmp", "w") as f:
        for start in range(0, rows, step):
            n = min(step, rows - start)
            vals = rng.uniform(-3, 3, size=(n, F))
            labels = rng.integers(0, 2, size=n)
            f.write("\n".join(
                f"{labels[i]} " + " ".join(
                    f"{j % 7}:{j}:{vals[i, j]:.6f}" for j in range(F))
                for i in range(n)) + "\n")
    os.replace(path + ".tmp", path)
    return path


# the binary ingest lanes and their one-time converters — the single
# source for the headline-lane path picker, the subprocess device lanes,
# and the host-side lane rates
BINARY_LANES = (("rec", ensure_rec_dataset),
                ("crec", ensure_crec_dataset),
                ("recd", ensure_drec_dataset))


def _load_baseline():
    """bench_baseline.json as a dict, or None when absent."""
    path = os.path.join(REPO, "bench_baseline.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def git_provenance() -> dict:
    """{"git_sha", "git_dirty"} of the tree this run measures (None/None
    outside a git checkout — provenance is evidence, never a blocker)."""
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                             capture_output=True, text=True,
                             timeout=30).stdout.strip() or None
        st = subprocess.run(["git", "status", "--porcelain"], cwd=REPO,
                            capture_output=True, text=True, timeout=30)
        dirty = bool(st.stdout.strip()) if st.returncode == 0 else None
        return {"git_sha": sha, "git_dirty": dirty}
    except (OSError, subprocess.TimeoutExpired):
        return {"git_sha": None, "git_dirty": None}


def host_fingerprint() -> dict:
    """The stable facts a ledger reader needs to know whether two runs
    are comparable at all: host name, core count, schedulable affinity,
    memory, platform, python."""
    import platform
    import socket
    try:
        affinity = len(os.sched_getaffinity(0))
    except AttributeError:
        affinity = os.cpu_count()
    mem_gb = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    mem_gb = round(int(line.split()[1]) / 1e6, 1)
                    break
    except OSError:
        pass
    return {"host": socket.gethostname(), "cpus": os.cpu_count(),
            "affinity": affinity, "mem_gb": mem_gb,
            "platform": platform.platform(),
            "python": platform.python_version()}


def dmlc_env_overrides() -> dict:
    """Every DMLC_*/DCT_* env var active for this run — the knobs that
    change what the numbers mean (doc/benchmarking.md)."""
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith(("DMLC_", "DCT_"))}


def append_ledger(result: dict, provenance: dict, host: dict,
                  env_overrides: dict, host_resources, smoke: bool,
                  history_path: str) -> "str | None":
    """Append this run's normalized record to the bench regression
    ledger (scripts/benchdiff.py reads it); returns the path written or
    None. Best-effort by design: a full disk must not sink the already-
    printed result."""
    try:
        scripts = os.path.join(REPO, "scripts")
        if scripts not in sys.path:
            sys.path.insert(0, scripts)
        import benchdiff
        record = benchdiff.make_record(
            result, git_sha=provenance.get("git_sha"),
            git_dirty=provenance.get("git_dirty"), host=host,
            env_overrides=env_overrides, host_resources=host_resources,
            smoke=smoke, argv=sys.argv[1:])
        benchdiff.append_record(record, history_path)
        return history_path
    except Exception as e:  # noqa: BLE001 - the ledger is evidence,
        # never the reason a measured run dies
        print(f"# ledger append failed: {e}", file=sys.stderr)
        return None


def cache_lane_probe(path: str, rows: int, nthread: int) -> dict:
    """Parse-once-serve-many lane (cpp/src/shard_cache.h, doc/caching.md):
    epoch 1 parses text while teeing binary shards into a fresh cache dir,
    epoch 2+ replays the shards through the mmap zero-copy reader. Reports
    both rates so the ROADMAP success metric (epoch-2+ ingest within 2x of
    the raw recd lane) is a visible ratio, not an inference."""
    import shutil
    import tempfile
    from dmlc_core_tpu.io.native import NativeParser
    os.makedirs(CACHE_DIR, exist_ok=True)
    cdir = tempfile.mkdtemp(prefix="shardcache_", dir=CACHE_DIR)
    try:
        def one_epoch() -> float:
            t0 = time.time()
            got = 0
            with NativeParser(path, nthread=nthread, cache_dir=cdir) as p:
                for blk in p:
                    got += blk.num_rows
            dt = time.time() - t0
            assert got == rows, f"row count mismatch: {got} != {rows}"
            return rows / dt
        ep1 = one_epoch()  # transcode (text parse + shard tee)
        ep2 = max(one_epoch() for _ in range(3))  # mmap replay, best of 3
        cache_bytes = sum(
            os.path.getsize(os.path.join(cdir, f)) for f in os.listdir(cdir))
        return {"epoch1_rows_per_sec": round(ep1, 1),
                "epoch2_rows_per_sec": round(ep2, 1),
                "replay_speedup": round(ep2 / ep1, 2),
                "cache_bytes": cache_bytes,
                "text_bytes": os.path.getsize(path)}
    finally:
        shutil.rmtree(cdir, ignore_errors=True)


def remote_lane_probe(path: str, nthread: int, latency_ms: int = 20,
                      cap_bytes: int = 8 << 20,
                      concurrency: int = 12, sampler=None) -> dict:
    """Parallel ranged remote reads lane (cpp/src/range_reader.h,
    doc/io-ranged.md) against the OUT-OF-PROCESS origin rig
    (scripts/loadrig.py, doc/benchmarking.md): the libsvm dataset is
    served by pre-forked mock-S3 worker processes with ``latency_ms``
    injected per request AND per body block server-side (a
    latency-bandwidth-capped origin), and every remote pass runs in its
    own parse-client subprocess — fresh native singleton per endpoint,
    no GIL shared between the origin and the fetch+parse threads it
    measures.  Reports sequential vs ranged vs local rates, the
    zero-latency origin ceiling, the range scheduler's telemetry, and a
    CPU attribution row (client vs origin seconds, from /proc) so a
    vs_local gap names its binding side instead of the retired
    ``mock_ceiling`` guess."""
    import tempfile
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import loadrig
    from tests.mock_origin import OriginConfig
    from dmlc_core_tpu.io.native import NativeParser

    with open(path, "rb") as f:
        blob = f.read(cap_bytes)
    blob = blob[: blob.rfind(b"\n") + 1]  # whole lines only
    lane_rows = blob.count(b"\n")
    key = "bench/remote/data.libsvm"
    # at least 2 origin workers so the serving side is never one
    # process; more when the host has the cores to back them
    workers = max(2, os.cpu_count() or 2)
    # one connection caps at latency_block/latency_ms — the long-haul-link
    # shape where parallel ranges win; scaled to the payload so a
    # sequential pass always pays ~8 serialized bursts regardless of size
    latency_block = max(len(blob) // 8, 64 << 10)

    def local_pass(u):
        t0 = time.time()
        got = 0
        with NativeParser(u, nthread=nthread, fmt="libsvm") as p:
            for blk in p:
                got += blk.num_rows
        dt = time.time() - t0
        assert got == lane_rows, f"row count mismatch: {got} != {lane_rows}"
        return lane_rows / dt

    def client_pass(origin, env_extra, reps):
        env = dict(os.environ, **origin.env())
        env.update({k: str(v) for k, v in env_extra.items()})
        out = subprocess.run(
            [sys.executable,
             os.path.join(scripts, "loadrig.py"), "parse-client",
             "--uri", origin.uri(key), "--fmt", "libsvm",
             "--nthread", str(nthread), "--reps", str(reps)],
            capture_output=True, text=True, timeout=600, env=env)
        if out.returncode != 0:
            raise RuntimeError("parse-client failed: "
                               + (out.stderr or "")[-300:])
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["rows"] == lane_rows, \
            f"row count mismatch: {res['rows']} != {lane_rows}"
        return res

    ranged_env = {"DMLC_IO_RANGE": "1",
                  "DMLC_IO_RANGE_CONCURRENCY": str(concurrency)}
    tmp = tempfile.NamedTemporaryFile(suffix=".libsvm", delete=False)
    try:
        tmp.write(blob)
        tmp.close()
        spec = [f"{key}=@{tmp.name}"]
        # local parse of the SAME bytes: the vs_local denominator
        local_rps = max(local_pass(tmp.name) for _ in range(2))
        # the origin's own ceiling: ranged ingest with NO injected
        # latency against the same worker fleet — how fast this origin
        # can serve at all, measured instead of guessed
        with loadrig.spawn_origin(
                "s3", spec, OriginConfig(workers=workers)) as org:
            ceiling_rps = client_pass(org, ranged_env, 2)["rows_per_sec"]
        cfg = OriginConfig(workers=workers, latency_ms=latency_ms,
                           latency_block=latency_block)
        with loadrig.spawn_origin("s3", spec, cfg) as org:
            if sampler is not None:
                sampler.watch("remote_origin", org.proc.pid, *org.pids)
            seq_rps = client_pass(
                org, {"DMLC_IO_RANGE": "0"}, 2)["rows_per_sec"]
            origin_cpu0 = org.cpu_seconds()
            if sampler is not None:
                section = sampler.section("remote_lane_ranged")
            else:
                import contextlib
                section = contextlib.nullcontext()
            with section:
                ranged = client_pass(org, ranged_env, 3)
            origin_cpu = round(org.cpu_seconds() - origin_cpu0, 3)
        ranged_rps = ranged["rows_per_sec"]
        counters = ranged.get("counters", {})
        gauges = ranged.get("gauges", {})
        hb = ranged.get("range_hists", {}).get("io_range_bytes", {})
        sched = {
            "ranges_issued": int(counters.get("io_range_issued_total", 0)),
            "range_retries": int(counters.get("io_range_retried_total",
                                              0)),
            "degraded_200": int(
                counters.get("io_range_degraded_200_total", 0)),
            "sched_range_kb": round(
                gauges.get("io_range_sched_bytes", 0) / 1024, 1),
            "sched_concurrency": int(
                gauges.get("io_range_sched_concurrency", 0)),
        }
        if hb.get("count"):
            sched["mean_range_kb"] = round(hb["sum"] / hb["count"] / 1024,
                                           1)
        # the ranged client's own transport-retry noise (io_* counters
        # live in ITS process now, not the bench's — extras.io_retry
        # below only sees in-process traffic)
        client_io = {k: int(counters.get(f"io_{k}_total", 0))
                     for k in ("requests", "retries", "timeouts",
                               "giveups")}
        # CPU attribution (the evidence the mock_ceiling caveat lacked):
        # client parse+fetch seconds vs origin serve seconds over the
        # ranged wall time, against the cores this host has
        ncores = os.cpu_count() or 1
        wall = ranged.get("total_dt") or ranged["best_dt"]
        client_busy = ranged["cpu_s"] / wall if wall else 0.0
        origin_busy = origin_cpu / wall if wall else 0.0
        if client_busy + origin_busy >= 0.85 * ncores:
            verdict = ("client_core_saturated"
                       if client_busy >= origin_busy
                       else "origin_core_saturated")
        else:
            verdict = "latency_bound"
        return {
            "bytes": len(blob),
            "rows": lane_rows,
            "latency_ms": latency_ms,
            "local_rows_per_sec": round(local_rps, 1),
            "sequential_rows_per_sec": round(seq_rps, 1),
            "ranged_rows_per_sec": round(ranged_rps, 1),
            "origin_ceiling_rows_per_sec": round(ceiling_rps, 1),
            "ranged_vs_sequential": round(ranged_rps / seq_rps, 2),
            "ranged_vs_local": round(ranged_rps / local_rps, 3),
            # the out-of-process origin's best case vs local: how much
            # of any remaining vs_local gap is origin capacity
            "ceiling_vs_local": round(ceiling_rps / local_rps, 3),
            # how much of the injected latency the scheduler hid: ranged
            # WITH latency vs the same path with NONE (the origin ceiling)
            "latency_hidden": round(ranged_rps / ceiling_rps, 3),
            "range_scheduler": sched,
            "client_io_retry": client_io,
            "origin": {
                "out_of_process": True,
                "workers": workers,
                "client_cpu_s": ranged["cpu_s"],
                "origin_cpu_s": origin_cpu,
                "ranged_wall_s": round(wall, 3),
                "ncores": ncores,
                "cpu_attribution": verdict,
            },
        }
    finally:
        os.unlink(tmp.name)


def text_lane_probe(path: str, rows: int, nthread: int, fmt: str,
                    fmt_args: str = "") -> dict:
    """Host parse throughput for a text lane (multi-chunk parse pipeline —
    NativeParser rides the native reader/worker/reassembly stages). No device
    stage, so it runs in this (jax-free) parent. Best of 3 passes."""
    from dmlc_core_tpu.io.native import NativeParser
    best = None
    uri = path + fmt_args
    for _ in range(3):
        t0 = time.time()
        got = 0
        with NativeParser(uri, nthread=nthread, fmt=fmt) as p:
            for blk in p:
                got += blk.num_rows
        dt = time.time() - t0
        assert got == rows, f"row count mismatch: {got} != {rows}"
        best = dt if best is None else min(best, dt)
    return {"rows_per_sec": round(rows / best, 1),
            "mb_per_sec": round(os.path.getsize(path) / best / 1e6, 1)}


def recordio_roundtrip_probe(records: int = 200000, payload: int = 256,
                             native: bool = True) -> dict:
    """RecordIO write+read round-trip records/s (BASELINE.md target row;
    reference analog: recordio_test.cc / the ImageNet .rec round-trip)."""
    import tempfile
    from dmlc_core_tpu.io.native import (NativeRecordIOReader,
                                         NativeRecordIOWriter)
    blob = bytes(range(256)) * (payload // 256 + 1)
    blob = blob[:payload]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "rt.rec")
        t0 = time.time()
        with NativeRecordIOWriter(path) as w:
            for i in range(records):
                w.write_record(blob)
        t_write = time.time() - t0
        t0 = time.time()
        got = 0
        with NativeRecordIOReader(path) as r:
            for rec in r:
                assert len(rec) == payload
                got += 1
        t_read = time.time() - t0
    assert got == records
    out = {"records_per_sec": round(records / (t_write + t_read), 1),
           "write_records_per_sec": round(records / t_write, 1),
           "read_records_per_sec": round(records / t_read, 1),
           "payload_bytes": payload}
    # ENGINE-level number alongside the Python-API one above (which pays
    # a ctypes call per record): this is the rate comparable to the
    # reference's C++ round-trip in bench_baseline.json parity_rows.
    # `make` runs unconditionally (dependency-tracked: a no-op when fresh,
    # a rebuild after C++ edits — never a stale engine; its output is shown
    # and a failed build fails the run). Skipped in smoke runs
    # (native=False): a clean checkout would pay an -O3 build inside the
    # CI path.
    if not native:
        return out
    binary = os.path.join(REPO, "dmlc_core_tpu", "_native", "bench_pipeline")
    subprocess.run(["make", "-C", os.path.join(REPO, "cpp"),
                    "benchpipeline"], check=True, timeout=300)
    with tempfile.TemporaryDirectory() as d2:
        r = subprocess.run(
            [binary, "rt", str(records), str(payload),
             os.path.join(d2, "rt.rec")],
            capture_output=True, text=True, timeout=300, check=True)
    # "recordio_rt   NNN rec/s  (write ..., read ..., ...)"
    out["native_records_per_sec"] = float(r.stdout.split()[1])
    return out


def parse_rows_per_sec(path: str, rows: int, nthread: int, fmt: str = "auto",
                       dense_dtype: str = "bfloat16",
                       stats_out: "dict | None" = None
                       ) -> "tuple[float, float]":
    """(rows/s, seconds) host-side throughput at a given worker count:
    parse for the text/rec lanes, batch assembly for the zero-parse dense
    lane (which has no parse stage — nthread does not apply). When
    `stats_out` is given, the parse pipeline's occupancy counters
    (NativeParser.pipeline_stats) are copied into it."""
    got = 0
    if fmt in ("recd", "crec"):
        # imported (jax and all) before the clock starts
        from dmlc_core_tpu.tpu.device_iter import (CsrRecHostBatcher,
                                                   DenseRecHostBatcher)
        t0 = time.time()
        b = (DenseRecHostBatcher(path, dense_dtype=dense_dtype)
             if fmt == "recd" else CsrRecHostBatcher(path))
        while True:
            batch = b.next_batch()
            if batch is None:
                break
            got += batch.total_rows
        b.close()
    else:
        from dmlc_core_tpu.io.native import NativeParser, lib
        lib()  # built and loaded before the clock starts
        t0 = time.time()
        with NativeParser(path, nthread=nthread, fmt=fmt) as p:
            for blk in p:
                got += blk.num_rows
            if stats_out is not None:
                stats_out.update(p.pipeline_stats() or {})
    dt = time.time() - t0
    assert got == rows, f"row count mismatch: {got} != {rows}"
    return rows / dt, dt


def pallas_format_probe(batch_rows: int = 1024, features: int = 28,
                        nnz_per_row: int = 28) -> dict:
    """Device-side CSR->dense batch formatting: the Pallas
    scatter-as-matmul kernel (ops/pallas_kernels.py, compiled by Mosaic)
    vs XLA scatter-add, on a shard-sized problem. Child process only.
    Values are cross-checked on device before timing."""
    import numpy as np
    import jax
    from dmlc_core_tpu.ops.pallas_kernels import csr_to_dense_pallas
    from dmlc_core_tpu.ops.sparse import csr_to_dense
    device = require_accelerator()
    rng = np.random.default_rng(11)
    nnz = batch_rows * nnz_per_row
    row = np.repeat(np.arange(batch_rows, dtype=np.int32), nnz_per_row)
    col = rng.integers(0, features, nnz).astype(np.int32)
    val = rng.normal(size=nnz).astype(np.float32)
    row_d, col_d, val_d = (jax.device_put(a) for a in (row, col, val))
    xla_fn = jax.jit(lambda r, c, v: csr_to_dense(
        r, c, v, batch_rows, features, impl="xla"))
    pl_fn = jax.jit(lambda r, c, v: csr_to_dense_pallas(
        r, c, v, batch_rows, features))
    np.testing.assert_allclose(np.asarray(pl_fn(row_d, col_d, val_d)),
                               np.asarray(xla_fn(row_d, col_d, val_d)),
                               rtol=1e-5, atol=1e-5)

    def one_ms(fn):
        t0 = time.time()
        fn(row_d, col_d, val_d).block_until_ready()
        return (time.time() - t0) * 1e3

    # A/B-interleaved best-of-5, so host drift lands on both sides
    xla_ms = pallas_ms = float("inf")
    for _ in range(5):
        xla_ms = min(xla_ms, one_ms(xla_fn))
        pallas_ms = min(pallas_ms, one_ms(pl_fn))
    return {**device,
            "rows": batch_rows, "features": features, "nnz": nnz,
            "xla_ms": round(xla_ms, 3), "pallas_ms": round(pallas_ms, 3),
            "pallas_speedup": round(xla_ms / pallas_ms, 3),
            "pallas_rows_per_sec": round(batch_rows / (pallas_ms / 1e3), 1)}


def device_lane_probe(rows: int, batch_rows: int = 8192,
                      reps: int = 3) -> dict:
    """The device lane (doc/benchmarking.md "Device lane"): a tiny
    pre-jitted LinearLearner step consumes the device iterator on the
    accelerator. The warm epoch compiles every batch shape (its compile
    counts ARE the compile-churn evidence); the timed epochs then measure
    steady state and must see zero new shapes. Reports rows/s,
    `device_transfer_us` percentiles (log2-bucket upper bounds), the
    span-derived overlap ratio, compile counts, and the device-lane stall
    verdict. Child process only (`--device-lane`)."""
    import jax
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.models.linear import LinearLearner
    from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter,
                                               jax_profiler_capture)
    device = require_accelerator()
    path = ensure_dataset(rows)
    telemetry.reset()
    learner = LinearLearner(28, mesh=None, learning_rate=0.1)
    params = learner.init()

    def one_epoch(it, params):
        t0 = time.perf_counter()
        got = 0
        loss = None
        for batch in it:
            got += batch.total_rows
            params, loss = learner.step(params, batch)
        if loss is not None:
            loss.block_until_ready()
        dt = time.perf_counter() - t0
        assert got == rows, f"row count mismatch: {got} != {rows}"
        return dt, params

    with DeviceRowBlockIter(path, batch_rows=batch_rows, mesh=None,
                            layout="csr") as it:
        # warm epoch: every shape compiles here, on purpose — the
        # compile trail it leaves is the churn evidence
        _, params = one_epoch(it, params)
        snap = telemetry.snapshot(native=False)
        compile_events = sum(
            int(c["value"]) for c in snap["counters"]
            if c["name"] == "device_compile_events_total")
        jit_compiles = sum(
            int(c["value"]) for c in snap["counters"]
            if c["name"] == "device_jit_compiles_total")
        distinct = max((g["value"] for g in snap["gauges"]
                        if g["name"] == "device_distinct_shapes"),
                       default=0)
        # steady state: zeroed registry + span ring, warm jit cache; the
        # shape census is process-wide so a replay adds no new events
        telemetry.reset()
        dts = []
        with jax_profiler_capture() as profiled:
            for _ in range(reps):
                it.before_first()
                dt, params = one_epoch(it, params)
                dts.append(dt)
    dts.sort()
    dt = statistics.median(dts)
    snap = telemetry.snapshot(native=False)
    new_shapes = sum(1 for c in snap["counters"]
                     if c["name"] == "device_compile_events_total"
                     and c["value"])
    xfer = telemetry.histogram("device_transfer_us")
    block = telemetry.histogram("device_put_block_us")
    ratio = telemetry.device_overlap_ratio()
    # attribution needs the NATIVE half too: the parse_stage_* sums the
    # NET-stage subtraction rests on live in the native registry (the
    # batcher here is native) — a native=False snapshot would zero them
    # and degenerate every verdict to stage/transfer_bound
    att = telemetry.stall_attribution(telemetry.snapshot())
    dev_bytes = telemetry.counter("device_transfer_bytes_total").value
    out = {
        **device,
        "rows": rows,
        "batch_rows": batch_rows,
        "reps": len(dts),
        "hbm_ingest_rows_per_sec": round(rows / dt, 1),
        "spread_rows_per_sec": [round(rows / dts[-1], 1),
                                round(rows / dts[0], 1)],
        "device_bytes_per_sec": round(dev_bytes / sum(dts), 1),
        "device_transfer_p50_us": xfer.quantile(0.5),
        "device_transfer_p99_us": xfer.quantile(0.99),
        "device_put_block_p99_us": block.quantile(0.99),
        "overlap_ratio": round(ratio, 4) if ratio is not None else -1.0,
        "distinct_shapes": int(distinct),
        "compile_events_total": compile_events,
        "jit_compiles_total": jit_compiles,
        "steady_new_shapes": new_shapes,
        "stall_verdict": att["verdict"],
    }

    # zero-copy ingest bw-util (doc/benchmarking.md "Zero-copy ingest"):
    # replay the SAME rows from a warm transcoding shard cache
    # (#cachefile= sugar — epoch 2+ is mmap + one fused shard-major fill
    # per batch, no text parse) under a light full-touch consumer, so the
    # measured quantity is the ingest path the zero-copy device_put
    # serves rather than the text parser or the learner's compute. The
    # denominator is the best COPYING device_put of the SAME batch
    # sequence (misaligned_copy pins the probe off the aliasing fast
    # path), floored by the lane's own best epoch.
    import shutil
    import tempfile
    import numpy as np
    import jax.numpy as jnp
    cdir = tempfile.mkdtemp(prefix="dct_bench_zc_")
    curi = f"{path}#cachefile={cdir}"
    try:
        def misaligned_copy(v):
            # pin the probe tree at 32 (mod 64): np.empty-grade alignment
            # that can NEVER hit the 64-byte aliasing fast path, so the
            # denominator deterministically measures the copying transfer
            # (a luckily-64-aligned np.array copy would alias and report
            # impossible tens-of-GB/s "copy" bandwidth)
            raw = np.empty(v.nbytes + 64, np.uint8)
            off = (32 - raw.ctypes.data) % 64
            out = raw[off:off + v.nbytes].view(v.dtype).reshape(v.shape)
            out[...] = v
            return out

        host_trees = []
        with DeviceRowBlockIter(curi, batch_rows=batch_rows, mesh=None,
                                layout="csr", to_device=False) as hit:
            for b in hit:  # this first pass parses text AND tees the cache
                host_trees.append({k: misaligned_copy(np.asarray(v))
                                   for k, v in b.tree().items()})
        probe_bytes = sum(int(v.nbytes) for t in host_trees
                          for v in t.values())

        def put_sequence_sample(salt: int) -> float:
            # one timed COPYING device_put per batch of the epoch — the
            # denominator moves the SAME batch sequence at the SAME
            # granularity as the numerator, so the per-dispatch fixed cost
            # (jax Python dispatch is ~0.2 ms/call on this host, on the
            # order of the per-batch copy itself) appears on both sides of
            # the ratio instead of only taxing the numerator. Leaves are
            # salted before timing so no transfer-dedup layer can serve a
            # repeat from cache.
            for t in host_trees:
                for v in t.values():
                    flat = v.reshape(-1)
                    flat[:: max(1, 4096 // max(v.itemsize, 1))] = \
                        np.asarray(salt, dtype=v.dtype)
            t0 = time.perf_counter()
            landed = [jax.device_put(t) for t in host_trees]
            jax.block_until_ready(
                [v for t in landed for v in t.values()])
            return probe_bytes / (time.perf_counter() - t0)

        @jax.jit
        def consume(tree):
            # touch every array so the batch is fully materialized
            return sum(jnp.sum(v.astype(jnp.float32))
                       for v in tree.values())

        # prefetch=0: the synchronous ingest mode — on this measurement
        # there is nothing to overlap with (the consumer is the bench
        # itself), so double-buffer thread wakeups would only add
        # scheduler noise to the number
        with DeviceRowBlockIter(curi, batch_rows=batch_rows, mesh=None,
                                layout="csr", prefetch=0) as it:
            zc_bytes = 0
            for b in it:  # warm replay epoch: proves device consumability
                zc_bytes += sum(int(v.nbytes) for v in b.tree().values())
                consume(b.tree()).block_until_ready()
            # timed reps measure the INGEST path only — replay + fused
            # fill + device_put — mirrored by the denominator probe, a
            # bare copying device_put of the same batch sequence with no
            # consumer. Batches leave the pipeline READY (_device_put
            # blocks before queueing), so draining the iterator IS
            # bytes-landed-on-device. One epoch is a few milliseconds
            # here, far below this host's noise floor, so: sample MANY
            # whole epochs, INTERLEAVED A/B with the denominator's
            # copying samples (the idiom the telemetry overhead guard
            # pins) so host drift hits both sides of the ratio alike.
            # The headline util is MEDIAN/MEDIAN — the sustained ratio;
            # max-of-N on each side picks extreme order statistics that
            # need not come from the same machine state, so best/best is
            # reported alongside as the min-time-estimator view, not as
            # the headline.
            zbws, abws = [], []
            t_start = time.perf_counter()
            while len(zbws) < 3 * reps or \
                    time.perf_counter() - t_start < 0.6:
                it.before_first()
                t0 = time.perf_counter()
                for b in it:
                    pass
                zbws.append(zc_bytes / (time.perf_counter() - t0))
                abws.append(put_sequence_sample(len(abws)))
        landed_bw = statistics.median(zbws)
        best_bw = max(zbws)
        attain = max(abws)
        attain_med = statistics.median(abws)
        out["hbm_ingest_bw_util"] = round(
            landed_bw / max(attain_med, landed_bw, 1.0), 4)
        out["hbm_ingest_bw_util_best"] = round(
            best_bw / max(attain, best_bw, 1.0), 4)
        out["zero_copy_bytes_per_sec"] = round(landed_bw, 1)
        out["attainable_pytree_bytes_per_sec"] = round(attain, 1)
        snap = telemetry.snapshot(native=False)
        out["zero_copy_batches_total"] = sum(
            int(c["value"]) for c in snap["counters"]
            if c["name"] == "device_zero_copy_batches_total")
        out["zero_copy_fallbacks_total"] = sum(
            int(c["value"]) for c in snap["counters"]
            if c["name"] == "device_zero_copy_fallbacks_total")
    finally:
        shutil.rmtree(cdir, ignore_errors=True)
    if profiled:
        out["jax_profile_dir"] = os.environ.get("DMLC_JAX_PROFILE")
    return out


def _serve_scrape_metric(port: int, name: str) -> float:
    """Read one metric off the scoring server's ``/metrics`` endpoint
    (label series summed; 0.0 when absent)."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            total += float(line.split()[-1])
    return total


# the serving lane's model artifact, written by a child: the checkpoint
# layer imports jax, which this parent must not
_WRITE_LINEAR_MODEL = """
import sys
import numpy as np
from dmlc_core_tpu.serving.model import save_model
uri, features = sys.argv[1], int(sys.argv[2])
rng = np.random.default_rng(7)
save_model(uri, "linear",
           {"w": rng.normal(size=features).astype(np.float32),
            "b": np.float32(0.0)}, features)
"""


def run_serving_lane(args, sampler=None) -> dict:
    """Online scoring lane (doc/serving.md): the scoring server runs
    OUT of process (``python -m dmlc_core_tpu.serving``) on the device
    jax gives it, and a loadrig client in this parent drives ``POST
    /score`` with generated libsvm payloads of ragged sizes. Reported:
    sustained QPS (closed-loop), coordinated-omission-safe open-loop
    p50/p99/p999 on the intended-time clock at ~70% of sustained, the
    shed/error counts, the compile-census pin (``steady_new_shapes``
    must stay 0: the server compiles its bucket ladder before it says
    ready), and the device the server reports in ``/statz``. The
    host-resource sampler watches the server pid so the report
    attributes client vs server CPU."""
    import http.client
    import shutil
    import tempfile
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import loadrig

    features = 1 << 14
    tmp = tempfile.mkdtemp(prefix="bench-serving-")
    server = None
    try:
        uri = os.path.join(tmp, "model.ckpt")
        subprocess.run([sys.executable, "-c", _WRITE_LINEAR_MODEL, uri,
                        str(features)], check=True, cwd=REPO, timeout=300)
        errlog = open(os.path.join(tmp, "server.err"), "w+")
        server = subprocess.Popen(
            [sys.executable, "-m", "dmlc_core_tpu.serving",
             "--model-uri", uri, "--rows-buckets", "16,64,256",
             "--batch-delay-ms", "2", "--shed-lateness-ms", "500"],
            stdout=subprocess.PIPE, stderr=errlog, text=True, cwd=REPO)
        # ready comes after the bucket ladder compiled: cold, on the chip,
        # that is the slow part of this lane
        line = ""
        deadline = time.time() + 600
        while time.time() < deadline:
            line = server.stdout.readline()
            if line.startswith("SERVE_READY") or not line:
                break
        if not line.startswith("SERVE_READY"):
            errlog.seek(0)
            raise RuntimeError("serving server never came ready:\n"
                               + errlog.read()[-2000:])
        port = int(line.split("port=")[1].split()[0])
        if sampler is not None:
            sampler.watch("serving_server", server.pid)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            conn.request("GET", "/statz")
            device = json.loads(conn.getresponse().read())["device"]
        finally:
            conn.close()

        spec = (f"libsvm:rows=2,rows_max=8,features={features},"
                "nnz=16,seed=7")
        payload_fn, ctype = loadrig.score_payload_fn(spec)
        fn = loadrig.http_request_fn(
            f"http://127.0.0.1:{port}/score", method="POST",
            headers={"Content-Type": ctype}, payload_fn=payload_fn)
        shapes_warm = _serve_scrape_metric(port, "serve_distinct_shapes")
        loadrig.closed_loop(fn, workers=2,
                            duration_s=1.0 if args.smoke else 3.0)
        sustained = loadrig.closed_loop(
            fn, workers=8, duration_s=2.0 if args.smoke else 6.0)
        sustained_qps = sustained["achieved_qps"]
        open_out = loadrig.open_loop(
            fn, qps=max(1.0, 0.7 * sustained_qps),
            duration_s=2.0 if args.smoke else 8.0, max_inflight=64)
        shapes_steady = _serve_scrape_metric(port,
                                             "serve_distinct_shapes")
        shed_total = (
            _serve_scrape_metric(port, "serve_shed_total") or
            open_out["shed"])
        # SLO hygiene pin: a healthy server at 0.7x sustained open-loop
        # must never page — any fast-burn trip here is a regression
        # (scripts/benchdiff.py carries slo_burn_clean LOWER-is-better;
        # good runs report 0, and a non-zero count fails the lane loudly)
        burn_trips = _serve_scrape_metric(port, "slo_page_trips_total")
        if burn_trips:
            raise RuntimeError(
                f"SLO page tripped {int(burn_trips)}x during the 0.7x "
                "open-loop phase — a healthy server must not burn")
        server.send_signal(signal.SIGTERM)
        if server.wait(60) != 0:
            raise RuntimeError(
                f"serving server exited {server.returncode} on SIGTERM")
        ii = open_out["intended_us"]
        return {
            **device,
            "sustained_qps": round(sustained_qps, 1),
            "open_loop_qps": open_out["achieved_qps"],
            "open_loop_p50_ms": round(ii["p50"] / 1e3, 2),
            "open_loop_p99_ms": round(ii["p99"] / 1e3, 2),
            "open_loop_p999_ms": round(ii["p999"] / 1e3, 2),
            "service_p99_ms": round(
                open_out["service_us"]["p99"] / 1e3, 2),
            "completed": open_out["completed"],
            "errors": open_out["errors"],
            "client_shed": open_out["shed"],
            "server_shed": shed_total,
            "distinct_shapes": int(shapes_steady),
            "steady_new_shapes": int(shapes_steady - shapes_warm),
            "slo_burn_clean": int(burn_trips),
        }
    finally:
        if server is not None and server.poll() is None:
            server.kill()
            server.wait(10)
        shutil.rmtree(tmp, ignore_errors=True)


def mesh_lane_probe(smoke: bool = False) -> dict:
    """Elastic mesh training lane (doc/robustness.md "Elastic mesh
    training"): a real 2-process ``jax.distributed`` world under the
    in-process tracker, stepped by tests/mesh_worker.py — lease acquire,
    cross-process KV allgather, lease complete, every step.

    Two numbers ride the regression ledger (scripts/benchdiff.py
    ``mesh_lane`` — the MULTICHIP_r* dryrun series promoted from
    pass/fail droppings to measured metrics):

    - ``steps_per_sec``: steady-state collective steps/s of an
      uninterrupted world, measured between the first and last progress
      beat of rank 0 so world bring-up (jax.distributed init, tracker
      link dance) is excluded;
    - ``recovery_s``: SIGKILL one rank mid-step of a supervised world
      and measure wall clock from the kill to the FIRST step the
      relaunched world writes — recovery-time-to-first-resumed-step
      (failure detection + world teardown + fresh coordinator + rejoin).
      Lower is better; benchdiff inverts the ratio for it.
    """
    import shutil
    import signal
    import tempfile
    import threading

    from dmlc_core_tpu.tracker import rendezvous

    worker = os.path.join(REPO, "tests", "mesh_worker.py")
    nworkers = 2
    root = tempfile.mkdtemp(prefix="meshlane_", dir=CACHE_DIR)
    # the tracker runs in-process: its liveness knobs come from OUR env
    os.environ.setdefault("DMLC_TRACKER_RECOVER_GRACE_MS", "300")

    def read_progress(pdir, rank):
        try:
            with open(os.path.join(pdir, f"rank{rank}.progress")) as f:
                step, pid = f.read().split()
            return int(step), int(pid)
        except (OSError, ValueError):
            return None

    def run_world(tag, steps_by_attempt, step_sleep, dead_after_ms,
                  world_attempts, driver):
        """One tracked world; `driver(pdir_of, procs_by_attempt)` runs on
        the monitor side while run_job owns the tracker thread."""
        procs_by_attempt = []

        def pdir_of(att):
            d = os.path.join(root, f"{tag}{att}")
            os.makedirs(d, exist_ok=True)
            return d

        def launch(nw, ns, envs, tracker=None):
            att = int(envs.get("DMLC_WORLD_ATTEMPT", "0"))
            n = steps_by_attempt[min(att, len(steps_by_attempt) - 1)]
            env = dict(os.environ)
            env.update({k: str(v) for k, v in envs.items()})
            env.update({
                "DMLC_ROLE": "worker", "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": REPO,
                "DMLC_STEP_DEADLINE_MS": str(dead_after_ms)})
            ps = []
            for i in range(nw):
                ps.append(subprocess.Popen(
                    [sys.executable, worker, pdir_of(att), str(n),
                     str(step_sleep)],
                    env=dict(env, DMLC_TASK_ID=str(i)),
                    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
            procs_by_attempt.append(ps)

            def stop():
                for p in ps:
                    if p.poll() is None:
                        p.kill()
            return stop

        errs = []

        def run():
            try:
                rendezvous.run_job(
                    nworkers, 0, launch, host_ip="127.0.0.1",
                    heartbeat_ms=150, dead_after_ms=dead_after_ms,
                    num_shards=2 * nworkers, mesh=True,
                    world_attempts=world_attempts)
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        th = threading.Thread(target=run, daemon=True)
        th.start()
        ok = False
        try:
            out = driver(pdir_of, procs_by_attempt)
            ok = True
        finally:
            # after a successful drive, let the world finish CLEANLY
            # (killing a worker mid-shutdown reads as a lost rank and
            # aborts the very run just measured); on a failed drive,
            # kill immediately
            grace = time.monotonic() + (90 if ok else 0)
            for ps in procs_by_attempt:
                for p in ps:
                    if p.poll() is None:
                        try:
                            p.wait(timeout=max(0.0,
                                               grace - time.monotonic()))
                        except subprocess.TimeoutExpired:
                            pass
                    if p.poll() is None:
                        p.kill()
            th.join(timeout=60)
        if errs:
            raise errs[0]
        if th.is_alive():
            raise RuntimeError(f"mesh lane: {tag} tracker never finished")
        return out

    try:
        # -- phase 1: uninterrupted steps/s -------------------------------
        steps = 20 if smoke else 60

        def timed(pdir_of, procs):
            pdir = pdir_of(0)
            beats = []  # (monotonic, step) — one entry per step change
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                got = read_progress(pdir, 0)
                if got is not None and (not beats
                                        or got[0] != beats[-1][1]):
                    beats.append((time.monotonic(), got[0]))
                    if got[0] >= steps - 1:
                        break
                time.sleep(0.002)
            (t1, s1), (t2, s2) = beats[0], beats[-1]
            if s2 <= s1 or t2 <= t1:
                raise RuntimeError(f"mesh lane: no steady window "
                                   f"({beats[:3]}...)")
            return (s2 - s1) / (t2 - t1)

        steps_per_sec = run_world("steady", [steps], 0.0, 2000, 0, timed)

        # -- phase 2: SIGKILL -> relaunch -> first resumed step -----------
        dead_after_ms = 1000

        def chaos(pdir_of, procs):
            p0 = pdir_of(0)
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                got = [read_progress(p0, r) for r in range(nworkers)]
                if all(g is not None and g[0] >= 1 for g in got):
                    break
                time.sleep(0.005)
            else:
                raise RuntimeError("mesh lane: attempt 0 never progressed")
            t_kill = time.monotonic()
            os.kill(got[0][1], signal.SIGKILL)
            p1 = pdir_of(1)
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if any(read_progress(p1, r) is not None
                       for r in range(nworkers)):
                    return time.monotonic() - t_kill
                time.sleep(0.005)
            raise RuntimeError("mesh lane: world never resumed")

        recovery_s = run_world("chaos", [100000, 3], 0.05, dead_after_ms,
                               2, chaos)

        # CPU by design: this lane measures the tracker's control plane
        # (detection, relaunch, KV-store collective cadence) in a
        # two-process world, and two JAX processes cannot share a chip
        return {"platform": "cpu", "scope": "control-plane only",
                "steps_per_sec": round(steps_per_sec, 1),
                "recovery_s": round(recovery_s, 3),
                "nworkers": nworkers, "steps": steps,
                "dead_after_ms": dead_after_ms}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def attainable_contiguous_bw(sharding, nbytes: int) -> float:
    """Best host->device bandwidth (B/s) for one large contiguous buffer
    under the pipeline's sharding: the optimistic ceiling. The buffer is
    mutated between reps so no transfer-dedup/caching layer can serve a
    repeat from memory and inflate the ceiling."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    if isinstance(sharding, dict):
        # per-leaf sharding of the packed batch tree: the 1-D probe buffer
        # needs a plain leading-axis spec over the SAME mesh so the
        # multi-chip ceiling still measures D parallel DMAs
        any_leaf = next(iter(sharding.values()))
        sharding = NamedSharding(any_leaf.mesh, PartitionSpec("data"))
    ndev = 1
    if sharding is not None:
        ndev = int(np.prod([d for d in sharding.mesh.devices.shape]))
    n = max(nbytes // 4, 1 << 20)
    n -= n % max(ndev, 1)  # divisible by the device count for P("data")
    buf = np.empty(n, np.float32)
    buf.fill(1.0)
    best = 0.0
    for i in range(3):
        buf[:: 4096 // 4] = float(i)  # dirty one word per page
        t0 = time.time()
        arr = (jax.device_put(buf, sharding) if sharding is not None
               else jax.device_put(buf))
        arr.block_until_ready()
        dt = time.time() - t0
        best = max(best, buf.nbytes / dt)
        del arr
    return best


def pytree_put_sample(host_tree, sharding, salt: int) -> float:
    """One timed host->device transfer of the whole pytree: bandwidth in
    B/s for a single device_put + block_until_ready. Arrays are mutated
    (`salt`) before the put to defeat transfer caching."""
    import numpy as np
    import jax
    nbytes = sum(int(v.nbytes) for v in host_tree.values())
    for v in host_tree.values():
        flat = v.reshape(-1)
        flat[:: max(1, 4096 // max(v.itemsize, 1))] = \
            np.asarray(salt, dtype=v.dtype)
    t0 = time.time()
    tree = (jax.device_put(host_tree, sharding) if sharding is not None
            else jax.device_put(host_tree))
    jax.block_until_ready(list(tree.values()))
    dt = time.time() - t0
    del tree
    return nbytes / dt


def attainable_pytree_bw(host_tree, sharding) -> float:
    """Best host->device bandwidth (B/s) for the SAME pytree of arrays the
    pipeline lands per batch — the honest denominator for bw-util (the
    per-array dispatch overhead is part of what a real batch pays)."""
    return max(pytree_put_sample(host_tree, sharding, i) for i in range(3))


def tree_nbytes(batch) -> int:
    return sum(int(v.nbytes) for v in batch.tree().values())


def run_e2e_epoch(it, rows, consume):
    """One timed end-to-end pass over a (restarted) iterator; returns
    (seconds, device_bytes)."""
    import time as _t
    t0 = _t.time()
    got = 0
    device_bytes = 0
    acc = None
    for batch in it:
        got += batch.total_rows  # host-side count: no device sync
        device_bytes += tree_nbytes(batch)
        acc = consume(batch.tree())
    if acc is not None:
        acc.block_until_ready()
    dt = _t.time() - t0
    assert got == rows, f"row count mismatch: {got} != {rows}"
    return dt, device_bytes


def run_lane(path, rows, fmt, args, mesh, consume):
    """Median-of-reps e2e lane; returns a metrics dict."""
    import numpy as np
    from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter

    # grab one HOST batch for the pytree ceiling
    host_tree = None
    with DeviceRowBlockIter(path, fmt=fmt, batch_rows=args.batch_rows,
                            mesh=mesh, nthread=args.threads,
                            dense_dtype=args.dense_dtype,
                            to_device=False) as hit:
        for batch in hit:
            host_tree = {k: np.asarray(v) for k, v in batch.tree().items()}
            break
    # ONE iterator for warm + timed reps: the warm epoch compiles every
    # batch shape, faults the page cache, and primes the recycle pool that
    # lives in the batcher — reps then measure steady state
    with DeviceRowBlockIter(path, fmt=fmt, batch_rows=args.batch_rows,
                            mesh=mesh, nthread=args.threads,
                            dense_dtype=args.dense_dtype) as it:
        for batch in it:
            consume(batch.tree()).block_until_ready()
        sharding = it.sharding
        # fast lanes (binary ingest epochs run in tens of ms) need more
        # samples for a stable median: auto-scale toward ~1s of timed work
        # based on the FIRST STEADY epoch (the warm epoch includes compile
        # and first-transfer costs and would never trigger the scale).
        # Auto capped at 15; an explicit larger --reps is always honored.
        it.before_first()
        runs = [run_e2e_epoch(it, rows, consume)]
        reps = max(args.reps, min(15, int(0.75 / max(runs[0][0], 1e-3))))
        for _ in range(reps - 1):
            it.before_first()
            runs.append(run_e2e_epoch(it, rows, consume))
    dts = sorted(dt for dt, _ in runs)
    device_bytes = runs[0][1]
    dt = statistics.median(dts)

    landed_bw = device_bytes / dt
    best_bw = device_bytes / dts[0]
    attain_pytree = attainable_pytree_bw(host_tree, sharding)
    attain_contig = attainable_contiguous_bw(
        sharding, min(device_bytes, 256 << 20))
    # the denominator is the best observed host->HBM capability from ANY
    # probe — including the pipeline's own best epoch. The probes are as
    # exposed to host noise as the pipeline; taking the max keeps the
    # ratio honest (a probe hit by a stall must not inflate utilization
    # past 1) and degrades to the pytree probe on quiet hosts.
    denom = max(attain_pytree, attain_contig, best_bw, 1.0)
    util = landed_bw / denom
    # best-epoch utilization answers the capability question ("can this
    # lane saturate the link") separately from the median ("does it,
    # typically")
    util_best = best_bw / denom
    return {
        "dt": dt,
        "reps": len(runs),
        "rows_per_sec": rows / dt,
        "spread_rows_per_sec": [round(rows / dts[-1], 1),
                                round(rows / dts[0], 1)],
        "hbm_ingest_bw_util": round(util, 4),
        "hbm_ingest_bw_util_best": round(util_best, 4),
        "device_bytes_per_sec": round(landed_bw, 1),
        "attainable_pytree_bytes_per_sec": round(attain_pytree, 1),
        "attainable_contiguous_bytes_per_sec": round(attain_contig, 1),
    }


def _occupancy_row(stats: dict) -> dict:
    return {k: stats[k] for k in
            ("occupancy_avg", "inflight_peak", "capacity", "workers",
             "chunks_read", "reader_waits", "worker_waits",
             "consumer_waits", "simd_lane") if k in stats}


def _stall_extras() -> dict:
    """Stall attribution from the span-backed stage histograms of THIS
    process (telemetry.stall_attribution, doc/observability.md):
    per-stage occupancy + a fill/parse/consumer/transfer-bound verdict
    derived from the same spans the tracker's /trace serves — plus the
    per-stage parse latency means that name where the host time went."""
    from dmlc_core_tpu import telemetry
    att = telemetry.stall_attribution()
    out = {"stall_attribution": {
        "verdict": att["verdict"],
        "occupancy": {k: round(v, 4) for k, v in att["occupancy"].items()},
        "stage_ms": {k: round(v / 1e3, 1)
                     for k, v in att["stage_us"].items()}}}
    stage_mean_ms = {}
    for h in telemetry.snapshot(native=True)["histograms"]:
        if h["name"].startswith("parse_stage_") and h["count"]:
            stage = h["name"][len("parse_stage_"):-len("_us")]
            stage_mean_ms[stage] = round(h["sum"] / h["count"] / 1e3, 3)
    if stage_mean_ms:
        out["parse_stage_mean_ms"] = stage_mean_ms
    return out


def e2e_lane(args, rows: int) -> dict:
    """One ingest lane end to end, in this (child) process:
    ``{"rows_per_sec", "dt", "host_rows_per_sec", "extras"}`` for
    ``--format``. With --parse-only the lane stops at the host batch and
    never initialises a jax backend; otherwise batches land on the
    accelerator under a data mesh and the extras carry the device it ran
    on."""
    from dmlc_core_tpu import telemetry
    lane_fmt = args.format
    lane_path = (ensure_dataset(rows) if lane_fmt == "libsvm"
                 else dict(BINARY_LANES)[lane_fmt](rows))
    single_core = (os.cpu_count() or 1) <= 1
    if args.parse_only:
        stats = {}
        rps, dt = parse_rows_per_sec(lane_path, rows, args.threads,
                                     fmt=lane_fmt,
                                     dense_dtype=args.dense_dtype,
                                     stats_out=stats)
        extras = _stall_extras()
        if stats:
            extras["parse_pipeline_occupancy"] = {
                "headline": _occupancy_row(stats)}
            extras["parse_simd_lane"] = stats.get("simd_lane", "scalar")
        # one core serializes every stage: the occupancy split is still
        # reported, but no verdict can promise overlap
        extras["bottleneck"] = ("host_cpu_serialized_single_core"
                                if single_core else
                                extras["stall_attribution"]["verdict"])
        return {"rows_per_sec": rps, "dt": dt, "host_rows_per_sec": rps,
                "extras": extras}

    import jax
    import jax.numpy as jnp
    from dmlc_core_tpu.tpu.sharding import data_mesh

    mesh = data_mesh()
    device = require_accelerator(mesh)
    # the lane's HOST half alone (deserialize for rec, batch assembly for
    # crec/recd, parse for text), best of 2 — what the device half is
    # compared against
    host_rps = max(parse_rows_per_sec(lane_path, rows, args.threads,
                                      fmt=lane_fmt,
                                      dense_dtype=args.dense_dtype)[0]
                   for _ in range(2))
    telemetry.reset()

    @jax.jit
    def consume(tree):
        # touch every array so the batch is fully materialized in HBM
        return sum(jnp.sum(v.astype(jnp.float32)) for v in tree.values())

    lane = run_lane(lane_path, rows, lane_fmt, args, mesh, consume)
    extras = {
        **device,
        "hbm_ingest_bw_util": lane["hbm_ingest_bw_util"],
        "hbm_ingest_bw_util_best": lane["hbm_ingest_bw_util_best"],
        "device_bytes_per_sec": lane["device_bytes_per_sec"],
        "attainable_pytree_bytes_per_sec":
            lane["attainable_pytree_bytes_per_sec"],
        "attainable_contiguous_bytes_per_sec":
            lane["attainable_contiguous_bytes_per_sec"],
        "e2e_spread_rows_per_sec": lane["spread_rows_per_sec"],
        "reps": lane["reps"],
        "ncores": os.cpu_count(),
        **_stall_extras(),
    }
    if lane["hbm_ingest_bw_util"] < 0.9:
        extras["bottleneck"] = (
            "host_cpu_serialized_single_core" if single_core
            else extras["stall_attribution"]["verdict"])
        print(f"# bw-util {lane['hbm_ingest_bw_util']:.1%}: landed "
              f"{lane['device_bytes_per_sec'] / 1e6:.0f} MB/s vs "
              f"pytree-attainable "
              f"{lane['attainable_pytree_bytes_per_sec'] / 1e6:.0f} MB/s"
              f" (contiguous "
              f"{lane['attainable_contiguous_bytes_per_sec'] / 1e6:.0f}"
              f" MB/s) -> {extras['bottleneck']} on "
              f"{os.cpu_count()} core(s)", file=sys.stderr)
    return {"rows_per_sec": lane["rows_per_sec"], "dt": lane["dt"],
            "host_rows_per_sec": host_rps, "extras": extras}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny quick run")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--parse-only", action="store_true",
                    help="host-only metrics: every lane stops at the host "
                         "batch, no jax backend is initialised, and the "
                         "device, serving and mesh lanes are skipped")
    ap.add_argument("--batch-rows", type=int, default=65536)
    ap.add_argument("--threads", type=int, default=0,
                    help="parse workers (default 0 = one per core: "
                         "measured on the 2-core bench host, oversubscribed "
                         "workers cost ~2x on the CPU-bound local-file lane "
                         "— 4 workers on 2 cores thrash where 2 scale)")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed e2e repetitions; the median is reported")
    ap.add_argument("--format", choices=("libsvm", "rec", "crec", "recd"),
                    default="libsvm",
                    help="headline lane: text parse, binary CSR row "
                         "blocks, CSR device planes, or zero-parse dense "
                         "row matrices")
    ap.add_argument("--dense-dtype", choices=("bf16", "f32"), default="bf16",
                    help="dense device dtype (bf16 halves host+HBM bytes)")
    ap.add_argument("--no-scaling-table", action="store_true")
    ap.add_argument("--no-rec-lane", action="store_true",
                    help="skip the secondary binary-ingest lanes")
    ap.add_argument("--no-ledger", action="store_true",
                    help="skip appending this run to bench_history.jsonl"
                         " (doc/benchmarking.md; DMLC_BENCH_HISTORY "
                         "overrides the path, =0 disables)")
    # child modes: one lane, in a process of its own (see run_child)
    for flag in ("--e2e-lane", "--pallas-probe", "--device-lane",
                 "--mesh-lane"):
        ap.add_argument(flag, action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    rows = args.rows or (20000 if args.smoke else 200000)
    dense_flag = args.dense_dtype
    args.dense_dtype = "bfloat16" if dense_flag == "bf16" else "float32"
    if args.pallas_probe:
        print(json.dumps(pallas_format_probe()))
        return
    if args.device_lane:
        print(json.dumps(device_lane_probe(rows)))
        return
    if args.mesh_lane:
        print(json.dumps(mesh_lane_probe(smoke=args.smoke)))
        return
    if args.e2e_lane:
        print(json.dumps(e2e_lane(args, rows)))
        return

    # provenance header (doc/benchmarking.md): every run names the tree,
    # host, and env knobs it measured, first thing — a number without
    # them is not reproducible
    provenance = git_provenance()
    host = host_fingerprint()
    env_over = dmlc_env_overrides()
    sha12 = (provenance["git_sha"] or "unknown")[:12]
    print(f"# provenance: sha={sha12}"
          f"{'+dirty' if provenance['git_dirty'] else ''} "
          f"host={host['host']} cpus={host['cpus']} "
          f"(affinity {host['affinity']}) mem={host['mem_gb']}G "
          f"python={host['python']}", file=sys.stderr)
    if env_over:
        print("# env overrides: "
              + " ".join(f"{k}={v}" for k, v in env_over.items()),
              file=sys.stderr)
    # host resource sampler: every lane's CPU/RSS/page-cache/net
    # envelope rides extras.host_resources — the evidence side of any
    # "the host was the bottleneck" verdict
    from dmlc_core_tpu.telemetry import HostResourceSampler
    sampler = HostResourceSampler().start()

    path = ensure_dataset(rows)
    # the headline lane's own file: text for libsvm, converted for the
    # binary lanes — every reported number uses this file. Converted here
    # so the children find it in the cache.
    lane_fmt = args.format
    lane_path = (path if lane_fmt == "libsvm"
                 else dict(BINARY_LANES)[lane_fmt](rows))
    size_mb = os.path.getsize(lane_path) / 1e6

    from dmlc_core_tpu.io.native import NativeParser

    # warm: build/load the native lib outside the timed region, and
    # before any child could race to build it
    with NativeParser(path) as p:
        p.next_block()

    extras = {}
    if not args.no_scaling_table and lane_fmt not in ("recd", "crec"):
        # recd/crec have no parse stage to thread-scale (ingest is framing
        # + memcpy on one staging thread): the table would be four
        # identical passes, so it is omitted for those lanes. Extended to
        # 8 threads so scaling regressions past the 4-worker point stay
        # visible; per-stage pipeline occupancy (reader/worker/consumer
        # waits, avg chunks in flight) rides along so a flat row is
        # attributable to a stage, not a guess.
        scaling = {}
        occupancy = {}
        for t in (1, 2, 4, 8):
            stats = {}
            with sampler.section(f"thread_scaling_{t}"):
                scaling[str(t)] = round(
                    parse_rows_per_sec(lane_path, rows, t, fmt=lane_fmt,
                                       stats_out=stats)[0], 1)
            if stats:
                occupancy[str(t)] = _occupancy_row(stats)
        extras["thread_scaling"] = scaling
        if occupancy:
            extras["parse_pipeline_occupancy"] = occupancy

    # what every e2e child shares with this run
    lane_argv = ["--e2e-lane", f"--rows={rows}",
                 f"--batch-rows={args.batch_rows}",
                 f"--threads={args.threads}", f"--reps={args.reps}",
                 "--dense-dtype", dense_flag]
    if args.parse_only:
        lane_argv.append("--parse-only")
    side_lanes = args.format == "libsvm"
    device_lanes = side_lanes and not args.parse_only

    with sampler.section("headline"):
        head = run_child(f"{lane_fmt} lane",
                         lane_argv + [f"--format={lane_fmt}"], timeout=900)
    rps, dt = head["rows_per_sec"], head["dt"]
    occupancy = head["extras"].pop("parse_pipeline_occupancy", {})
    extras.update(head["extras"])
    if occupancy:
        extras.setdefault("parse_pipeline_occupancy", {}).update(occupancy)

    # secondary lanes (north-star isolation): binary CSR row blocks, CSR
    # device planes and zero-parse dense row matrices, each a child with
    # the chip to itself the way a real job would see it
    if side_lanes and not args.no_rec_lane:
        extras["host_lane_rates"] = {}
        for fmt2, ensure in BINARY_LANES:
            ensure(rows)
            with sampler.section(f"{fmt2}_lane"):
                child = run_child(f"{fmt2} lane",
                                  lane_argv + [f"--format={fmt2}"],
                                  timeout=900)
            extras["host_lane_rates"][fmt2] = round(
                child["host_rows_per_sec"], 1)
            if args.parse_only:
                continue
            ce = child["extras"]
            extras[fmt2 + "_lane"] = {
                "rows_per_sec": round(child["rows_per_sec"], 1),
                **{k: ce[k] for k in (
                    "platform", "device_kind", "device_count", "mesh",
                    "hbm_ingest_bw_util", "hbm_ingest_bw_util_best",
                    "device_bytes_per_sec",
                    "attainable_pytree_bytes_per_sec",
                    "e2e_spread_rows_per_sec", "reps")}}
            print(f"# {fmt2} lane ({ce['platform']}): "
                  f"{child['rows_per_sec']:.0f} rows/s, "
                  f"bw-util {ce['hbm_ingest_bw_util']:.1%} "
                  f"(best {ce['hbm_ingest_bw_util_best']:.1%})",
                  file=sys.stderr)
        print(f"# host lane rates: {extras['host_lane_rates']}",
              file=sys.stderr)

    # on-device CSR->dense formatting, Pallas kernel vs XLA scatter-add
    if not args.parse_only:
        extras["pallas_csr_to_dense"] = run_child(
            "pallas probe", ["--pallas-probe"], timeout=600)
        print(f"# pallas csr->dense: {extras['pallas_csr_to_dense']}",
              file=sys.stderr)

    # the device lane: a pre-jitted model step consuming the device
    # iterator
    if device_lanes:
        with sampler.section("device_lane"):
            dl = extras["device_lane"] = run_child(
                "device lane", ["--device-lane", f"--rows={rows}"],
                timeout=300 if args.smoke else 600)
        print(f"# device lane ({dl['platform']}): "
              f"{dl['hbm_ingest_rows_per_sec']:.0f} rows/s, "
              f"transfer p50 {dl['device_transfer_p50_us']:.0f}us "
              f"p99 {dl['device_transfer_p99_us']:.0f}us, overlap "
              f"{dl['overlap_ratio']:.0%}, {dl['distinct_shapes']} "
              f"shape(s), {dl['jit_compiles_total']} compile(s), "
              f"{dl['steady_new_shapes']} steady-state new shapes "
              f"-> {dl['stall_verdict']}", file=sys.stderr)

    # elastic mesh training lane (doc/robustness.md "Elastic mesh
    # training"): collective steps/s of a real 2-process jax.distributed
    # world under the tracker, and recovery-time-to-first-resumed-step
    # after a SIGKILL world relaunch. The one lane this parent pins to the
    # CPU backend, by design: it measures the control plane, not device
    # math, its two-process world could not share a chip, it emits no
    # device-named metric, and its JSON says "platform": "cpu".
    if device_lanes:
        with sampler.section("mesh_lane"):
            ml = extras["mesh_lane"] = run_child(
                "mesh lane",
                ["--mesh-lane"] + (["--smoke"] if args.smoke else []),
                timeout=300 if args.smoke else 600,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
        print(f"# mesh lane ({ml['platform']}, {ml['scope']}): "
              f"{ml['steps_per_sec']:.1f} collective "
              f"steps/s ({ml['nworkers']} procs, {ml['steps']} "
              f"steps), SIGKILL recovery to first resumed step "
              f"{ml['recovery_s']:.2f}s "
              f"(dead-after {ml['dead_after_ms']}ms)",
              file=sys.stderr)

    # online scoring lane (doc/serving.md): out-of-process scoring
    # server (it gets the chip) driven by a loadrig POST client —
    # sustained QPS plus coordinated-omission-safe open-loop percentiles
    # ride the ledger (scripts/benchdiff.py serving_lane; sustained_qps
    # GOOD, open_loop_p99_ms LOW)
    if device_lanes:
        with sampler.section("serving_lane"):
            sl = extras["serving_lane"] = run_serving_lane(args, sampler)
        print(f"# serving lane ({sl['platform']}): "
              f"{sl['sustained_qps']:.0f} sustained "
              f"qps; open-loop @{sl['open_loop_qps']:.0f} qps "
              f"p50/p99/p999 {sl['open_loop_p50_ms']:.1f}/"
              f"{sl['open_loop_p99_ms']:.1f}/"
              f"{sl['open_loop_p999_ms']:.1f} ms (intended-time), "
              f"{sl['errors']} errors, "
              f"{sl['steady_new_shapes']} steady-state new shapes",
              file=sys.stderr)

    baseline = _load_baseline()  # one read serves the parity ratios + vs

    # the remaining BASELINE.md target rows: csv-with-prefetch MB/s,
    # libfm rows/s, and the RecordIO write+read round-trip — pure HOST
    # probes (no device stage), run in this parent on every kind of run
    if side_lanes:
        # parse-once-serve-many lane (shard cache, doc/caching.md):
        # epoch-1 transcode rate, epoch-2 mmap replay rate, and the
        # ROADMAP ratio against the recd binary host lane
        with sampler.section("cache_lane"):
            cl = extras["cache_lane"] = cache_lane_probe(path, rows,
                                                         args.threads)
        recd = extras.get("host_lane_rates", {}).get("recd")
        if recd:
            cl["vs_recd_host"] = round(cl["epoch2_rows_per_sec"] / recd, 3)
        print(f"# cache lane: epoch1 {cl['epoch1_rows_per_sec']:.0f} "
              f"rows/s -> epoch2 {cl['epoch2_rows_per_sec']:.0f} rows/s "
              f"({cl['replay_speedup']}x replay"
              + (f", {cl['vs_recd_host']}x recd host"
                 if "vs_recd_host" in cl else "")
              + ")", file=sys.stderr)
        # parallel ranged remote reads lane (doc/io-ranged.md): mock-S3
        # ingest under injected per-request/per-block latency — sequential
        # vs ranged vs local as ratios, plus what the readahead scheduler
        # chose
        with sampler.section("remote_lane"):
            rl = extras["remote_lane"] = remote_lane_probe(
                path, args.threads, latency_ms=20,
                cap_bytes=(2 << 20) if args.smoke else (8 << 20),
                concurrency=8 if args.smoke else 12,
                sampler=sampler)
        print(f"# remote lane: local {rl['local_rows_per_sec']:.0f} "
              f"rows/s, sequential {rl['sequential_rows_per_sec']:.0f}"
              f", ranged {rl['ranged_rows_per_sec']:.0f} "
              f"({rl['ranged_vs_sequential']}x seq, "
              f"{rl['ranged_vs_local']}x local, latency hidden "
              f"{rl['latency_hidden']:.0%} of the origin ceiling "
              f"{rl['origin_ceiling_rows_per_sec']:.0f}; "
              f"{rl['origin']['workers']}-worker origin "
              f"{rl['origin']['origin_cpu_s']}s CPU vs client "
              f"{rl['origin']['client_cpu_s']}s -> "
              f"{rl['origin']['cpu_attribution']}; "
              f"scheduler {rl['range_scheduler']})", file=sys.stderr)
        with sampler.section("csv_lane"):
            extras["csv_lane"] = text_lane_probe(
                ensure_csv_dataset(rows), rows, args.threads, "csv",
                "?format=csv&label_column=0")
        with sampler.section("libfm_lane"):
            extras["libfm_lane"] = text_lane_probe(
                ensure_libfm_dataset(rows), rows, args.threads, "libfm")
        with sampler.section("recordio_roundtrip"):
            extras["recordio_roundtrip"] = recordio_roundtrip_probe(
                records=20000 if args.smoke else 200000,
                native=not args.smoke)
        # parity ratios vs the same-machine reference build
        # (bench_baseline.json parity_rows, measured by
        # scripts/ref_bench.cc; the recordio row is engine-level on both
        # sides there — the probe above measures the Python binding)
        pr = (baseline or {}).get("parity_rows") or {}
        ref_csv = pr.get("reference_csv_mb_per_sec")
        ref_fm = pr.get("reference_libfm_rows_per_sec")
        if ref_csv:
            extras["csv_lane"]["vs_reference"] = round(
                extras["csv_lane"]["mb_per_sec"] / ref_csv, 3)
        if ref_fm:
            extras["libfm_lane"]["vs_reference"] = round(
                extras["libfm_lane"]["rows_per_sec"] / ref_fm, 3)
        ref_rt = pr.get("reference_recordio_rt_records_per_sec")
        ours_rt = extras["recordio_roundtrip"].get(
            "native_records_per_sec")
        if ref_rt and ours_rt:
            extras["recordio_roundtrip"]["vs_reference_native"] = \
                round(ours_rt / ref_rt, 3)
        print(f"# csv {extras['csv_lane']['mb_per_sec']} MB/s, "
              f"libfm {extras['libfm_lane']['rows_per_sec']:.0f} "
              f"rows/s, recordio rt "
              f"{extras['recordio_roundtrip']['records_per_sec']:.0f} "
              f"rec/s", file=sys.stderr)

    vs = None
    if baseline is not None and lane_fmt == "libsvm":
        # the recorded baseline is the reference's TEXT parse-to-host rate;
        # the rec lane has no reference analog, so it reports no ratio
        # (scale: baseline measured on the 200k dataset; rows/s is
        # size-stable)
        vs = round(rps / baseline["reference_rows_per_sec"], 3)

    # io_retry keeps its legacy key spelling (derived from the io_*_total
    # counters) and covers THIS process only — the remote lane's
    # parse-client subprocesses report their own retry noise in
    # extras.remote_lane.client_io_retry, so this row is zeros unless
    # some in-process path touched remote I/O
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.io.native import _LEGACY_IO_STAT_NAMES
    counters = {c["name"]: c["value"]
                for c in telemetry.snapshot(native=True)["counters"]
                if not c["labels"]}
    extras["io_retry"] = {legacy: int(counters.get(name, 0))
                          for legacy, name in _LEGACY_IO_STAT_NAMES}

    # the run-wide resource envelope + per-lane sections (the rig's
    # evidence plane, doc/benchmarking.md) and this run's provenance
    extras["host_resources"] = {"overall": sampler.stop(),
                                "lanes": sampler.sections}
    extras["provenance"] = {**provenance, "host": host,
                            "env_overrides": env_over}

    print(f"# {rows} rows ({size_mb:.1f} MB {lane_fmt}) in {dt:.3f}s = "
          f"{size_mb / dt:.1f} MB/s (median of "
          f"{extras.get('reps', args.reps)})", file=sys.stderr)
    result = {
        "metric": f"higgs_{lane_fmt}_ingest_rows_per_sec",
        "value": round(rps, 1),
        "unit": "rows/s",
        "vs_baseline": vs,
        "extras": extras,
    }
    print(json.dumps(result))

    # bench regression ledger (scripts/benchdiff.py): every run appends
    # one normalized record so the trajectory is diffable from day one
    history = os.environ.get("DMLC_BENCH_HISTORY") or os.path.join(
        REPO, "bench_history.jsonl")
    if not args.no_ledger and history not in ("0", "off"):
        written = append_ledger(result, provenance, host, env_over,
                                extras["host_resources"], args.smoke,
                                history)
        if written:
            print(f"# ledger: appended to {written}", file=sys.stderr)


if __name__ == "__main__":
    main()
