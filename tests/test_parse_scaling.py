"""Multi-core parse-scaling guard (VERDICT r3 item 8).

The worker fan-out (parser.cc FillBlocks tiling) has correctness coverage
under TSan but the first bench host exposed ONE core, so its
thread_scaling table is structurally flat and a serialization bug that
only shows up multi-core would go unnoticed. This test asserts real
scaling the day the suite runs on a multi-core host and auto-skips on
single-core boxes. Reference analog: text_parser.h:110-146 parallel fill.
"""

import os
import time

import numpy as np
import pytest

from dmlc_core_tpu.io.native import NativeParser


def _parse_secs(path: str, rows: int, nthread: int) -> float:
    best = None
    for _ in range(3):
        t0 = time.time()
        got = 0
        # threaded=False isolates ParseBlock fan-out from pipeline overlap
        with NativeParser(path, nthread=nthread, threaded=False) as p:
            for b in p:
                got += b.num_rows
        dt = time.time() - t0
        assert got == rows
        best = dt if best is None else min(best, dt)
    return best


def _usable_cpus() -> int:
    """CPUs actually schedulable for THIS process (affinity mask), not the
    host's core count — a cgroup-pinned CI runner must not be asked to
    scale on cores it cannot use."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


# Four schedulable cores minimum: below that the stages themselves contend
# (measured on a 2-core container: prefetch reader + 2 parse workers + the
# consuming thread cap the sync fan-out at ~1.0-1.3x, and the pipelined
# path at ~1.2-1.7x, regardless of correctness — a threshold there only
# measures the scheduler). The first bench host had ONE core, so
# this continues to auto-skip until the suite lands on a real multi-core
# host.
@pytest.mark.skipif(_usable_cpus() < 4,
                    reason="parse scaling needs >= 4 schedulable cores "
                           "(stage threads contend below that)")
def test_parse_throughput_scales_with_cores(tmp_path):
    rng = np.random.default_rng(12)
    path = tmp_path / "scale.libsvm"
    with open(path, "w") as f:
        for i in range(120000):
            feats = " ".join(
                f"{j}:{rng.uniform(-3, 3):.6f}" for j in range(16))
            f.write(f"{i % 2} {feats}\n")
    t1 = _parse_secs(str(path), 120000, 1)
    t4 = _parse_secs(str(path), 120000, 4)
    speedup = t1 / t4
    # >=1.5x from 1 -> 4 workers; a serialized fan-out scores ~1.0 and
    # fails loudly
    assert speedup >= 1.5, (
        f"parse fan-out did not scale: 1 thread {t1:.3f}s vs "
        f"4 threads {t4:.3f}s ({speedup:.2f}x)")


def test_simd_lane_single_thread_floor(tmp_path):
    """The ISSUE 3 acceptance lane, host-noise-proof edition: unlike the
    >=4-core scaling guards above, this runs on the 1-2 core bench host,
    so a regression of the SIMD text-ingest lane (doc/parsing.md) fails
    tier-1.

    Measured in PROCESS CPU TIME with interleaved A/B batches and bounded
    re-measure — the PR 5 overhead-guard recipe (tests/test_telemetry.py).
    The previous wall-clock best-of-3 version was the suite's known flake:
    this host's wall clock swings ±40% minute-to-minute under full runs
    (passes in isolation, fails in the pack), far above the 0.85x ratio
    it asserts. CPU ticks drift ~10% here, so each sample is a BATCH of
    passes, lanes alternate order so neither always pays the post-switch
    sample, and the guard re-measures up to 4 times, passing on the first
    in-bound result — noise clears within an attempt or two, while the
    regression class this exists to catch (a fused-decode bug or an
    always-delegate storm at ~0.5x) fails every attempt.

    Two assertions:
      - the SIMD lane is actually engaged (not silently scalar);
      - SIMD CPU cost per pass <= scalar/0.85 (ratio >= 0.85; the healthy
        ratio measures 1.05-1.35x), plus a loose absolute CPU-throughput
        floor for catastrophic slowdowns.
    """
    rng = np.random.default_rng(17)
    path = tmp_path / "floor.libsvm"
    with open(path, "w") as f:
        for i in range(30000):
            feats = " ".join(
                f"{j}:{rng.uniform(-3, 3):.6f}" for j in range(16))
            f.write(f"{i % 2} {feats}\n")
    size_mb = os.path.getsize(path) / 1e6

    def batch_cpu(env_tier: str, n: int = 8) -> float:
        # CPU accounting is tick-granular (~10 ms) and one pass costs
        # ~30 ms; an 8-pass batch keeps the quantization under ~5%
        old = os.environ.get("DMLC_PARSE_SIMD")
        os.environ["DMLC_PARSE_SIMD"] = env_tier
        try:
            t0 = time.process_time()
            for _ in range(n):
                got = 0
                with NativeParser(str(path), nthread=1,
                                  threaded=False) as p:
                    for b in p:
                        got += b.num_rows
                assert got == 30000
            return (time.process_time() - t0) / n
        finally:
            if old is None:
                os.environ.pop("DMLC_PARSE_SIMD", None)
            else:
                os.environ["DMLC_PARSE_SIMD"] = old

    with NativeParser(str(path), nthread=1) as p:
        p.next_block()
        lane = (p.pipeline_stats() or {}).get("simd_lane", "scalar")
    if lane == "scalar":
        pytest.skip("no SIMD tier on this host (big-endian or forced off)")

    batch_cpu("1", n=1)  # warm the page cache outside the measured reps

    def measure():
        best = {"0": float("inf"), "1": float("inf")}
        for rep in range(2):
            order = ("0", "1") if rep % 2 == 0 else ("1", "0")
            for tier in order:
                best[tier] = min(best[tier], batch_cpu(tier))
        return best

    ratios = []
    for _ in range(4):
        best = measure()
        ratios.append(best["0"] / best["1"])  # scalar CPU / simd CPU
        if ratios[-1] >= 0.85 and size_mb / best["1"] >= 40.0:
            break
    scalar_t, simd_t = best["0"], best["1"]
    assert ratios[-1] >= 0.85, (
        f"SIMD lane ({lane}) regressed below the scalar lane across "
        f"{len(ratios)} interleaved CPU-time measurements: ratios "
        f"{[round(r, 3) for r in ratios]} ({size_mb / simd_t:.0f} "
        f"MB/cpu-s vs scalar {size_mb / scalar_t:.0f} MB/cpu-s)")
    assert size_mb / simd_t >= 40.0, (
        f"catastrophic single-thread parse slowdown: "
        f"{size_mb / simd_t:.0f} MB/cpu-s across {len(ratios)} attempts")


@pytest.mark.skipif(_usable_cpus() < 4,
                    reason="pipeline scaling needs >= 4 schedulable cores")
def test_pipelined_parse_scales_with_cores(tmp_path):
    """The ISSUE 1 acceptance lane: the multi-chunk in-flight pipeline
    (threaded=True) must deliver >=2x
    rows/s at 4 workers vs 1 on a host with cores to spare."""
    rng = np.random.default_rng(12)
    path = tmp_path / "scale.libsvm"
    with open(path, "w") as f:
        for i in range(120000):
            feats = " ".join(
                f"{j}:{rng.uniform(-3, 3):.6f}" for j in range(16))
            f.write(f"{i % 2} {feats}\n")

    def pipe_secs(nthread: int) -> float:
        best = None
        for _ in range(3):
            t0 = time.time()
            got = 0
            with NativeParser(str(path), nthread=nthread,
                              threaded=True) as p:
                for b in p:
                    got += b.num_rows
            dt = time.time() - t0
            assert got == 120000
            best = dt if best is None else min(best, dt)
        return best

    t1 = pipe_secs(1)
    t4 = pipe_secs(4)
    speedup = t1 / t4
    assert speedup >= 2.0, (
        f"parse pipeline did not scale: 1 worker {t1:.3f}s vs "
        f"4 workers {t4:.3f}s ({speedup:.2f}x)")
