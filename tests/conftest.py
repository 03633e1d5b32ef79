"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip hardware is unavailable in CI, so sharding/collective tests run on
``xla_force_host_platform_device_count=8`` CPU devices — the same simulation
strategy the reference uses for distributed input splitting (instantiating the
same URI with different (part_index, num_parts) in one process,
test/unittest/unittest_inputsplit.cc:116-145).
"""

import os
import sys

# tests run on the CPU backend; the chip is exercised by chip_smoke.py
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# fast retry loops for the fault-injection suites (the S3 config singleton
# reads these once, at first native S3 use — set them before any test runs).
# The backoff cap + jitter seed keep the decorrelated-jitter sleeps tiny and
# reproducible under test (cpp/src/retry.h RetryPolicy).
os.environ.setdefault("S3_MAX_RETRY", "10")
os.environ.setdefault("S3_RETRY_SLEEP_MS", "5")
os.environ.setdefault("DMLC_IO_BACKOFF_CAP_MS", "50")
os.environ.setdefault("DMLC_IO_JITTER_SEED", "7")
