"""Zero-copy ingest lane (doc/observability.md "Zero-copy ingest").

Pins the contracts the zero-copy cache-replay->device path rests on:

- every staging buffer the batchers may hand to device_put is 64-byte
  aligned (XLA:CPU aliases instead of copies only at that alignment),
  including buffers coming back through the recycle pool;
- the zero-copy and copying transfer paths are byte-identical for
  csr/dense x f32/bf16 (`DMLC_DEVICE_ZERO_COPY` is a safe A/B switch);
- ineligible trees fall back and are COUNTED, per reason
  (`device_zero_copy_fallbacks_total{reason=}`), never silently copied;
- recycling is gated on an alias PROBE of the first transferred batch
  (not a backend-name assumption); aliased staging is parked behind
  weakrefs and recycled once the consumer drops the device batch, so a
  prompt consumer sees pool reuse even on an aliasing backend, while a
  consumer that holds every batch overflows the parking lot — dropped
  entries visible in the `device_recycle_skipped` gauge;
- under a mesh every leaf lands sharded over the leading device axis
  (the placement-table path) with zero fallbacks;
- the native bf16.h narrowing is bit-for-bit ml_dtypes.bfloat16
  round-to-nearest-even on every non-NaN float32, and quiets NaNs with
  the sign preserved, across the C/Python boundary.
"""

import random

import numpy as np
import pytest

import jax

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.io.native import (NativeParser, bf16_convert, bf16_upcast,
                                     _bf16_dtype)
from dmlc_core_tpu.tpu import device_iter
from dmlc_core_tpu.tpu.device_iter import (DenseBatch, DeviceRowBlockIter,
                                           HostBatcher, NativeHostBatcher,
                                           PaddedBatch, _aligned_empty)
from dmlc_core_tpu.tpu.sharding import data_mesh


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()
    yield
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()


def write_libsvm(path, rows, features=8, seed=0):
    rng = random.Random(seed)
    lines = []
    for i in range(rows):
        feats = [f"{j}:{rng.uniform(-1, 1):.4f}" for j in range(features)]
        lines.append(f"{i % 2} " + " ".join(feats))
    path.write_text("\n".join(lines) + "\n")
    return path


def _counters(labeled=False):
    snap = telemetry.snapshot(native=False)
    if labeled:
        return [(c["name"], c["labels"], c["value"])
                for c in snap["counters"]]
    return {c["name"]: c["value"] for c in snap["counters"]
            if not c["labels"]}


def _gauges():
    snap = telemetry.snapshot(native=False)
    return {g["name"]: g["value"] for g in snap["gauges"]}


def _fallbacks():
    """Total device_zero_copy_fallbacks_total across reason labels,
    plus the per-reason map."""
    per = {}
    for name, labels, value in _counters(labeled=True):
        if name == "device_zero_copy_fallbacks_total":
            per[labels.get("reason", "")] = value
    return sum(per.values()), per


# -- 64-byte alignment ---------------------------------------------------------
def test_aligned_empty_is_64_byte_aligned():
    for shape, dtype in [((3,), np.int32), ((8, 3, 129), np.int32),
                         ((1, 7), np.float32), ((5, 33), _bf16_dtype()),
                         ((2, 4, 8), np.float32)]:
        for _ in range(8):  # allocator addresses vary; every call must align
            a = _aligned_empty(shape, dtype)
            assert a.ctypes.data % 64 == 0
            assert a.flags["C_CONTIGUOUS"]
            assert a.shape == shape and a.dtype == np.dtype(dtype)


def _assert_staging_aligned(b):
    for name in ("big", "cols", "aux", "val16", "x"):
        v = getattr(b, name, None)
        if isinstance(v, np.ndarray) and v.size:
            assert v.ctypes.data % 64 == 0, name


@pytest.mark.parametrize("kwargs", [
    dict(layout="csr"),
    dict(layout="csr", csr_val_dtype="bf16"),
    dict(layout="dense"),
    dict(layout="dense", dense_dtype="bf16"),
])
def test_native_staging_buffers_aligned_incl_pool_reuse(tmp_path, kwargs):
    p = write_libsvm(tmp_path / "a.libsvm", rows=256, features=8)
    nb = NativeHostBatcher(str(p), batch_rows=128, num_shards=4,
                           min_nnz_bucket=64, **kwargs)
    b1 = nb.next_batch()
    _assert_staging_aligned(b1)
    lead = b1.x if isinstance(b1, DenseBatch) else b1.big
    addr = lead.ctypes.data
    nb.recycle(b1)
    b2 = nb.next_batch()  # same static shape -> must come from the pool
    _assert_staging_aligned(b2)
    lead2 = b2.x if isinstance(b2, DenseBatch) else b2.big
    assert lead2.ctypes.data == addr
    nb.close()


def test_python_batcher_staging_aligned(tmp_path):
    p = write_libsvm(tmp_path / "b.libsvm", rows=200, features=8)
    hb = HostBatcher(NativeParser(str(p)), batch_rows=100, num_shards=2,
                     min_nnz_bucket=64, layout="csr")
    b = hb.next_batch()
    assert b.big.ctypes.data % 64 == 0
    assert b.aux.ctypes.data % 64 == 0


# -- byte identity: zero-copy vs copying --------------------------------------
def _collect_trees(uri, monkeypatch, zero_copy, mesh, **kwargs):
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "1" if zero_copy else "0")
    out = []
    with DeviceRowBlockIter(uri, batch_rows=256, mesh=mesh,
                            min_nnz_bucket=64, **kwargs) as it:
        for b in it:
            out.append({k: np.asarray(v) for k, v in b.tree().items()})
    return out


@pytest.mark.parametrize("kwargs", [
    dict(layout="csr"),
    dict(layout="csr", csr_val_dtype="bf16"),
    dict(layout="dense"),
    dict(layout="dense", dense_dtype="bf16"),
], ids=["csr-f32", "csr-bf16", "dense-f32", "dense-bf16"])
@pytest.mark.parametrize("use_mesh", [False, True],
                         ids=["single", "mesh8"])
def test_zero_copy_byte_identity(tmp_path, monkeypatch, kwargs, use_mesh):
    p = write_libsvm(tmp_path / "c.libsvm", rows=640, features=8)
    mesh = data_mesh() if use_mesh else None
    zc = _collect_trees(str(p), monkeypatch, True, mesh, **kwargs)
    cp = _collect_trees(str(p), monkeypatch, False, mesh, **kwargs)
    assert len(zc) == len(cp) == 3  # 640 rows / 256 = 2 full + 1 partial
    for tz, tc in zip(zc, cp):
        assert set(tz) == set(tc)
        for k in tz:
            a, b = tz[k], tc[k]
            assert a.dtype == b.dtype and a.shape == b.shape, k
            if a.dtype == _bf16_dtype():
                a, b = a.view(np.uint16), b.view(np.uint16)
            assert np.array_equal(a, b), k


# -- counters, sharded placement, recycle probe -------------------------------
def test_zero_copy_counters_and_sharded_placement(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "1")
    p = write_libsvm(tmp_path / "d.libsvm", rows=2048, features=8)
    mesh = data_mesh()
    leading = jax.sharding.PartitionSpec("data")
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=mesh,
                            min_nnz_bucket=64, layout="csr") as it:
        batches = list(it)  # the consumer HOLDS every batch
        assert it._recycle_aliases is True  # CPU device_put aliases host
    assert len(batches) == 8
    for b in batches:
        for k, v in b.tree().items():
            assert isinstance(v, jax.Array), k
            assert v.sharding.spec == leading, k
            assert v.shape[0] == 8, k
    total, per = _fallbacks()
    assert total == 0, per
    assert _counters()["device_zero_copy_batches_total"] == 8
    # aliasing backend + every batch still alive -> none of the parked
    # staging buffers can be swept; the 8 batches overflow the
    # (prefetch-scaled, here 4-deep) parking lot, and each overflow drop
    # is visible in the gauge
    assert _gauges()["device_recycle_skipped"] == 4


def test_deferred_recycle_reuses_pool_for_prompt_consumer(tmp_path,
                                                          monkeypatch):
    """A consumer that DROPS each batch lets the weakref sweep return the
    aliased staging to the pool: staging addresses repeat across the
    epoch and nothing is dropped from the parking lot."""
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "1")
    p = write_libsvm(tmp_path / "d2.libsvm", rows=2048, features=8)
    addrs = []
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=None,
                            min_nnz_bucket=64, layout="csr",
                            prefetch=0) as it:
        assert it._prefetch == 0
        for b in it:
            # record the aliased staging address WITHOUT keeping a view
            # alive (a live np.asarray view would pin the jax array and
            # defeat the sweep)
            addrs.append(int(np.asarray(b.big).ctypes.data))
            del b
        assert it._recycle_aliases is True
    assert len(addrs) == 8
    assert len(set(addrs)) < 8  # staging came back through the pool
    assert _gauges().get("device_recycle_skipped", 0) == 0


def _alias_verdicts():
    return {labels["verdict"]: value
            for name, labels, value in _counters(labeled=True)
            if name == "device_alias_probe_total" and value}


def test_alias_probe_verdict_is_counted():
    """Probed-and-aliases, probed-and-copies and could-not-probe are
    three answers; the last one still defers recycling (correctness must
    not depend on the probe) but no longer hides in the first."""
    from dmlc_core_tpu.tpu.device_iter import _tree_aliases_host
    telemetry.reset()
    host = _aligned_empty((4, 64), np.int32)
    host[...] = 7
    other = _aligned_empty((4, 64), np.int32)
    other[...] = 7

    # XLA:CPU aliases a 64-byte-aligned host buffer on device_put
    assert _tree_aliases_host({"a": host}, {"a": jax.device_put(host)})
    assert _alias_verdicts() == {"aliases": 1}
    # a device array that lives elsewhere (what a DMA to HBM looks like)
    assert not _tree_aliases_host({"a": host},
                                  {"a": jax.device_put(other)})
    assert _alias_verdicts() == {"aliases": 1, "no_alias": 1}

    class NoPointer:                       # a backend that will not say
        @property
        def addressable_shards(self):
            raise RuntimeError("no host-visible address on this backend")

    assert _tree_aliases_host({"a": host}, {"a": NoPointer()})
    assert _alias_verdicts() == {"aliases": 1, "no_alias": 1,
                                 "unprobeable": 1}
    assert any(e.get("event") == "device-alias-probe"
               and "no host-visible address" in e.get("error", "")
               for e in telemetry.events())


def test_prefetch0_sync_mode_matches_pipelined(tmp_path, monkeypatch):
    """prefetch=0 (no pipeline threads) must land byte-identical batches
    and the same counters as the default threaded pipeline."""
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "1")
    p = write_libsvm(tmp_path / "d3.libsvm", rows=640, features=8)
    sync = _collect_trees(str(p), monkeypatch, True, None,
                          layout="csr", prefetch=0)
    assert _counters()["device_zero_copy_batches_total"] == 3
    piped = _collect_trees(str(p), monkeypatch, True, None, layout="csr")
    assert len(sync) == len(piped) == 3
    for ts, tp in zip(sync, piped):
        for k in ts:
            assert np.array_equal(ts[k], tp[k]), k


def test_zero_copy_disabled_takes_copying_path(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "0")
    p = write_libsvm(tmp_path / "e.libsvm", rows=512, features=8)
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=data_mesh(),
                            min_nnz_bucket=64, layout="csr") as it:
        assert len(list(it)) == 2
    counters = _counters()
    assert counters.get("device_zero_copy_batches_total", 0) == 0
    assert _fallbacks()[0] == 0  # disabled is a choice, not a fallback


def _unaligned_like(a):
    """A copy of `a` at a deliberately 64-byte-MISaligned address (numpy
    bases are 16-byte aligned, so a one-int32 offset lands on 4 mod 16)."""
    raw = np.zeros(a.size + 16, np.int32)
    out = raw[1:1 + a.size].reshape(a.shape)
    assert out.ctypes.data % 64 != 0
    out[...] = a
    return out


def test_fallback_counted_per_reason(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "1")
    p = write_libsvm(tmp_path / "f.libsvm", rows=64, features=8)
    it = DeviceRowBlockIter(str(p), batch_rows=64, min_nnz_bucket=64,
                            layout="csr")
    try:
        big = _unaligned_like(np.arange(3 * 8, dtype=np.int32)
                              .reshape(1, 3, 8))
        aux = _unaligned_like(np.arange(3 * 4, dtype=np.int32)
                              .reshape(1, 3, 4))
        cols = _unaligned_like(np.arange(16, dtype=np.int32)
                               .reshape(1, 16))
        got = it._device_put(PaddedBatch(big=big, cols=cols, aux=aux,
                                         total_rows=2))
        # the fallback still LANDS the batch, bit-exactly
        assert np.array_equal(np.asarray(got.big), big)
        assert np.array_equal(np.asarray(got.cols), cols)
        assert np.array_equal(np.asarray(got.aux), aux)
        total, per = _fallbacks()
        assert per.get("unaligned") == 1 and total == 1
        assert _counters().get("device_zero_copy_batches_total", 0) == 0
        # non-contiguous host leaves take their own reason
        big_t = np.asfortranarray(np.zeros((2, 3, 8), np.int32))
        aux_c = _aligned_empty((2, 3, 4), np.int32)
        aux_c.fill(0)
        cols_c = _aligned_empty((2, 16), np.int32)
        cols_c.fill(0)
        it._device_put(PaddedBatch(big=big_t, cols=cols_c, aux=aux_c,
                                   total_rows=0))
        assert _fallbacks()[1].get("non_contiguous_host") == 1
        # an aligned, contiguous tree goes zero-copy on the same iterator
        big_a = _aligned_empty((1, 3, 8), np.int32)
        big_a.fill(1)
        aux_a = _aligned_empty((1, 3, 4), np.int32)
        aux_a.fill(0)
        it._device_put(PaddedBatch(big=big_a, cols=cols_c[:1], aux=aux_a,
                                   total_rows=0))
        assert _counters()["device_zero_copy_batches_total"] == 1
        assert _fallbacks()[0] == 2  # unchanged
    finally:
        it.close()


def test_bf16_csr_rejected_on_binary_and_index64_lanes(tmp_path):
    p = write_libsvm(tmp_path / "g.libsvm", rows=8, features=4)
    with pytest.raises(DMLCError):
        DeviceRowBlockIter(str(p), fmt="crec", csr_val_dtype="bf16")
    with pytest.raises(DMLCError):
        DeviceRowBlockIter(str(p), index64=True, csr_val_dtype="bf16")


# -- bf16.h <-> ml_dtypes parity ----------------------------------------------
def _native_narrow(f32):
    out = np.empty(f32.shape, _bf16_dtype())
    bf16_convert(np.ascontiguousarray(f32), out)
    return out


def test_bf16_parity_fuzz_non_nan():
    """Every non-NaN float32 must narrow bit-for-bit like
    ml_dtypes.bfloat16 (round-to-nearest-even), including RNE ties,
    subnormals, overflow-to-inf, and signed zeros/infinities."""
    rng = np.random.default_rng(20260806)
    bits = rng.integers(0, 2 ** 32, 100000, dtype=np.uint32)
    special = np.array([
        0x00000000, 0x80000000,              # +/- 0
        0x7f800000, 0xff800000,              # +/- inf
        0x00000001, 0x80000001, 0x007fffff,  # subnormals
        0x3f808000, 0x3f818000,              # RNE ties: to even, up
        0x3f807fff, 0x3f808001,              # just below / above the tie
        0x7f7fffff, 0xff7fffff,              # f32 max -> rounds to inf
        0x7f7f0000, 0x42280000,              # exact bf16 values
    ], np.uint32)
    bits = np.concatenate([bits, special])
    f = bits.view(np.float32)
    keep = ~np.isnan(f)
    f = np.ascontiguousarray(f[keep])
    want = f.astype(_bf16_dtype()).view(np.uint16)
    got = _native_narrow(f).view(np.uint16)
    mism = np.nonzero(want != got)[0]
    assert mism.size == 0, (
        f[mism[:5]], want[mism[:5]], got[mism[:5]])


def test_bf16_nan_quieted_sign_preserved():
    bits = np.array([0x7fc00000, 0xffc00000,   # quiet +/- NaN
                     0x7f800001, 0xff800001,   # signaling +/- NaN
                     0x7fabcdef, 0xffabcdef,   # payload NaNs
                     0x7fffffff, 0xffffffff], np.uint32)
    f = np.ascontiguousarray(bits.view(np.float32))
    got = _native_narrow(f).view(np.uint16)
    for src, out in zip(bits, got):
        assert (out & 0x7f80) == 0x7f80 and (out & 0x007f) != 0  # still NaN
        assert (out & 0x0040) != 0                               # quieted
        assert (out >> 15) == (int(src) >> 31)                   # sign kept


def test_bf16_roundtrip_upcast_exact():
    """bf16 -> f32 upcast is exact (bf16 values are f32 values), and
    narrowing the upcast result is the identity."""
    all16 = np.arange(2 ** 16, dtype=np.uint16)
    # drop NaNs: exponent all-ones with nonzero mantissa
    nan = ((all16 & 0x7f80) == 0x7f80) & ((all16 & 0x007f) != 0)
    vals16 = np.ascontiguousarray(all16[~nan]).view(_bf16_dtype())
    up = np.empty(vals16.shape, np.float32)
    bf16_upcast(vals16, up)
    assert np.array_equal(up.view(np.uint32),
                          vals16.view(np.uint16).astype(np.uint32) << 16)
    back = _native_narrow(up)
    assert np.array_equal(back.view(np.uint16), vals16.view(np.uint16))


def test_bf16_batch_values_match_ml_dtypes(tmp_path):
    """End-to-end: the fused native fill's bf16 plane equals narrowing the
    f32 plane with ml_dtypes (the same RNE), across the C/Python boundary."""
    p = write_libsvm(tmp_path / "h.libsvm", rows=128, features=8, seed=3)
    nb32 = NativeHostBatcher(str(p), batch_rows=128, num_shards=2,
                             min_nnz_bucket=64, layout="csr")
    nb16 = NativeHostBatcher(str(p), batch_rows=128, num_shards=2,
                             min_nnz_bucket=64, layout="csr",
                             csr_val_dtype="bf16")
    b32, b16 = nb32.next_batch(), nb16.next_batch()
    assert b16.val16.dtype == _bf16_dtype()
    want = b32.val.astype(_bf16_dtype()).view(np.uint16)
    assert np.array_equal(b16.val16.view(np.uint16), want)
    nb32.close()
    nb16.close()
