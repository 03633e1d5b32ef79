"""Round-3 ingest features: the binary "rec" row-block format
(cpp/src/parser.cc RecParser + io/convert.py), native bf16 dense emission
(batcher.cc FillDense x_dtype), host-buffer recycling, and the int32
feature-id range guard (VERDICT r2 items 1-3)."""

import numpy as np
import pytest

import ml_dtypes

from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.io.convert import rows_to_recordio
from dmlc_core_tpu.io.native import NativeParser
from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter, HostBatcher,
                                           NativeHostBatcher, _expand_cols)


def write_libsvm(path, rows, features=12, seed=3, qid=False):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(rows):
        feats = " ".join(
            f"{j}:{rng.uniform(-2, 2):.5f}" for j in range(features))
        q = f"qid:{i // 10} " if qid else ""
        lines.append(f"{i % 2} {q}{feats}")
    path.write_text("\n".join(lines) + "\n")
    return path


def collect(path, fmt="auto", nthread=0, **kw):
    lab, idx, val, lens = [], [], [], []
    with NativeParser(str(path), fmt=fmt, nthread=nthread, **kw) as p:
        for b in p:
            lab.append(b.label.copy())
            idx.append(b.index.copy())
            val.append(b.value.copy() if b.value is not None
                       else np.ones(b.nnz, np.float32))
            lens.extend(np.diff(b.offset).tolist())
    return (np.concatenate(lab), np.concatenate(idx), np.concatenate(val),
            np.asarray(lens))


# -- rec binary format ------------------------------------------------------
def test_rec_round_trip_identical(tmp_path):
    src = write_libsvm(tmp_path / "a.libsvm", rows=3000)
    dst = tmp_path / "a.rec"
    n = rows_to_recordio(str(src), str(dst), rows_per_record=256)
    assert n == 3000
    a = collect(src)
    b = collect(dst, fmt="rec")
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_rec_auto_detected_by_suffix(tmp_path):
    src = write_libsvm(tmp_path / "b.libsvm", rows=500)
    dst = tmp_path / "b.rec"
    rows_to_recordio(str(src), str(dst))
    lab, _, _, _ = collect(dst)  # fmt="auto" resolves via .rec suffix
    assert lab.size == 500


def test_rec_partitioned_exact_cover(tmp_path):
    src = write_libsvm(tmp_path / "c.libsvm", rows=4000)
    dst = tmp_path / "c.rec"
    rows_to_recordio(str(src), str(dst), rows_per_record=128)
    total = 0
    seen = []
    for k in range(4):
        with NativeParser(str(dst), part=k, npart=4, fmt="rec") as p:
            for b in p:
                total += b.num_rows
                seen.append(b.label.copy())
    assert total == 4000
    # every row appears exactly once (labels alternate 0/1: check count)
    assert np.concatenate(seen).sum() == 2000


def test_rec_threaded_parse_matches_serial(tmp_path):
    src = write_libsvm(tmp_path / "d.libsvm", rows=5000)
    dst = tmp_path / "d.rec"
    rows_to_recordio(str(src), str(dst), rows_per_record=64)
    a = collect(dst, fmt="rec", nthread=1)
    b = collect(dst, fmt="rec", nthread=8)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_rec_qid_carried(tmp_path):
    src = write_libsvm(tmp_path / "e.libsvm", rows=300, qid=True)
    dst = tmp_path / "e.rec"
    rows_to_recordio(str(src), str(dst), rows_per_record=50)
    qids = []
    with NativeParser(str(dst), fmt="rec") as p:
        for b in p:
            assert b.qid is not None
            qids.append(b.qid.copy())
    q = np.concatenate(qids)
    assert np.array_equal(q, np.arange(300) // 10)


def test_rec_index_width_mismatch_raises(tmp_path):
    src = write_libsvm(tmp_path / "f.libsvm", rows=100)
    dst = tmp_path / "f.rec"
    rows_to_recordio(str(src), str(dst))  # uint32 payload
    with pytest.raises(DMLCError, match="index width mismatch"):
        collect(dst, fmt="rec", index64=True)


def test_rec_rejects_foreign_records(tmp_path):
    from dmlc_core_tpu.io.native import NativeRecordIOWriter
    dst = tmp_path / "g.rec"
    with NativeRecordIOWriter(str(dst)) as w:
        w.write_record(b"not a row block payload")
    with pytest.raises(DMLCError, match="bad payload magic"):
        collect(dst, fmt="rec")


def test_rec_device_iter_end_to_end(tmp_path):
    src = write_libsvm(tmp_path / "h.libsvm", rows=2000)
    dst = tmp_path / "h.rec"
    rows_to_recordio(str(src), str(dst), rows_per_record=100)
    got = 0
    with DeviceRowBlockIter(str(dst), fmt="rec", batch_rows=512,
                            to_device=False) as it:
        for b in it:
            got += b.total_rows
    assert got == 2000


# -- native bf16 dense emission --------------------------------------------
def test_native_bf16_dense_matches_f32(tmp_path):
    src = write_libsvm(tmp_path / "i.libsvm", rows=700, features=10)
    bf = NativeHostBatcher(str(src), batch_rows=256, num_shards=2,
                           dense_dtype="bf16")
    f32 = NativeHostBatcher(str(src), batch_rows=256, num_shards=2,
                            dense_dtype=np.float32)
    while True:
        a = bf.next_batch()
        b = f32.next_batch()
        if a is None:
            assert b is None
            break
        assert a.x.dtype == np.dtype(ml_dtypes.bfloat16)
        assert b.x.dtype == np.float32
        # bf16 has 8 mantissa bits: relative error <= 2^-8
        err = np.abs(a.x.astype(np.float32) - b.x)
        assert err.max() <= np.abs(b.x).max() * 2 ** -8 + 1e-7
        assert np.array_equal(a.label, b.label)
        assert np.array_equal(a.nrows, b.nrows)
    bf.close()
    f32.close()


def test_bf16_rejects_other_dtypes(tmp_path):
    src = write_libsvm(tmp_path / "j.libsvm", rows=10)
    with pytest.raises(DMLCError, match="dense_dtype"):
        NativeHostBatcher(str(src), batch_rows=8, dense_dtype=np.float16)


# -- host buffer recycling --------------------------------------------------
def test_recycle_pool_reuses_buffers(tmp_path):
    src = write_libsvm(tmp_path / "k.libsvm", rows=600, features=6)
    b = NativeHostBatcher(str(src), batch_rows=128, num_shards=2,
                          dense_dtype="bf16")
    first = b.next_batch()
    ptr = first.x.__array_interface__["data"][0] if first.x.base is None \
        else first.x.base.__array_interface__["data"][0]
    b.recycle(first)
    second = b.next_batch()
    ptr2 = second.x.base.__array_interface__["data"][0]
    assert ptr == ptr2  # same backing buffer came back from the pool
    b.close()


def test_recycle_foreign_dtype_dropped(tmp_path):
    src = write_libsvm(tmp_path / "l.libsvm", rows=100, features=4)
    b = NativeHostBatcher(str(src), batch_rows=64, dense_dtype="bf16")
    batch = b.next_batch()
    fake = type(batch)(x=batch.x.astype(np.float32), label=batch.label,
                       weight=batch.weight, nrows=batch.nrows,
                       total_rows=batch.total_rows)
    b.recycle(fake)  # wrong dtype: silently dropped, not poisoning the pool
    nxt = b.next_batch()
    assert nxt.x.dtype == np.dtype(ml_dtypes.bfloat16)
    b.close()


# -- int32 feature-id range guard ------------------------------------------
def _write_big_index(path, big):
    path.write_text(f"1 5:1.0 {big}:2.0\n0 3:1.0\n")
    return path


def test_index64_overflow_raises_python_batcher(tmp_path):
    big = 2 ** 31 + 7
    p = _write_big_index(tmp_path / "m.libsvm", big)
    parser = NativeParser(str(p), index64=True)
    hb = HostBatcher(parser, batch_rows=4, num_shards=1, layout="csr")
    with pytest.raises(DMLCError, match="exceeds the int32"):
        hb.next_batch()
    parser.close()


def test_index64_overflow_raises_dense_layout(tmp_path):
    big = 2 ** 31 + 7
    p = _write_big_index(tmp_path / "n.libsvm", big)
    parser = NativeParser(str(p), index64=True)
    hb = HostBatcher(parser, batch_rows=4, num_shards=1, layout="dense",
                     dense_max_features=2 ** 33)
    with pytest.raises(DMLCError, match="exceeds the int32"):
        hb.next_batch()
    parser.close()


def test_index_overflow_raises_native_batcher(tmp_path):
    # uint32 ids >= 2^31 wrap negative in the int32 device layout too;
    # PaddedBatcher::Accumulate refuses them (batcher.cc)
    big = 2 ** 31 + 7
    p = _write_big_index(tmp_path / "o.libsvm", big)
    b = NativeHostBatcher(str(p), batch_rows=4, layout="csr")
    with pytest.raises(DMLCError, match="exceeds the int32"):
        b.next_batch()
    b.close()


# the largest int32 id is a column like another; the distinct list's
# padding repeats it (col_slots) and no slot names the padding
_TOP_ID = 2 ** 31 - 1


def _batcher_of(kind, path):
    if kind == "native":
        return NativeHostBatcher(str(path), batch_rows=4, layout="csr"), None
    parser = NativeParser(str(path), index64=True)
    return HostBatcher(parser, batch_rows=4, num_shards=1,
                       layout="csr"), parser


@pytest.mark.parametrize("kind", ["python", "native"])
def test_index_below_limit_ok(tmp_path, kind):
    p = _write_big_index(tmp_path / "p.libsvm", _TOP_ID)
    hb, parser = _batcher_of(kind, p)
    batch = hb.next_batch()
    assert batch is not None
    assert int(_expand_cols(batch.cols, batch.slot).max()) == _TOP_ID
    assert batch.cols[0, :4].tolist() == [3, 5, _TOP_ID, _TOP_ID]
    assert int(batch.slot.max()) == 2
    (parser or hb).close()


# -- recd: zero-parse dense row-matrix lane ---------------------------------
def write_dense_pair(tmp_path, rows=3000, features=14, weights=False,
                     seed=6):
    from dmlc_core_tpu.io.convert import rows_to_dense_recordio
    rng = np.random.default_rng(seed)
    src = tmp_path / "dd.libsvm"
    lines = []
    for i in range(rows):
        w = f":{rng.uniform(0.5, 2):.3f}" if weights else ""
        feats = " ".join(
            f"{j}:{rng.uniform(-2, 2):.5f}" for j in range(features))
        lines.append(f"{i % 2}{w} {feats}")
    src.write_text("\n".join(lines) + "\n")
    dst = tmp_path / "dd.drec"
    n = rows_to_dense_recordio(str(src), str(dst), rows_per_record=256)
    assert n == rows
    return src, dst


def batches_of(path, fmt="auto", dt="bf16", batch_rows=512, **kw):
    out = []
    with DeviceRowBlockIter(str(path), fmt=fmt, batch_rows=batch_rows,
                            to_device=False, dense_dtype=dt, **kw) as it:
        for b in it:
            out.append(b)
    return out

def test_recd_matches_text_dense_lane(tmp_path):
    src, dst = write_dense_pair(tmp_path)
    a = batches_of(src)
    b = batches_of(dst)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.total_rows == y.total_rows
        assert np.array_equal(np.asarray(x.label), np.asarray(y.label))
        assert np.array_equal(np.asarray(x.weight), np.asarray(y.weight))
        assert np.array_equal(np.asarray(x.nrows), np.asarray(y.nrows))
        # both lanes quantize to bf16: identical storage expected
        assert np.array_equal(
            np.asarray(x.x).view(np.uint16), np.asarray(y.x).view(np.uint16))


def test_recd_weights_carried(tmp_path):
    src, dst = write_dense_pair(tmp_path, rows=700, weights=True)
    a = batches_of(src)
    b = batches_of(dst)
    for x, y in zip(a, b):
        assert np.allclose(np.asarray(x.weight), np.asarray(y.weight))
    # padding rows keep weight 0
    assert float(np.asarray(b[-1].weight).reshape(-1)[-1]) == 0.0


def test_recd_f32_output_from_bf16_disk(tmp_path):
    _, dst = write_dense_pair(tmp_path, rows=600)
    b = batches_of(dst, dt=np.float32)
    assert all(np.asarray(x.x).dtype == np.float32 for x in b)
    # bf16 -> f32 widening is exact: values representable in bf16
    bb = batches_of(dst, dt="bf16")
    for x, y in zip(b, bb):
        assert np.array_equal(np.asarray(x.x),
                              np.asarray(y.x).astype(np.float32))


def test_recd_partitioned_exact_cover_and_epochs(tmp_path):
    _, dst = write_dense_pair(tmp_path, rows=4000)
    total = 0
    for k in range(4):
        total += sum(b.total_rows for b in batches_of(dst, part=k, npart=4))
    assert total == 4000
    # two epochs via before_first
    from dmlc_core_tpu.tpu.device_iter import DenseRecHostBatcher
    hb = DenseRecHostBatcher(str(dst), batch_rows=512, dense_dtype="bf16")
    def epoch_rows():
        n = 0
        while True:
            b = hb.next_batch()
            if b is None:
                return n
            n += b.total_rows
    assert epoch_rows() == 4000
    hb.reset()
    assert epoch_rows() == 4000
    hb.close()


def test_recd_rejects_qid_data(tmp_path):
    from dmlc_core_tpu.io.convert import rows_to_dense_recordio
    src = tmp_path / "q.libsvm"
    src.write_text("1 qid:1 0:1.0\n0 qid:1 1:2.0\n")
    with pytest.raises(DMLCError, match="dense representation"):
        rows_to_dense_recordio(str(src), str(tmp_path / "q.drec"))


def test_recd_rejects_foreign_records(tmp_path):
    from dmlc_core_tpu.io.native import (NativeDenseRecBatcher,
                                         NativeRecordIOWriter)
    dst = tmp_path / "bad.drec"
    with NativeRecordIOWriter(str(dst)) as w:
        w.write_record(b"0123456789abcdef not a dense record")
    b = NativeDenseRecBatcher(str(dst), batch_rows=64)
    with pytest.raises(DMLCError, match="bad payload magic"):
        b.meta()
    b.close()


def test_recd_truncated_record_raises(tmp_path):
    import struct
    from dmlc_core_tpu.io.native import (NativeDenseRecBatcher,
                                         NativeRecordIOWriter)
    dst = tmp_path / "trunc.drec"
    with NativeRecordIOWriter(str(dst)) as w:
        # claims 100 rows x 8 features but carries no payload
        w.write_record(struct.pack("<IIII", 0x44524431, 1, 100, 8))
    b = NativeDenseRecBatcher(str(dst), batch_rows=64)
    with pytest.raises(DMLCError, match="truncated"):
        b.meta()
    b.close()


def test_recd_recycle_pool(tmp_path):
    _, dst = write_dense_pair(tmp_path, rows=2000)
    from dmlc_core_tpu.tpu.device_iter import DenseRecHostBatcher
    hb = DenseRecHostBatcher(str(dst), batch_rows=256, dense_dtype="bf16")
    first = hb.next_batch()
    ptr = first.x.base.__array_interface__["data"][0]
    hb.recycle(first)
    second = hb.next_batch()
    assert second.x.base.__array_interface__["data"][0] == ptr
    hb.close()


# -- multi-file datasets (';'-separated URIs and directories) ---------------
def test_rec_multi_file_and_directory(tmp_path):
    from dmlc_core_tpu.io.convert import rows_to_recordio
    d = tmp_path / "parts"
    d.mkdir()
    total = 0
    for i in range(3):
        src = write_libsvm(tmp_path / f"s{i}.libsvm", rows=400 + 100 * i,
                           seed=i)
        rows_to_recordio(str(src), str(d / f"p{i}.rec"), rows_per_record=64)
        total += 400 + 100 * i
    # ';'-separated explicit list
    uri = ";".join(str(d / f"p{i}.rec") for i in range(3))
    lab, _, _, _ = collect(uri, fmt="rec")
    assert lab.size == total
    # whole directory
    lab2, _, _, _ = collect(str(d), fmt="rec")
    assert lab2.size == total
    # partitioned over the multi-file set: exact cover
    got = 0
    for k in range(4):
        with NativeParser(uri, part=k, npart=4, fmt="rec") as p:
            got += sum(b.num_rows for b in p)
    assert got == total


def test_recd_multi_file_exact_cover(tmp_path):
    from dmlc_core_tpu.io.convert import rows_to_dense_recordio
    from dmlc_core_tpu.tpu.device_iter import DenseRecHostBatcher
    total = 0
    uris = []
    for i in range(3):
        src = write_libsvm(tmp_path / f"t{i}.libsvm", rows=300, seed=10 + i,
                           features=9)
        dst = tmp_path / f"t{i}.drec"
        rows_to_dense_recordio(str(src), str(dst), rows_per_record=50,
                               num_features=9)
        uris.append(str(dst))
        total += 300
    uri = ";".join(uris)
    got = 0
    for k in range(3):
        b = DenseRecHostBatcher(uri, part=k, npart=3, batch_rows=512,
                                dense_dtype="bf16")
        while True:
            batch = b.next_batch()
            if batch is None:
                break
            got += batch.total_rows
        b.close()
    assert got == total


# -- exact record shuffling over an index (?index=1&shuffle=1) --------------
def _rowid_rec(tmp_path, rows=2000, rows_per_record=25):
    from dmlc_core_tpu.io.convert import (build_recordio_index,
                                          rows_to_recordio)
    src = tmp_path / "ids.libsvm"
    src.write_text("".join(f"{i} 0:{float(i)}\n" for i in range(rows)))
    rec = str(tmp_path / "ids.rec")
    rows_to_recordio(str(src), rec, rows_per_record=rows_per_record)
    nrec = build_recordio_index(rec)
    assert nrec == rows // rows_per_record
    return rec, rows


def _rec_order(uri, part=0, npart=1):
    out = []
    with NativeParser(uri, part=part, npart=npart, fmt="rec") as p:
        for b in p:
            out.extend(b.label.astype(int).tolist())
    return out


def test_indexed_shuffle_exact_cover_and_epochs(tmp_path):
    rec, rows = _rowid_rec(tmp_path)
    plain = _rec_order(rec)
    assert plain == list(range(rows))
    s = _rec_order(rec + "?index=1&shuffle=1&shuffle_seed=7")
    assert sorted(s) == plain and s != plain
    assert _rec_order(rec + "?index=1&shuffle=1&shuffle_seed=7") == s
    with NativeParser(rec + "?index=1&shuffle=1", fmt="rec") as p:
        e1 = [x for b in p for x in b.label.astype(int).tolist()]
        p.before_first()
        e2 = [x for b in p for x in b.label.astype(int).tolist()]
    assert sorted(e1) == sorted(e2) == plain and e1 != e2
    # record-count partitioning composes with the index
    cover = sorted(sum((_rec_order(rec + "?index=1", part=k, npart=4)
                        for k in range(4)), []))
    assert cover == plain


def test_indexed_shuffle_through_device_iter(tmp_path):
    rec, rows = _rowid_rec(tmp_path)
    labels = []
    with DeviceRowBlockIter(rec + "?index=1&shuffle=1&shuffle_seed=2",
                            fmt="rec", batch_rows=256,
                            to_device=False) as it:
        for b in it:
            labels.extend(np.asarray(b.label).reshape(-1)[
                :b.total_rows].astype(int).tolist())
    assert sorted(labels) == list(range(rows))
    assert labels != list(range(rows))


def test_indexed_shuffle_arg_validation(tmp_path):
    rec, _ = _rowid_rec(tmp_path)
    with pytest.raises(DMLCError, match="shuffle_parts"):
        NativeParser(rec + "?index=1&shuffle_parts=4", fmt="rec")
    with pytest.raises(DMLCError, match="index"):
        NativeParser(rec + "?shuffle=1", fmt="rec")
    src = tmp_path / "t.libsvm"
    src.write_text("1 0:1.0\n")
    with pytest.raises(DMLCError, match="rec"):
        NativeParser(str(src) + "?index=1")


def test_index_builder_handles_multi_chunk_and_escaped_records(tmp_path):
    from dmlc_core_tpu.io.convert import (build_recordio_index,
                                          rows_to_recordio)
    from dmlc_core_tpu.io.native import NativeRecordIOWriter
    # file larger than one 1 MiB read chunk: the walk must stay aligned
    # when a record payload straddles chunk boundaries
    rng = np.random.default_rng(0)
    src = tmp_path / "big.libsvm"
    with open(src, "w") as f:
        for i in range(10000):
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.uniform():.6f}" for j in range(30)) + "\n")
    rec = str(tmp_path / "big.rec")
    rows_to_recordio(str(src), rec, rows_per_record=200)
    assert (tmp_path / "big.rec").stat().st_size > 2 * (1 << 20)
    # the index must carry one entry per actual record (record COUNT is an
    # implementation detail: the converter cuts records within parsed
    # blocks, so chunking/worker count adds a short tail record per
    # slice — at least ceil(rows/rows_per_record), no fixed upper bound)
    from dmlc_core_tpu.io.native import NativeRecordIOReader
    with NativeRecordIOReader(rec) as r:
        nrec = sum(1 for _ in r)
    assert nrec >= 50
    assert build_recordio_index(rec) == nrec
    # escaped records (embedded aligned magics split into parts) index at
    # their first part, once each
    rec2 = str(tmp_path / "esc.rec")
    magic = (0xCED7230A).to_bytes(4, "little")
    with NativeRecordIOWriter(rec2) as w:
        for _ in range(50):
            w.write_record(b"A" * 4096 + magic * 3 + b"B" * 4096)
    assert build_recordio_index(rec2) == 50


def test_shuffle_batch_requires_index(tmp_path):
    rec, _ = _rowid_rec(tmp_path)
    with pytest.raises(DMLCError, match="shuffle_batch"):
        NativeParser(rec + "?shuffle_parts=4&shuffle_batch=64", fmt="rec")
