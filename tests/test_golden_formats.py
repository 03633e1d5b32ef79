"""Byte-exact golden fixtures for the three binary on-disk formats.

Converting a fixed 2-row libsvm source must reproduce these exact bytes —
any drift in the RecordIO framing (magic 0xced7230a, lrec, padding), the
DRB1 row-block wire format, the DRD1 dense header, or the DRC1 CSR-plane
layout (incl. the window-maxima table) fails here before it can corrupt
cross-version data. The layouts are little-endian regardless of host; the
native decode suite drives the big-endian branches against the same bytes
(cpp/test/test_core.cc TestRecordIOGoldenBytes /
TestBinaryLaneBEDecodeBranches / TestGoldenBinaryRecordsDecode — the
QEMU-free analog of the reference s390x lane, scripts/test_script.sh:60-65).
"""

import numpy as np

from dmlc_core_tpu.io.convert import (rows_to_csr_recordio,
                                      rows_to_dense_recordio,
                                      rows_to_recordio)
from dmlc_core_tpu.tpu.device_iter import CsrRecHostBatcher

SRC = "1 0:0.5 2:-1.5\n0 1:2.0\n"

GOLDEN_REC = (
    "0a23d7ce98000000314252440000000003000000000000000000000000000000"
    "0200000000000000030000000000000002000000000000000000803f00000000"
    "0000000000000000000000000000000000000000000000000300000000000000"
    "0000000002000000010000000300000000000000000000 3f0000c0bf00000040"
    "0000000000000000000000000000000000000000020000000000000000000000"
).replace(" ", "")

GOLDEN_DREC = (
    "0a23d7ce300000003144524400000000020000000300000000"
    "00803f000000000000003f000000000000c0bf000000000000004000000000"
)

GOLDEN_CREC = (
    "0a23d7ce580000003143524400000000020000000200000003000000000000000"
    "2000000000000000200000000000000030000000000000002000000010000000"
    "000803f000000000000000002000000010000000000003f0000c0bf00000040"
)


def _convert(tmp_path, fn, name, **kw):
    src = tmp_path / "g.libsvm"
    src.write_text(SRC)
    dst = tmp_path / name
    fn(str(src), str(dst), **kw)
    return dst.read_bytes()


def test_rec_bytes_golden(tmp_path):
    got = _convert(tmp_path, rows_to_recordio, "g.rec")
    assert got.hex() == GOLDEN_REC


def test_drec_bytes_golden(tmp_path):
    got = _convert(tmp_path, rows_to_dense_recordio, "g.drec",
                   dtype="float32")
    assert got.hex() == GOLDEN_DREC


def test_crec_bytes_golden(tmp_path):
    got = _convert(tmp_path, rows_to_csr_recordio, "g.crec")
    assert got.hex() == GOLDEN_CREC


def test_crec_golden_decodes(tmp_path):
    """The committed bytes (not just freshly converted ones) decode to the
    source rows — guards reader/writer drifting together."""
    path = tmp_path / "fixed.crec"
    path.write_bytes(bytes.fromhex(GOLDEN_CREC))
    b = CsrRecHostBatcher(str(path), batch_rows=2, min_nnz_bucket=4)
    try:
        batch = b.next_batch()
        assert batch.total_rows == 2
        assert batch.label.reshape(-1).tolist() == [1.0, 0.0]
        assert batch.cols[0][batch.slot[0, :3]].tolist() == [0, 2, 1]
        np.testing.assert_allclose(batch.val.reshape(-1)[:3],
                                   [0.5, -1.5, 2.0])
        assert b.next_batch() is None
    finally:
        b.close()
