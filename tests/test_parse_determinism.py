"""Multithreaded parse determinism (ISSUE 1 acceptance pin).

The multi-chunk in-flight pipeline (cpp/src/parser.h PipelinedParser) must
deliver output BYTE-IDENTICAL to a synchronous single-threaded parse:
reader-stage tiling is a pure function of chunk bytes, workers race only on
who parses which slice, and the ordered-reassembly stage serves chunks in
input order. These tests concatenate every per-row/per-feature array across
blocks for all four text formats plus the binary rec lane and assert exact
equality between nthread=1 (threaded=False, the serial reference) and a
4-worker pipeline with several chunks in flight. Chunks are shrunk via
DCT_CHUNK_SIZE_KB so the fixtures span many chunks.
"""

import numpy as np
import pytest

from dmlc_core_tpu.io.native import NativeParser

ROWS = 30000


@pytest.fixture(autouse=True)
def _small_chunks(monkeypatch):
    # read at split construction (input_split.cc DefaultChunkSize): ~64 KB
    # chunks turn the ~1-2 MB fixtures into dozens of in-flight chunks
    monkeypatch.setenv("DCT_CHUNK_SIZE_KB", "64")


def _libsvm_fixture(tmp_path):
    rng = np.random.default_rng(5)
    path = tmp_path / "det.libsvm"
    with open(path, "w") as f:
        for i in range(ROWS):
            if i % 997 == 0:
                f.write("# comment line\n\n")  # skipped identically
            feats = " ".join(
                f"{j}:{rng.uniform(-4, 4):.6f}" for j in range(10))
            f.write(f"{i % 3}:{1.0 + i % 5} qid:{i % 11} {feats}\n")
    return str(path)


def _csv_fixture(tmp_path):
    rng = np.random.default_rng(6)
    path = tmp_path / "det.csv"
    with open(path, "w") as f:
        for i in range(ROWS):
            cells = [f"{v:.6f}" for v in rng.uniform(-4, 4, size=9)]
            if i % 7 == 0:
                cells[3] = ""  # missing value keeps its column index
            f.write(f"{i % 2}," + ",".join(cells) + "\n")
    return str(path) + "?format=csv&label_column=0"


def _libfm_fixture(tmp_path):
    rng = np.random.default_rng(8)
    path = tmp_path / "det.libfm"
    with open(path, "w") as f:
        for i in range(ROWS):
            feats = " ".join(
                f"{j % 5}:{j}:{rng.uniform(-2, 2):.6f}" for j in range(8))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


def _criteo_fixture(tmp_path):
    rng = np.random.default_rng(9)
    path = tmp_path / "det.tsv"
    with open(path, "w") as f:
        for i in range(ROWS):
            ints = ["" if rng.random() < 0.2 else str(rng.integers(0, 5000))
                    for _ in range(13)]
            cats = ["" if rng.random() < 0.1 else f"{rng.integers(2**32):08x}"
                    for _ in range(26)]
            f.write("\t".join([str(i % 2)] + ints + cats) + "\n")
    return str(path) + "?format=criteo&hash_bits=25"


def _rec_fixture(tmp_path):
    from dmlc_core_tpu.io.convert import rows_to_recordio
    src = _libsvm_fixture(tmp_path)
    dst = str(tmp_path / "det.rec")
    # small records so the rec stream also spans many chunks
    rows_to_recordio(src, dst, fmt="libsvm", rows_per_record=256)
    return dst


def _snapshot(uri, fmt="auto", **kw):
    """Concatenated copies of every array the parser surfaces, in delivery
    order (offsets as per-row lengths, which concatenation preserves)."""
    parts = {k: [] for k in ("label", "weight", "qid", "field", "index",
                             "value", "rowlen")}
    with NativeParser(uri, fmt=fmt, **kw) as p:
        for b in p:
            parts["rowlen"].append(np.diff(b.offset))
            for k in ("label", "weight", "qid", "field", "index", "value"):
                v = getattr(b, k)
                if v is not None:
                    parts[k].append(v.copy())
    return {k: (np.concatenate(v) if v else None)
            for k, v in parts.items()}


FIXTURES = [("libsvm", _libsvm_fixture), ("csv", _csv_fixture),
            ("libfm", _libfm_fixture), ("criteo", _criteo_fixture),
            ("rec", _rec_fixture)]


@pytest.mark.parametrize("name,make", FIXTURES, ids=[f[0] for f in FIXTURES])
def test_nthread4_byte_identical_to_serial(tmp_path, name, make):
    uri = make(tmp_path)
    serial = _snapshot(uri, nthread=1, threaded=False)
    assert serial["label"] is not None and len(serial["label"]) >= ROWS
    piped = _snapshot(uri, nthread=4, threaded=True, chunks_in_flight=5)
    for key, want in serial.items():
        got = piped[key]
        if want is None:
            assert got is None, f"{name}/{key} appeared only multithreaded"
            continue
        assert got is not None, f"{name}/{key} lost in the pipeline"
        assert want.dtype == got.dtype, f"{name}/{key} dtype changed"
        # byte-identical, not allclose: same parse code must have run over
        # the same slices in the same order
        assert want.tobytes() == got.tobytes(), (
            f"{name}/{key}: multithreaded parse diverged from serial")


def test_pipeline_stats_surface(tmp_path):
    uri = _libsvm_fixture(tmp_path)
    with NativeParser(uri, nthread=2, threaded=True, chunks_in_flight=3) as p:
        rows = sum(b.num_rows for b in p)
        stats = p.pipeline_stats()
    assert rows >= ROWS
    assert stats is not None
    assert stats["chunks_read"] > 1  # small chunks -> many chunks
    assert stats["capacity"] == 3
    assert stats["workers"] == 2
    assert stats["blocks_delivered"] > 0
    assert 0 < stats["occupancy_avg"] <= stats["capacity"]
    assert stats["inflight_peak"] <= stats["capacity"]
    # threaded=False carries no pipeline
    with NativeParser(uri, nthread=2, threaded=False) as p:
        next(iter(p))
        assert p.pipeline_stats() is None


def test_chunks_in_flight_uri_arg(tmp_path):
    # the knob also rides URI sugar (parser.cc Create parse_uarg) so
    # batcher/device lanes can set it without a new ABI entry point
    uri = _libsvm_fixture(tmp_path)
    with NativeParser(uri + "?chunks_in_flight=2", nthread=2) as p:
        rows = sum(b.num_rows for b in p)
        stats = p.pipeline_stats()
    assert rows >= ROWS
    assert stats["capacity"] == 2
