"""The ``criteo`` text format (ISSUE 32): the Criteo click logs as published,
``label \\t I1..I13 \\t C1..C26``, every present cell hashed with its column
to an id below ``2**hash_bits``, value 1, empty cells skipped.

The rule is stated three times: natively (cpp/src/criteo_hash.h, run by
``CriteoParser`` in cpp/src/parser.cc), as numpy in the package
(dmlc_core_tpu/data/criteo.py, the oracle) and as the benchmark's plain
reference (benchmarks/reference/criteo.py, which imports nothing of the
program). Held here: the three agree id for id; golden lines (missing cells,
``\\r\\n``, a last line without newline); lines of 39 or 41 cells and a label
that is no number are refused by name, ``hash_bits`` absent, 0 and 32 too;
ids do not move with ``nthread``, the SIMD tier or chunk boundaries;
``part/npart`` cover every row once; text -> ``.crec`` / ``.rec`` -> batches
equal text -> batches; ``DeviceRowBlockIter(fmt="criteo")`` equals
``HostBatcher`` over the oracle's rows, ``cols`` and ``slot`` included; a shard
cache written at 24 bits is not replayed at 25; the lane counts its cells;
``examples/train.py --format criteo`` steps an FM; and ``data.Parser.create``
knows the formats the native registry has, each registered once.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.data import Parser, criteo
from dmlc_core_tpu.io.convert import rows_to_csr_recordio, rows_to_recordio
from dmlc_core_tpu.io.native import (NativeParser, native_criteo_id,
                                     parser_format_names)
from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter, HostBatcher,
                                           _expand_cols)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from reference import criteo as reference  # noqa: E402

HEX = b"0123456789abcdef"


def _line(rng, label, missing=0.15) -> bytes:
    """One line of the logs' shape: decimal integer cells (now and then
    negative or long), 8-hex-digit categorical cells, some of either empty."""
    cells = [b"%d" % label]
    for c in range(39):
        if rng.random() < missing:
            cells.append(b"")
        elif c < 13:
            cells.append(b"%d" % rng.integers(-3, 10 ** int(rng.integers(1, 12))))
        else:
            cells.append(bytes(HEX[i] for i in rng.integers(0, 16, 8)))
    return b"\t".join(cells)


def _file(path, rows, seed=0, eol=b"\n", labels=None):
    rng = np.random.default_rng(seed)
    lines = [_line(rng, i % 2 if labels is None else labels[i])
             for i in range(rows)]
    path.write_bytes(eol.join(lines) + eol)
    return str(path), lines


def _drain(uri, **kw):
    """label, row lengths and ids of every block, concatenated; the blocks
    carry no value, weight, qid or field."""
    label, lens, index = [], [], []
    kw.setdefault("fmt", "criteo")
    with NativeParser(uri, **kw) as p:
        for b in p:
            assert b.value is None and b.weight is None
            assert b.qid is None and b.field is None
            label.append(b.label.copy())
            lens.append(np.diff(b.offset))
            index.append(b.index.copy())
    return (np.concatenate(label), np.concatenate(lens),
            np.concatenate(index))


# -- the rule, three times ---------------------------------------------------------

@pytest.mark.parametrize("hash_bits", [1, 24, 25, 31, 40, 63])
def test_native_oracle_and_reference_agree_id_for_id(hash_bits):
    rng = np.random.default_rng(hash_bits)
    columns = rng.integers(0, 39, 400)
    cells = [bytes(rng.integers(1, 256, int(n)).astype(np.uint8))
             for n in rng.integers(0, 30, 400)]
    cells[:4] = [b"", b"0", b"68fd1e64", b"68fd1e64\0"]
    native = [native_criteo_id(int(c), cell, hash_bits)
              for c, cell in zip(columns, cells)]
    oracle = criteo.fold(criteo.hash64(columns, cells), hash_bits)
    plain = [reference.cell_id(int(c), cell, hash_bits)
             for c, cell in zip(columns, cells)]
    width = 32
    text = np.zeros((len(cells), width), np.uint8)
    for i, cell in enumerate(cells):
        text[i, :len(cell)] = np.frombuffer(cell, np.uint8)
    many = reference.cell_ids(columns, text,
                              np.array([len(c) for c in cells]), hash_bits)
    assert native == oracle.tolist() == plain == many.tolist()
    assert max(native) < 2 ** hash_bits
    # a cell and the same cell with a zero byte after it; the same string
    # in two columns
    assert native[2] != native[3]
    assert native_criteo_id(13, b"68fd1e64", hash_bits) != \
        native_criteo_id(14, b"68fd1e64", hash_bits) or hash_bits == 1


def test_the_worked_id_of_the_documentation():
    """doc/parsing.md works this id by hand: column 13 (C1), ``68fd1e64``."""
    assert reference.hash64(13, b"68fd1e64") == 0x91FB01B9CF143E61
    assert reference.cell_id(13, b"68fd1e64", 25) == 15679448
    assert native_criteo_id(13, b"68fd1e64", 25) == 15679448


# -- golden lines ------------------------------------------------------------------

GOLDEN = (
    b"\t".join([b"1", b"5", b"", b"-1", b"1234567890123", b"0", b"", b"",
                b"7", b"", b"", b"3", b"", b"2",
                b"68fd1e64", b"80e26c9b", b"", b"fb936136", b"7b4723c4"]
               + [b""] * 20 + [b"3a171ecb"]) + b"\r\n"
    + b"0" + b"\t" * 39 + b"\n"
    + b"\n"
    + b"0.5\t1\t2\t3\t4\t5\t6\t7\t8\t9\t10\t11\t12\t13\t"
    + b"\t".join([b"0000000%x" % i for i in range(10)]
                 + [b"abcdef%02d" % i for i in range(16)]))


def test_golden_lines_against_the_oracle_and_the_reference(tmp_path):
    path = tmp_path / "golden.tsv"
    path.write_bytes(GOLDEN)
    label, lens, index = _drain(f"{path}?hash_bits=25")
    want = criteo.parse(GOLDEN, 25)
    assert label.tolist() == want.label.tolist() == [1.0, 0.0, 0.5]
    assert lens.tolist() == np.diff(want.offset).tolist() == [12, 0, 39]
    assert index.dtype == np.uint32
    assert index.tolist() == want.index.tolist()
    plain = []
    for line in GOLDEN.replace(b"\r", b"").split(b"\n"):
        if line:
            plain += reference.line_ids(line, 25)[1]
    assert index.tolist() == plain
    # the first row's first three entries: I1 = 5, I3 = -1, I4 (two words)
    assert index[:3].tolist() == [reference.cell_id(0, b"5", 25),
                                  reference.cell_id(2, b"-1", 25),
                                  reference.cell_id(3, b"1234567890123", 25)]


REFUSED = {
    "39_cells": (b"1" + b"\t7" * 38, "has 39 cells, not 40"),
    "41_cells": (b"1" + b"\t7" * 40, "has 41 cells, not 40"),
    "one_cell": (b"1", "has 1 cells, not 40"),
    "label_text": (b"click" + b"\t7" * 39, "label that is not a number"),
    "label_empty": (b"\t7" * 39, "label that is not a number"),
    "label_tail": (b"1x" + b"\t7" * 39, "label that is not a number"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_malformed_line_is_refused_by_name(tmp_path, case):
    """Never a silently short or skipped row: the error names the format,
    where the line starts in its block, and what is wrong with it."""
    bad, why = REFUSED[case]
    good = b"0" + b"\t1" * 39
    path = tmp_path / "bad.tsv"
    path.write_bytes(good + b"\n" + bad + b"\n" + good + b"\n")
    with pytest.raises(DMLCError) as e:
        _drain(f"{path}?hash_bits=20", nthread=1)
    msg = str(e.value)
    assert "criteo" in msg and why in msg
    assert f"byte offset {len(good) + 1} " in msg
    with pytest.raises(DMLCError):
        criteo.parse(bad, 20)


@pytest.mark.parametrize("args,index64,why", [
    ("", False, "hash_bits"),
    ("?hash_bits=0", False, "hash_bits"),
    ("?hash_bits=32", False, "hash_bits=32"),
    ("?hash_bits=64", True, "hash_bits"),
    ("?hash_bits=many", False, "hash_bits"),
])
def test_hash_bits_has_no_default_and_a_range(tmp_path, args, index64, why):
    path, _ = _file(tmp_path / "a.tsv", 10)
    with pytest.raises(DMLCError, match=why):
        _drain(path + args, index64=index64)


def test_index64_takes_more_than_31_bits(tmp_path):
    path, lines = _file(tmp_path / "a.tsv", 50)
    _, _, index = _drain(path + "?hash_bits=40", index64=True)
    assert index.dtype == np.uint64
    assert index.tolist() == criteo.parse(b"\n".join(lines), 40).index.tolist()
    assert int(index.max()) >= 2 ** 32


# -- determinism ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def big(tmp_path_factory):
    path, lines = _file(tmp_path_factory.mktemp("criteo") / "big.tsv", 20000,
                        seed=3, labels=list(range(20000)))
    return path + "?hash_bits=25", criteo.parse(b"\n".join(lines), 25)


@pytest.fixture()
def small_chunks(monkeypatch):
    # ~64 KB chunks: the 4.6 MB file spans dozens of chunks in flight
    monkeypatch.setenv("DCT_CHUNK_SIZE_KB", "64")


@pytest.mark.parametrize("how", [
    dict(nthread=1, threaded=False),
    dict(nthread=4, threaded=True, chunks_in_flight=5),
    dict(nthread=4, threaded=False),
], ids=["serial", "pipelined-4", "barrier-4"])
def test_ids_equal_the_oracles_over_threads_and_chunk_boundaries(
        big, small_chunks, how):
    uri, want = big
    label, lens, index = _drain(uri, **how)
    assert label.tolist() == want.label.tolist()
    assert lens.tolist() == np.diff(want.offset).tolist()
    assert index.tobytes() == want.index.tobytes()


@pytest.mark.parametrize("tier", ["0", "swar", "sse2", "avx2", "1"])
def test_ids_do_not_move_with_the_simd_tier(big, monkeypatch, tier):
    uri, want = big
    monkeypatch.setenv("DMLC_PARSE_SIMD", tier)
    label, lens, index = _drain(uri, nthread=2)
    assert label.tolist() == want.label.tolist()
    assert index.tobytes() == want.index.tobytes()


@pytest.mark.parametrize("npart", [1, 3, 4])
def test_parts_cover_every_row_exactly_once(big, small_chunks, npart):
    uri, want = big
    parts = [_drain(uri, part=k, npart=npart) for k in range(npart)]
    label = np.concatenate([p[0] for p in parts])
    assert label.tolist() == want.label.tolist()   # the labels are 0..R-1
    assert np.concatenate([p[2] for p in parts]).tobytes() == \
        want.index.tobytes()
    assert npart == 1 or all(len(p[0]) for p in parts)


def test_crlf_and_a_last_line_without_newline(tmp_path):
    path, lines = _file(tmp_path / "crlf.tsv", 300, seed=5, eol=b"\r\n")
    data = (tmp_path / "crlf.tsv").read_bytes()
    (tmp_path / "crlf.tsv").write_bytes(data[:-2])
    want = criteo.parse(b"\n".join(lines), 22)
    label, lens, index = _drain(path + "?hash_bits=22")
    assert len(label) == 300 and index.tolist() == want.index.tolist()


# -- the normal entry points ---------------------------------------------------------

def _batches(uri, fmt, **kw):
    out = []
    with DeviceRowBlockIter(uri, fmt=fmt, batch_rows=512, to_device=False,
                            min_nnz_bucket=64, **kw) as it:
        for b in it:
            R = b.rows_per_shard
            n = int((b.row < R).sum())
            out.append((np.array(b.label), np.array(b.row[0, :n]),
                        np.array(_expand_cols(b.cols, b.slot)[0, :n]),
                        np.array(b.val[0, :n]), b.total_rows, b.total_nnz,
                        b.total_distinct))
    return out


def _same_batches(got, want, shapes=True):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            assert np.array_equal(a, b)


@pytest.fixture(scope="module")
def medium(tmp_path_factory):
    path, lines = _file(tmp_path_factory.mktemp("criteo_m") / "m.tsv", 2000,
                        seed=9)
    return path, b"\n".join(lines)


def test_text_to_crec_and_rec_give_the_texts_batches(medium, tmp_path):
    path, _ = medium
    uri = path + "?hash_bits=20"
    text = _batches(uri, "criteo")
    assert len(text) == 4 and text[-1][4] == 2000 - 3 * 512
    assert all((b[3] == 1.0).all() for b in text)     # every value is 1
    crec = str(tmp_path / "m.crec")
    assert rows_to_csr_recordio(uri, crec, fmt="criteo",
                                rows_per_record=300) == 2000
    _same_batches(_batches(crec, "crec"), text)
    rec = str(tmp_path / "m.rec")
    assert rows_to_recordio(uri, rec, fmt="criteo") == 2000
    _same_batches(_batches(rec, "rec"), text)


class _Blocks:
    """A parser over the oracle's rows, in blocks of uneven size."""

    def __init__(self, rows, cuts):
        self.rows, self.cuts, self.at = rows, cuts, 0

    def next_block(self):
        if self.at + 1 >= len(self.cuts):
            return None
        r0, r1 = self.cuts[self.at], self.cuts[self.at + 1]
        self.at += 1
        lo, hi = int(self.rows.offset[r0]), int(self.rows.offset[r1])
        return criteo.Rows(self.rows.label[r0:r1],
                           self.rows.offset[r0:r1 + 1] - np.uint64(lo),
                           self.rows.index[lo:hi])

    def before_first(self):
        self.at = 0


@pytest.mark.parametrize("shards", [1, 4])
def test_device_iter_equals_host_batcher_over_the_oracles_rows(medium, shards):
    """The whole lane against the oracle: the packs, the shards' distinct
    columns ``cols`` and every entry's ``slot`` included."""
    import jax
    from dmlc_core_tpu.tpu import data_mesh
    path, data = medium
    rows = criteo.parse(data, 20)
    host = HostBatcher(_Blocks(rows, [0, 700, 701, 1500, 2000]), 512, shards,
                       64, layout="csr")
    if shards > len(jax.devices()):
        pytest.skip("needs four host devices")
    with DeviceRowBlockIter(path + "?hash_bits=20", fmt="criteo",
                            batch_rows=512, mesh=data_mesh(shards),
                            to_device=False, min_nnz_bucket=64) as it:
        count = 0
        for got in it:
            want = host.next_batch()
            assert sorted(got.tree()) == sorted(want.tree()) == \
                ["aux", "big", "cols"]
            for leaf in ("aux", "big", "cols"):
                assert np.array_equal(np.asarray(got.tree()[leaf]),
                                      want.tree()[leaf]), leaf
            assert (got.total_rows, got.total_nnz, got.total_distinct) == \
                (want.total_rows, want.total_nnz, want.total_distinct)
            count += 1
    assert count == 4 and host.next_batch() is None


def test_data_parser_create_takes_the_format_and_its_argument(medium):
    path, data = medium
    want = criteo.parse(data, 18)
    for uri, fmt in ((path + "?hash_bits=18", "criteo"),
                     (path + "?format=criteo&hash_bits=18", "auto")):
        with Parser.create(uri, fmt=fmt) as p:
            index = np.concatenate([b.index.copy() for b in p])
        assert index.tolist() == want.index.tolist()
    with pytest.raises(DMLCError, match="URI args"):
        Parser.create(path, fmt="criteo", hash_bits=18)


def test_parser_create_knows_the_formats_of_the_native_registry(medium):
    """A format is registered once, in ``RegisterBuiltinParsers``:
    ``Parser.create`` reads the native registry and keeps no list."""
    names = parser_format_names()
    assert set(names) == {"libsvm", "csv", "libfm", "criteo", "rec"}
    with pytest.raises(DMLCError) as e:
        Parser.create(medium[0], fmt="no-such-format")
    for name in names:
        assert repr(name) in str(e.value)


def test_a_shard_cache_written_at_24_bits_is_not_replayed_at_25(
        medium, tmp_path):
    path, data = medium
    cdir = str(tmp_path / "cache")
    for _ in range(2):          # transcode, then replay
        for bits in (24, 25):
            _, _, index = _drain(f"{path}?hash_bits={bits}", cache_dir=cdir)
            assert index.tolist() == criteo.parse(data, bits).index.tolist()


def test_the_lane_counts_its_cells_once_a_block(medium):
    path, data = medium
    telemetry.enable(True)

    def read():
        snap = telemetry.snapshot(native=True)
        return [sum(c["value"] for c in snap["counters"]
                    if c["name"] == name
                    and c["labels"] == {"format": "criteo"})
                for name in ("parse_cells_total",
                             "parse_cells_missing_total")]
    before = read()
    _, lens, index = _drain(path + "?hash_bits=20")
    cells, missing = (a - b for a, b in zip(read(), before))
    assert cells == 39 * len(lens) == 39 * 2000
    assert missing == cells - len(index)
    assert 0.10 < missing / cells < 0.20


def test_train_example_steps_an_fm_on_a_criteo_file(tmp_path):
    path, _ = _file(tmp_path / "day_0.tsv", 2000, seed=11)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train.py"),
         path + "?hash_bits=14", "--format", "criteo", "--model", "fm",
         "--fm-rank", "4", "--batch-rows", "512", "--epochs", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "epoch 1: mean loss" in r.stdout and "4 batches, 2000 rows" \
        in r.stdout, r.stdout[-2000:]
