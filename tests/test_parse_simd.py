"""Differential fuzz: the SIMD text-ingest lanes (cpp/src/simd_scan.h,
doc/parsing.md) must produce RowBlocks byte-identical to the scalar lane
over adversarial inputs — CRLF, UTF-8 BOM, blank/whitespace-only lines,
>8-digit runs, truncated trailing tokens, '+'/hex-shaped tokens, exponent
notation, out-of-envelope mantissas, and 64-byte-block / load-guard
boundaries landing mid-token — for all three text formats and both index
widths. DMLC_PARSE_SIMD=0 must force the scalar lane (the kill switch),
and the chosen lane must be visible through pipeline_stats().

The C++-level twin (test_core --parse) covers every kernel tier and the
decoder primitives; this suite covers the full NativeParser path — URI,
chunking, pipeline, ctypes views — end to end.
"""

import os

import numpy as np
import pytest

from dmlc_core_tpu.io.native import NativeParser

# libsvm rows exercising every delegate path of the fused lane
ADVERSARIAL_LIBSVM = (
    b"\xef\xbb\xbf"
    b"1 0:2.5 3:-0.75 7:1e-4\r\n"
    b"0\r"
    b"# a comment line with 5:5 inside\n"
    b"   \t \n"
    b"2:0.5 3:9.25 11:3\n"
    b"1:1.5 2 qid:7 4:4\n"
    b"-1 qid:9 1:0.5 2:0.25\n"
    b"3.5:2.25 1:1 2:2\n"
    b"1 1:0.123456789012345678 2:2.5\n"
    b"1 3:nan 4:inf 5:0x10\n"
    b"1 +5:2.5 6:+0.5\n"
    b"garbage line here\n"
    b"1 2:3 trailing junk\n"
    b"1 1:2.5e309 2:1\n"
    b"0 1:.5 2:5. 3:.\n"
    b"1 000000000000001:2 2:3\n"
    b"1 12345678:0.25 23456789:1.5\n"
    b"1 7:1.25 # trailing comment\n"
    b"1 8:"
)

ADVERSARIAL_CSV = (
    b"\xef\xbb\xbf"
    b"1,2.5,,-0.75,1e-4\r\n"
    b"\r"
    b",,,\n"
    b"0, .5 ,5.,nan\n"
    b"1,0x10,inf,-inf\n"
    b"3,  2.25,junk,4.5trailing\n"
    b"9,123456789012345678901,0.123456789012345,+7\n"
    b"2,-3.5,1.25,"
)

ADVERSARIAL_LIBFM = (
    b"\xef\xbb\xbf"
    b"1 0:1:0.5 2:3:-0.25\r\n"
    b"0\r"
    b"# comment 1:2:3\n"
    b"  \t\n"
    b"1:0.5 2:3:1e-4 7\n"
    b"-1 1:2 3:4:5.5\n"
    b"1 1:2:3:4 5:6:7\n"
    b"garbage 1:2:3\n"
    b"1 2:+3:0.5 4:5:+1.5\n"
    b"0 1:.5:.25 2:5.:1\n"
    b"1 3:4:"
)


def _collect(path, fmt, index64, env_tier, nthread=2):
    """Parse the file under a pinned DMLC_PARSE_SIMD tier; returns the
    concatenated arrays of every block plus the reported lane. Corpora
    that legitimately fail validation (e.g. ragged value/index mixes) must
    fail IDENTICALLY in every lane, so a DMLCError becomes an ("error",
    message) outcome instead of aborting the comparison."""
    from dmlc_core_tpu.base import DMLCError
    old = os.environ.get("DMLC_PARSE_SIMD")
    os.environ["DMLC_PARSE_SIMD"] = env_tier
    try:
        arrays = {k: [] for k in
                  ("offset_deltas", "label", "weight", "qid", "field",
                   "index", "value")}
        lane = None
        try:
            with NativeParser(str(path), fmt=fmt, index64=index64,
                              nthread=nthread) as p:
                for blk in p:
                    arrays["offset_deltas"].append(
                        np.diff(blk.offset.copy()))
                    arrays["label"].append(blk.label.copy())
                    arrays["index"].append(blk.index.copy())
                    for name in ("weight", "qid", "field", "value"):
                        a = getattr(blk, name)
                        if a is not None:
                            arrays[name].append(a.copy())
                stats = p.pipeline_stats()
                lane = stats["simd_lane"] if stats else None
        except DMLCError as e:
            return ("error", str(e)), lane
        out = {}
        for k, chunks in arrays.items():
            out[k] = (np.concatenate(chunks) if chunks
                      else np.empty(0))
        return out, lane
    finally:
        if old is None:
            os.environ.pop("DMLC_PARSE_SIMD", None)
        else:
            os.environ["DMLC_PARSE_SIMD"] = old


def _assert_same(a, b, ctx):
    if isinstance(a, tuple) or isinstance(b, tuple):
        # identical-error outcomes count as lane agreement
        assert a == b, (ctx, a, b)
        return
    assert set(a) == set(b)
    for k in a:
        got, want = a[k], b[k]
        assert got.shape == want.shape, (ctx, k, got.shape, want.shape)
        # bitwise: float arrays may legitimately hold NaN
        assert got.tobytes() == want.tobytes(), (ctx, k)


CORPORA = [("libsvm", ADVERSARIAL_LIBSVM), ("csv", ADVERSARIAL_CSV),
           ("libfm", ADVERSARIAL_LIBFM)]


@pytest.mark.parametrize("fmt,corpus", CORPORA)
@pytest.mark.parametrize("index64", [False, True])
def test_simd_equals_scalar_adversarial(tmp_path, fmt, corpus, index64):
    path = tmp_path / f"adv.{fmt}"
    path.write_bytes(corpus)
    uri = str(path) + ("?format=csv&label_column=0" if fmt == "csv" else "")
    scalar, lane0 = _collect(uri, fmt, index64, "0")
    assert lane0 in ("scalar", None)  # DMLC_PARSE_SIMD=0 is the kill switch
    if lane0 is None:  # corpus errored before stats: outcome still compared
        assert isinstance(scalar, tuple)
    for tier in ("swar", "sse2", "avx2", "1"):
        simd, _ = _collect(uri, fmt, index64, tier)
        _assert_same(simd, scalar, (fmt, index64, tier))


@pytest.mark.parametrize("fmt", ["libsvm", "libfm"])
def test_simd_equals_scalar_indexing_modes(tmp_path, fmt):
    """The 1-based decrement is hoisted into the decode path for the
    forced mode; every mode must stay lane-identical (incl. the id-0 wrap
    the scalar post-pass produced)."""
    body = (b"1 1:2.5 3:4.5\n0 2:1.5\n1 0:1 5:2\n" if fmt == "libsvm"
            else b"1 1:1:2.5 2:3:4.5\n0 1:2:1.5\n1 0:0:1 2:5:2\n")
    path = tmp_path / f"mode.{fmt}"
    path.write_bytes(body)
    for mode in ("zero_based", "one_based", "auto"):
        uri = f"{path}?format={fmt}&indexing_mode={mode}"
        scalar, _ = _collect(uri, fmt, False, "0")
        simd, _ = _collect(uri, fmt, False, "1")
        _assert_same(simd, scalar, (fmt, mode))


def test_simd_equals_scalar_block_boundaries(tmp_path):
    """Randomized rows truncated at every offset over the last lines, so
    64-byte scan blocks and the fused decoders' 8/16-byte load guards land
    mid-token in every possible way."""
    rng = np.random.default_rng(29)
    rows = []
    for i in range(120):
        feats = " ".join(
            f"{rng.integers(0, 10**int(rng.integers(1, 10)))}:"
            f"{rng.uniform(-100, 100):.{int(rng.integers(0, 9))}f}"
            for _ in range(int(rng.integers(0, 5))))
        rows.append(f"{i % 3}{' ' if feats else ''}{feats}")
    full = ("\n".join(rows) + "\n").encode()
    for cut in range(max(0, len(full) - 80), len(full) + 1):
        path = tmp_path / "cut.libsvm"
        path.write_bytes(full[:cut])
        scalar, _ = _collect(path, "libsvm", False, "0", nthread=1)
        simd, _ = _collect(path, "libsvm", False, "1", nthread=1)
        _assert_same(simd, scalar, ("cut", cut))


def test_simd_lane_reported(tmp_path):
    """The chosen lane rides dct_parser_pipeline_stats into Python; unset
    env means best-supported, which on any little-endian host is at least
    the SWAR tier."""
    path = tmp_path / "t.libsvm"
    path.write_bytes(b"1 0:1 1:2\n" * 500)
    with NativeParser(str(path), nthread=1) as p:
        for _ in p:
            pass
        stats = p.pipeline_stats()
    assert stats is not None
    assert stats["simd_lane"] in ("swar", "sse2", "avx2", "scalar")
    assert stats["simd_tier"] == {"scalar": 0, "swar": 1, "sse2": 2,
                                  "avx2": 3}[stats["simd_lane"]]
