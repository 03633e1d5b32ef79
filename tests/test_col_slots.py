"""A feature is gathered and scattered once a batch (ISSUE 31): every CSR
shard travels as its distinct columns, ascending (``cols``), and a slot for
each entry (``slot``, in the col plane's place). The dedupe is stated once
natively (cpp/src/col_slots.h) and once by ``np.unique``
(device_iter.col_slots), and run by ``PaddedBatcher``, ``CsrRecBatcher`` and
``HostBatcher``.

- the two statements give the same lists and slots on random, all-equal,
  all-distinct, empty and padded shards; ``cols[slot] == col`` for every
  real entry; the capacity is ``nnz_bucket`` of the fullest shard's count;
- the three batchers agree with the oracle over whole files, in one shard
  and in four, and the counter ``device_cols_distinct_total`` reads what
  they counted;
- the list's padding is dropped by a scatter and read as zeros by a filling
  gather, with the hint the step gives them;
- a corpus whose distinct count crosses rungs compiles a shape a rung.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.io.convert import rows_to_csr_recordio
from dmlc_core_tpu.io.native import NativeParser, native_col_slots
from dmlc_core_tpu.tpu import device_iter
from dmlc_core_tpu.tpu.device_iter import (CsrRecHostBatcher,
                                           DeviceRowBlockIter, HostBatcher,
                                           NativeHostBatcher, _expand_cols,
                                           col_slots, nnz_bucket, unpack_tree)

TOP = 2 ** 31 - 1


def _shards(case, rng):
    """(col [D, NNZ], real entries per shard, floor) of a named case."""
    if case == "random":
        n = [5000, 3777, 4999, 1]
        col = rng.integers(0, 3000, (4, 5000))
    elif case == "skewed":    # kdd2012's shape: tiny fields beside huge ones
        n = [11 * 1024]
        col = np.concatenate(
            [rng.integers(0, 3, 4 * 1024), rng.integers(3, 700, 1024),
             50_000_000 * rng.random(6 * 1024) ** 3])[None]
    elif case == "all_equal":
        n = [4096, 4096]
        col = np.full((2, 4096), 54_686_451)
    elif case == "all_distinct":
        n = [2048]
        col = rng.permutation(1 << 24)[:2048][None] << 6
    elif case == "empty_shard":
        n = [300, 0, 0, 17]
        col = rng.integers(0, 90, (4, 300))
    elif case == "padded":    # real entries end well before the plane does
        n = [100, 64]
        col = rng.integers(0, 1 << 30, (2, 640))
    elif case == "one_pass":  # ids under 2^11: the sort's single pass
        n = [999]
        col = rng.integers(0, 2048, (1, 999))
    else:                     # "high_ids": all three passes, ids to 2^31 - 1
        n = [700, 701]
        col = TOP - rng.integers(0, 1 << 20, (2, 701))
        col[1, 5] = TOP       # the largest int32 id is a column like another
    col = np.ascontiguousarray(col, np.int32)
    for d, nd in enumerate(n):
        col[d, nd:] = 0       # the planes' padding, as the fills leave it
    return col, n, 128


CASES = ["random", "skewed", "all_equal", "all_distinct", "empty_shard",
         "padded", "one_pass", "high_ids"]


@pytest.mark.parametrize("case", CASES)
def test_native_equals_oracle_and_cols_of_slot_is_col(case):
    col, n, floor = _shards(case, np.random.default_rng(31))
    py, nat = col.copy(), col.copy()
    py_cols, py_distinct = col_slots(py, n, floor)
    nat_cols, nat_distinct = native_col_slots(nat, n, floor)
    assert np.array_equal(py, nat)
    assert np.array_equal(py_cols, nat_cols)
    assert py_distinct == nat_distinct
    counts = [np.unique(col[d, :nd]).size for d, nd in enumerate(n)]
    assert py_distinct == sum(counts)
    assert py_cols.shape == (len(n), nnz_bucket(max(counts + [1]), floor))
    U = py_cols.shape[1]
    for d, nd in enumerate(n):
        listed = max(counts[d], 1)
        assert np.array_equal(py_cols[d][py[d, :nd]], col[d, :nd])
        assert not py[d, nd:].any()                 # padded entries: slot 0
        assert py[d, :nd].max(initial=0) < listed
        # the distinct columns ascending, then 2^31 - 1 to the list's end
        assert (np.diff(py_cols[d, :listed].astype(np.int64)) > 0).all()
        assert (py_cols[d, listed:] == TOP).all() and U >= listed
        if nd == 0:
            assert py_cols[d, 0] == 0               # the stand-in column


def test_padding_is_dropped_by_the_scatter_and_reads_zero():
    col, n, floor = _shards("empty_shard", np.random.default_rng(5))
    want = np.unique(col[0, :n[0]])
    cols, _ = col_slots(col, n, floor)
    assert cols.shape[1] > want.size                # there is padding
    F = int(want.max()) + 1
    table = jnp.arange(F, dtype=jnp.float32)
    hints = dict(indices_are_sorted=True)
    ids = jnp.asarray(cols[0])
    out = np.asarray(table.at[ids].add(jnp.ones(ids.shape), **hints))
    touched = np.flatnonzero(out != np.arange(F))
    assert np.array_equal(touched, want)            # nothing else moved
    got = np.asarray(table.at[ids].get(mode="fill", fill_value=0, **hints))
    assert np.array_equal(got[:want.size], want.astype(np.float32))
    assert not got[want.size:].any()


# -- the three batchers ----------------------------------------------------------
def _write_rows(path, rows, per_row, features, seed):
    """libsvm text, ``per_row`` tokens a row drawn with kdd2012's skew."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for r in range(rows):
            ids = np.unique((features * rng.random(per_row) ** 3)
                            .astype(np.int64))
            f.write(f"{r % 2} " + " ".join(f"{c}:1" for c in ids) + "\n")
    return str(path)


@pytest.fixture(scope="module")
def skewed_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("slots")
    return _write_rows(d / "s.libsvm", 5 * 512 + 100, 9, 40000, seed=7)


def _open(kind, path, batch_rows, shards, floor):
    if kind == "native":
        return NativeHostBatcher(path, fmt="libsvm", batch_rows=batch_rows,
                                 num_shards=shards, min_nnz_bucket=floor,
                                 layout="csr")
    if kind == "crec":
        crec = path + ".crec"
        if not os.path.exists(crec):
            rows_to_csr_recordio(path, crec, fmt="libsvm",
                                 rows_per_record=300)
        return CsrRecHostBatcher(crec, batch_rows=batch_rows,
                                 num_shards=shards, min_nnz_bucket=floor)
    parser = NativeParser(path, fmt="libsvm", index64=True)
    return HostBatcher(parser, batch_rows, shards, floor, True, layout="csr")


def _epoch(batcher):
    out = []
    while True:
        b = batcher.next_batch()
        if b is None:
            return out
        out.append(b)


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_batchers_send_the_oracles_lists(skewed_file, kind, shards):
    """Every batch of the file, its short last one (whose tail shards are
    empty) included: the list, the slots and the counts are the oracle's on
    the batch's own columns, which the python batcher states by numpy. The
    short one's list is no narrower than the one before it (ISSUE 34,
    ``device_iter.tail_rung``)."""
    got = _epoch(_open(kind, skewed_file, 512, shards, 64))
    ref = _epoch(_open("python", skewed_file, 512, shards, 64))
    assert len(got) == len(ref) == 6
    before = 0
    for b, r in zip(got, ref):
        R = b.rows_per_shard
        n = (b.row < R).sum(axis=1)
        assert n.sum() == b.total_nnz
        r_col = _expand_cols(r.cols, r.slot)
        col = np.array(r_col)
        short = b.total_rows < 512
        want_cols, want_distinct = col_slots(col, n, 64)   # col -> slots
        if short and before > want_cols.shape[1]:
            want_cols = np.pad(want_cols,
                               ((0, 0), (0, before - want_cols.shape[1])),
                               constant_values=np.iinfo(np.int32).max)
        assert np.array_equal(b.cols, want_cols)
        # (the .crec lane's nnz capacity is the file's, not the batch's)
        width = min(b.nnz_bucket, r.nnz_bucket)
        assert np.array_equal(b.slot[:, :width], col[:, :width])
        assert not b.slot[:, width:].any() and not col[:, width:].any()
        assert b.total_distinct == want_distinct
        own = nnz_bucket(
            max(np.unique(r_col[d, :n[d]]).size for d in range(shards)), 64)
        assert b.cols.shape == (shards, max(own, before) if short else own)
        assert b.tail_lifted == (short and (
            own < before or b.nnz_bucket > nnz_bucket(int(n.max()), 64)))
        before = b.cols.shape[1]
        # col is not kept: the unpack reads it back from the two
        assert b.col is None
        assert b.tree().keys() == {"big", "cols", "aux"}
        named = unpack_tree(b.tree())
        for d in range(shards):
            assert np.array_equal(named["col"][d, :n[d]], r_col[d, :n[d]])
            assert (named["col"][d, n[d]:] == b.cols[d, 0]).all()
        assert np.array_equal(named["slot"], b.slot)
        assert np.array_equal(named["cols"], b.cols)
    assert got[-1].total_rows == 100


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_pooled_list_buffers_are_rewritten_whole(skewed_file, kind):
    """A recycled cols buffer holds another batch's list: the next fill
    must leave none of it."""
    fresh = [b.cols.copy() for b in _epoch(_open(kind, skewed_file, 512,
                                                 1, 64))]
    b = _open(kind, skewed_file, 512, 1, 64)
    for want in fresh:
        batch = b.next_batch()
        assert np.array_equal(batch.cols, want)
        batch.cols[:] = -7
        b.recycle(batch)


@pytest.fixture
def _counters():
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()
    yield lambda name: telemetry.counter(name).value
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_distinct_counter_reads_what_the_dedupe_counted(skewed_file, kind,
                                                        _counters):
    kw = {"fmt": "libsvm"}
    path = skewed_file
    if kind == "crec":
        _open("crec", path, 512, 1, 64).close()    # writes the .crec once
        path, kw = path + ".crec", {"fmt": "crec"}
    elif kind == "python":
        kw["index64"] = True
    with DeviceRowBlockIter(path, batch_rows=512, layout="csr",
                            min_nnz_bucket=64, **kw) as it:
        batches = list(it)
    want = sum(np.unique(_expand_cols(b.cols, b.slot)).size for b in
               _epoch(_open("python", skewed_file, 512, 1, 64)))
    assert sum(b.total_distinct for b in batches) == want
    assert _counters("device_cols_distinct_total") == want
    real = _counters("device_nnz_real_total")
    assert 0.5 < want / real < 0.9                 # the skew repeats features
    assert all(b.cols.shape == (1, b.tree()["cols"].shape[1])
               for b in batches)


def test_a_distinct_count_that_crosses_rungs_compiles_a_shape_a_rung(
        tmp_path, _counters):
    """64 rows of 8 tokens a batch, so one nnz rung (512); the first batch
    draws them from 40 columns, the second from 400: the list lands on 64
    and on 416, two shapes, and a replay adds none."""
    rng = np.random.default_rng(3)
    lines = []
    for features in (40, 400):
        for r in range(64):
            ids = np.sort(rng.choice(features, 8, replace=False))
            lines.append(f"{r % 2} " + " ".join(f"{c}:1" for c in ids))
    path = tmp_path / "rungs.libsvm"
    path.write_text("\n".join(lines) + "\n")

    def run():
        with DeviceRowBlockIter(str(path), batch_rows=64, layout="csr",
                                min_nnz_bucket=64) as it:
            return [(b.nnz_bucket, b.cols.shape[1]) for b in it]
    shapes = run()
    assert [s[0] for s in shapes] == [512, 512]
    assert shapes[0][1] == 64 and shapes[1][1] == nnz_bucket(
        shapes[1][1] - 1, 64) and 256 < shapes[1][1] <= 416
    gauge = [g["value"] for g in telemetry.snapshot(native=False)["gauges"]
             if g["name"] == "device_distinct_shapes"]
    assert gauge == [2]
    assert run() == shapes
    gauge = [g["value"] for g in telemetry.snapshot(native=False)["gauges"]
             if g["name"] == "device_distinct_shapes"]
    assert gauge == [2]
