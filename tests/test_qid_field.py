"""qid/field device-layout tests: ranking (qid) and FM (field) workloads on
the TPU path (VERDICT r1 item 4 — reference RowBlock carries qid/field,
include/dmlc/data.h:174-236; these must reach the device batch)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.ops.ranking import pairwise_logistic_loss
from dmlc_core_tpu.ops.sparse import field_aware_matvec
from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter, HostBatcher,
                                           NativeHostBatcher)
from dmlc_core_tpu.io.native import NativeParser


def write_ranking_libsvm(path, queries=6, rows_per_q=5, features=8, seed=0):
    """libsvm with qid:n groups; graded labels 0..2."""
    rng = np.random.default_rng(seed)
    lines = []
    expect = []  # (qid, label)
    for q in range(1, queries + 1):
        for _ in range(rows_per_q):
            label = int(rng.integers(0, 3))
            feats = " ".join(
                f"{j}:{rng.uniform(0.1, 1.0):.4f}" for j in range(features))
            lines.append(f"{label} qid:{q} {feats}")
            expect.append((q, label))
    path.write_text("\n".join(lines) + "\n")
    return expect


def write_libfm(path, rows=40, fields=4, features=16, seed=1):
    """label field:feature:value triples; returns per-row triple lists."""
    rng = np.random.default_rng(seed)
    lines, expect = [], []
    for i in range(rows):
        nnz = int(rng.integers(2, 6))
        triples = [(int(rng.integers(0, fields)),
                    int(rng.integers(0, features)),
                    float(np.round(rng.uniform(0.1, 2.0), 4)))
                   for _ in range(nnz)]
        lines.append(f"{i % 2} " + " ".join(
            f"{f}:{c}:{v:.4f}" for f, c, v in triples))
        expect.append(triples)
    path.write_text("\n".join(lines) + "\n")
    return expect


def batch_rows_of(batch, d, r):
    """(qid, label, weight) at shard d row r."""
    return (int(batch.qid[d, r]), float(batch.label[d, r]),
            float(batch.weight[d, r]))


def test_native_batcher_carries_qid(tmp_path):
    p = tmp_path / "rank.libsvm"
    expect = write_ranking_libsvm(p)
    b = NativeHostBatcher(str(p), layout="csr", batch_rows=32, num_shards=2,
                          min_nnz_bucket=64)
    got = []
    while True:
        batch = b.next_batch()
        if batch is None:
            break
        assert batch.qid is not None and batch.qid.shape == batch.label.shape
        assert batch.qid.dtype == np.int32
        D, R = batch.label.shape
        for d in range(D):
            for r in range(int(batch.nrows[d])):
                q, lab, w = batch_rows_of(batch, d, r)
                assert w > 0
                got.append((q, int(lab)))
        # padding rows carry the -1 sentinel (can't collide with real qids)
        for d in range(D):
            for r in range(int(batch.nrows[d]), R):
                assert int(batch.qid[d, r]) == -1
    assert got == expect
    b.close()


def test_native_batcher_carries_field(tmp_path):
    p = tmp_path / "fm.libfm"
    expect = write_libfm(p)
    b = NativeHostBatcher(str(p), fmt="libfm", layout="csr", batch_rows=64,
                          num_shards=1, min_nnz_bucket=64)
    batch = b.next_batch()
    assert batch is not None and batch.field is not None
    assert batch.field.shape == batch.slot.shape
    assert batch.field.dtype == np.int32
    # reconstruct per-row triples from the device layout
    R = batch.rows_per_shard
    rows = {}
    for r, c, f, v in zip(batch.row[0], batch.cols[0][batch.slot[0]],
                          batch.field[0], batch.val[0]):
        if v != 0:
            rows.setdefault(int(r), []).append((int(f), int(c), float(v)))
    for i, triples in enumerate(expect):
        got = sorted(np.round(rows[i], 4).tolist())
        want = sorted([(f, c, round(v, 4)) for f, c, v in triples])
        assert len(got) == len(want)
        for (gf, gc, gv), (wf, wc, wv) in zip(got, want):
            assert (int(gf), int(gc)) == (wf, wc)
            assert gv == pytest.approx(wv, abs=1e-4)
    b.close()


def test_host_batcher_python_path_parity(tmp_path):
    """The index64 (python) batcher carries qid/field identically."""
    p = tmp_path / "fm.libfm"
    write_libfm(p)
    nb = NativeHostBatcher(str(p), fmt="libfm", layout="csr", batch_rows=64,
                           num_shards=2, min_nnz_bucket=64)
    native = nb.next_batch()
    nb.close()
    parser = NativeParser(str(p), fmt="libfm", index64=True)
    hb = HostBatcher(parser, batch_rows=64, num_shards=2, min_nnz_bucket=64,
                     layout="csr")
    python = hb.next_batch()
    parser.close()
    assert python.field is not None and native.field is not None
    np.testing.assert_array_equal(python.row, native.row)
    np.testing.assert_array_equal(python.slot, native.slot)
    np.testing.assert_array_equal(python.cols, native.cols)
    np.testing.assert_array_equal(python.field, native.field)
    np.testing.assert_allclose(python.val, native.val, rtol=1e-6)


def test_qid_reaches_device_and_ranking_loss_runs(tmp_path):
    p = tmp_path / "rank.libsvm"
    expect = write_ranking_libsvm(p, queries=4, rows_per_q=8)
    from dmlc_core_tpu.tpu.sharding import data_mesh
    mesh = data_mesh(num_devices=2)
    with DeviceRowBlockIter(str(p), batch_rows=32, mesh=mesh,
                            min_nnz_bucket=64, layout="csr") as it:
        batch = next(iter(it))
    # device batches travel packed (two leaves); qid rides inside aux and
    # unpacks to the same named plane
    from dmlc_core_tpu.tpu.device_iter import unpack_tree
    named = unpack_tree({k: np.asarray(v) for k, v in
                         batch.tree().items()})
    assert "qid" in named

    # jitted per-shard pairwise loss vs a numpy oracle over the same shard
    qid0 = np.asarray(named["qid"][0])
    lab0 = np.asarray(named["label"][0])
    wgt0 = np.asarray(named["weight"][0])
    margin = np.linspace(-1, 1, len(qid0)).astype(np.float32)

    loss, pairs = jax.jit(pairwise_logistic_loss)(
        jnp.asarray(margin), jnp.asarray(lab0), jnp.asarray(qid0),
        jnp.asarray(wgt0))

    exp_loss, exp_pairs = 0.0, 0
    for i in range(len(qid0)):
        for j in range(len(qid0)):
            if (qid0[i] == qid0[j] and lab0[i] > lab0[j]
                    and wgt0[i] > 0 and wgt0[j] > 0):
                exp_pairs += 1
                exp_loss += float(np.log1p(np.exp(-(margin[i] - margin[j]))))
    assert int(pairs) == exp_pairs and exp_pairs > 0
    assert float(loss) == pytest.approx(exp_loss, rel=1e-5)
    del expect


def test_field_aware_matvec_matches_numpy(tmp_path):
    p = tmp_path / "fm.libfm"
    write_libfm(p, rows=30, fields=4, features=16)
    b = NativeHostBatcher(str(p), fmt="libfm", layout="csr", batch_rows=32,
                          num_shards=1, min_nnz_bucket=64)
    batch = b.next_batch()
    b.close()
    rng = np.random.default_rng(7)
    W = rng.normal(size=(4, 16)).astype(np.float32)
    R = batch.rows_per_shard
    col = batch.cols[0][batch.slot[0]]
    y = jax.jit(field_aware_matvec, static_argnames="num_rows")(
        jnp.asarray(batch.row[0]), jnp.asarray(col),
        jnp.asarray(batch.field[0]), jnp.asarray(batch.val[0]),
        jnp.asarray(W), num_rows=R)
    y_np = np.zeros(R, np.float32)
    for r, c, f, v in zip(batch.row[0], col, batch.field[0],
                          batch.val[0]):
        if r < R:
            y_np[r] += v * W[f, c]
    np.testing.assert_allclose(np.asarray(y), y_np, rtol=1e-5, atol=1e-6)


def test_dense_layout_carries_qid(tmp_path):
    p = tmp_path / "rank.libsvm"
    write_ranking_libsvm(p, queries=3, rows_per_q=4)
    b = NativeHostBatcher(str(p), layout="dense", batch_rows=16,
                          num_shards=2)
    batch = b.next_batch()
    b.close()
    assert batch.qid is not None
    assert int(batch.qid[0, 0]) == 1  # first query id
    # the packed tree carries qid inside aux (K == 4 planes, shard-major)
    tree = batch.tree()
    assert set(tree) == {"x", "aux"} and tree["aux"].shape[1] == 4


def test_no_qid_no_field_stays_none(tmp_path):
    p = tmp_path / "plain.libsvm"
    p.write_text("1 0:1.0 3:2.0\n0 1:0.5\n")
    b = NativeHostBatcher(str(p), layout="csr", batch_rows=8, num_shards=1,
                          min_nnz_bucket=16)
    batch = b.next_batch()
    b.close()
    assert batch.qid is None and batch.field is None
    assert "qid" not in batch.tree() and "field" not in batch.tree()


def test_auto_layout_forces_csr_for_field_data(tmp_path):
    # 16 features would pick dense, but field data must keep the CSR layout
    p = tmp_path / "fm.libfm"
    write_libfm(p, rows=20, fields=3, features=16)
    b = NativeHostBatcher(str(p), fmt="libfm", batch_rows=32, num_shards=1,
                          min_nnz_bucket=64)  # layout defaults to auto
    batch = b.next_batch()
    b.close()
    assert batch.field is not None  # CSR chosen, field plane present


def test_explicit_dense_with_field_raises(tmp_path):
    p = tmp_path / "fm.libfm"
    write_libfm(p, rows=10, fields=3, features=16)
    b = NativeHostBatcher(str(p), fmt="libfm", layout="dense", batch_rows=16,
                          num_shards=1)
    with pytest.raises(Exception, match="no dense layout"):
        b.next_batch()
    b.close()


def test_ranking_loss_ignores_sentinel_qid():
    # rows with qid -1 (absent/padding sentinel) must not form pairs
    margin = jnp.array([0.5, -0.5, 0.2, -0.2])
    label = jnp.array([2.0, 0.0, 2.0, 0.0])
    qid = jnp.array([-1, -1, 7, 7], jnp.int32)
    weight = jnp.ones(4)
    loss, pairs = pairwise_logistic_loss(margin, label, qid, weight)
    assert int(pairs) == 1  # only the qid=7 pair (2 > 0)
    assert float(loss) == pytest.approx(float(np.log1p(np.exp(-0.4))),
                                        rel=1e-5)


def test_fill_buffers_safe_without_columns(tmp_path):
    # a C-API consumer may pass qid/field buffers even when the stream never
    # carried the columns; the fill must emit sentinels, not read off-end
    from dmlc_core_tpu.io.native import NativeBatcher
    p = tmp_path / "plain.libsvm"
    p.write_text("1 0:1.0 3:2.0\n0 1:0.5\n1 2:0.25\n")
    nb = NativeBatcher(str(p), batch_rows=8, num_shards=2, min_nnz_bucket=16)
    meta = nb.next_meta()
    assert meta is not None and meta[3] is False and meta[4] is False
    take, bucket = meta[0], meta[1]
    row = np.empty((2, bucket), np.int32)
    col = np.empty((2, bucket), np.int32)
    val = np.empty((2, bucket), np.float32)
    label = np.empty(8, np.float32)
    weight = np.empty(8, np.float32)
    nrows = np.empty(2, np.int32)
    qid = np.empty(8, np.int32)
    field = np.empty((2, bucket), np.int32)
    nb.fill_csr(row, col, val, label, weight, nrows, qid=qid, field=field)
    nb.close()
    assert (qid == -1).all()      # sentinel everywhere
    assert (field == 0).all()     # zero plane


def test_structure_pins_on_first_batch():
    # qid appearing after the pytree structure pinned without it must raise
    # (silent mid-stream structure change would break jitted consumers).
    # Blocks come from a stub parser: within one chunk the native parser
    # already rejects ragged qid (parser.cc:164), so the mid-stream case
    # only arises at block boundaries.
    class Block:
        def __init__(self, n, with_qid):
            self.offset = np.arange(n + 1, dtype=np.int64)
            self.index = np.zeros(n, np.uint32)
            self.value = np.ones(n, np.float32)
            self.label = np.zeros(n, np.float32)
            self.weight = None
            self.qid = (np.arange(n, dtype=np.uint64) if with_qid else None)
            self.field = None
            self.num_rows = n
            self.nnz = n

    class StubParser:
        def __init__(self):
            self.blocks = [Block(8, False), Block(8, True)]

        def next_block(self):
            return self.blocks.pop(0) if self.blocks else None

        def before_first(self):
            pass

    hb = HostBatcher(StubParser(), batch_rows=8, num_shards=1,
                     min_nnz_bucket=16, layout="csr")
    first = hb.next_batch()
    assert first is not None and first.qid is None
    with pytest.raises(Exception, match="pinned"):
        hb.next_batch()
