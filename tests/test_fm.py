"""Factorization machine over the device batch layouts (models/fm.py):
margin matches a numpy oracle on both CSR and dense layouts, training
reduces loss on data with a planted multiplicative interaction (which a
linear model cannot fit), and the DP step runs sharded on the 8-device
mesh over packed batches."""

import os

import numpy as np
import pytest

import jax

from dmlc_core_tpu.models import FMLearner, LinearLearner
from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter
from dmlc_core_tpu.tpu.sharding import data_mesh


def fm_margin_oracle(b, w, V, X):
    lin = X @ w
    s1 = X @ V
    s2 = (X * X) @ (V * V)
    return b + lin + 0.5 * ((s1 * s1).sum(-1) - s2.sum(-1))


def write_interaction_libsvm(path, rows=1024, seed=3):
    """y = 1 iff x0*x1 > 0 — pure interaction, zero linear signal."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(rows, 4)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(int)
    with open(path, "w") as f:
        for i in range(rows):
            feats = " ".join(f"{j}:{X[i, j]:.5f}" for j in range(4))
            f.write(f"{y[i]} {feats}\n")
    return X, y


def test_fm_margin_matches_oracle_csr_and_dense(tmp_path):
    rng = np.random.default_rng(0)
    X, _ = write_interaction_libsvm(tmp_path / "m.libsvm", rows=256)
    learner = FMLearner(num_features=4, k=3)
    params = learner.init(seed=1)
    b = float(params.b)
    w = np.asarray(params.w)
    V = np.asarray(learner.factors(params))
    # nonzero linear part so the oracle covers every term
    w = rng.normal(size=4).astype(np.float32)
    params = params._replace(w=jax.numpy.asarray(w))
    want = fm_margin_oracle(b, w, V, X)
    for layout in ("csr", "dense"):
        with DeviceRowBlockIter(str(tmp_path / "m.libsvm"), batch_rows=256,
                                layout=layout, min_nnz_bucket=2048,
                                dense_dtype="float32",
                                to_device=False) as it:
            batch = next(iter(it))
        got = np.asarray(learner.predict(params, batch)).reshape(-1)
        np.testing.assert_allclose(got[:256], want, rtol=2e-5, atol=2e-5)


def test_fm_lane_dense_margin_matches_oracle_csr_and_dense(tmp_path):
    """``predict`` of a learner whose ``v`` is lane-dense (rank 16: the
    four features share one 128-lane row) against the oracle on ``factors``,
    by the row gather (csr) and by the ``[F, K]`` view (dense)."""
    X, _ = write_interaction_libsvm(tmp_path / "m.libsvm", rows=256)
    learner = FMLearner(num_features=4, k=16, init_scale=0.3)
    params = learner.init(seed=1)._replace(w=jax.numpy.asarray(
        np.random.default_rng(0).normal(size=4).astype(np.float32)))
    assert learner.lane_dense and params.v.shape == (1, 128)
    want = fm_margin_oracle(0.0, np.asarray(params.w),
                            np.asarray(learner.factors(params)), X)
    for layout in ("csr", "dense"):
        with DeviceRowBlockIter(str(tmp_path / "m.libsvm"), batch_rows=256,
                                layout=layout, min_nnz_bucket=2048,
                                dense_dtype="float32",
                                to_device=False) as it:
            batch = next(iter(it))
        got = np.asarray(learner.predict(params, batch)).reshape(-1)
        np.testing.assert_allclose(got[:256], want, rtol=2e-5, atol=2e-5)


def test_fm_learns_interaction_linear_cannot(tmp_path):
    write_interaction_libsvm(tmp_path / "i.libsvm", rows=2048)
    uri = str(tmp_path / "i.libsvm")

    def train(learner, epochs=12):
        params = learner.init()
        losses = []
        with DeviceRowBlockIter(uri, batch_rows=512, layout="dense",
                                dense_dtype="float32") as it:
            for _ in range(epochs):
                for batch in it:
                    params, loss = learner.step(params, batch)
                    losses.append(float(loss))
                it.before_first()
        return losses

    fm_losses = train(FMLearner(num_features=4, k=4, learning_rate=0.5,
                                init_scale=0.3))
    lin_losses = train(LinearLearner(num_features=4, learning_rate=0.5))
    # the FM must beat chance (log 2 ≈ 0.693) decisively; the linear model
    # cannot express x0*x1 and stays pinned near it
    assert fm_losses[-1] < 0.55, fm_losses[-1]
    assert lin_losses[-1] > 0.6, lin_losses[-1]
    assert fm_losses[-1] < lin_losses[-1] - 0.05


def test_fm_sharded_step_on_mesh(tmp_path):
    write_interaction_libsvm(tmp_path / "s.libsvm", rows=2048)
    mesh = data_mesh()
    assert mesh.devices.size == 8
    learner = FMLearner(num_features=4, k=4, mesh=mesh, learning_rate=0.5,
                        init_scale=0.3)
    params = learner.init()
    losses = []
    with DeviceRowBlockIter(str(tmp_path / "s.libsvm"), batch_rows=512,
                            mesh=mesh, layout="csr",
                            min_nnz_bucket=512) as it:
        for _ in range(10):
            for batch in it:
                assert set(batch.tree()) == {"big", "cols", "aux"}
                params, loss = learner.step(params, batch)
                losses.append(float(loss))
            it.before_first()
    assert losses[-1] < losses[0]
    assert losses[-1] < 0.6, losses[-1]


def test_fm_rejects_bad_rank():
    with pytest.raises(ValueError, match="k must be positive"):
        FMLearner(num_features=4, k=0)


# -- the row form of the CSR step on one device (models/_dp.py) ---------------
F_ROWS, K_ROWS = 11, 3   # shapes no batch leaf has: found by shape below


def write_recurring_libsvm(path, rows=300, seed=5):
    """Few features, so every one recurs across a batch's rows (and the
    scatter-add has duplicates to sum); 1 to 5 nonzeros a row, so every
    nnz bucket holds padded entries; 300 rows in batches of 256 leave a
    last batch with padding rows of weight 0."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(rows):
            cols = sorted(set(rng.integers(0, F_ROWS,
                                           size=rng.integers(1, 6)).tolist()))
            feats = " ".join(f"{c}:{rng.normal():.4f}" for c in cols)
            f.write(f"{rng.integers(0, 2)} {feats}\n")
    return str(path)


def _batches(uri, mesh=None):
    with DeviceRowBlockIter(uri, batch_rows=256, layout="csr", mesh=mesh,
                            min_nnz_bucket=2048) as it:
        return list(it)


def _lowered_ops(lowered):
    """Every operation of the lowered module, in program order."""
    from jaxlib.mlir import ir
    ops = []

    def visit(op):
        ops.append(op)
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir(dialect="stablehlo").operation.walk(visit)
    return ops


def _table_ops(lowered, shapes):
    """(op name, location) of every operation in the lowered module with a
    result of one of ``shapes``."""
    from jaxlib.mlir import ir
    return [(op.name, str(op.location)) for op in _lowered_ops(lowered)
            for r in op.results
            if isinstance(r.type, ir.RankedTensorType)
            and tuple(r.type.shape) in shapes]


@pytest.mark.parametrize("objective", ["logistic", "squared"])
@pytest.mark.parametrize("l2", [0.0, 0.03])
def test_fm_row_step_equals_the_table_step(tmp_path, l2, objective):
    from dmlc_core_tpu.tpu.device_iter import unpack_shard
    learner = FMLearner(F_ROWS, k=K_ROWS, objective=objective,
                        learning_rate=0.3, l2=l2, init_scale=0.2)
    params = learner.init(seed=7)
    # a linear part and a bias that are not zero, so that decay shows
    rng = np.random.default_rng(1)
    params = params._replace(
        b=jax.numpy.float32(0.25),
        w=jax.numpy.asarray(rng.normal(size=F_ROWS).astype(np.float32)))
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    assert [b.total_rows for b in batches] == [256, 44]
    for batch in batches:
        tree = batch.tree()
        assert learner._takes_row_form(tree)
        shard = unpack_shard({k: v[0] for k, v in tree.items()})
        col = np.asarray(shard["col"])
        val = np.asarray(shard["val"])
        assert (val == 0).sum() > 0                     # padded nonzeros
        assert len(set(col[val != 0])) < (val != 0).sum()   # duplicates
        R = batch.rows_per_shard
        (loss_sum, wsum), grads = jax.value_and_grad(
            lambda p: learner._shard_loss(p, shard, R),
            has_aux=True)(params)
        denom = jax.numpy.maximum(wsum, 1.0)
        # the rule written out, not the learner's own: p - lr·(g/d + l2·p)
        # on w and v (as [F, K]), b without decay
        V, gV = learner.factors(params), learner.factors(grads)
        want = {"b": params.b - 0.3 * grads.b / denom,
                "w": params.w - 0.3 * (grads.w / denom + l2 * params.w),
                "v": V - 0.3 * (gV / denom + l2 * V)}
        got, loss = learner.step(params, batch)
        np.testing.assert_allclose(float(loss), float(loss_sum / denom),
                                   rtol=1e-6)
        for leaf in ("b", "w", "v"):
            a = np.asarray(learner.factors(got) if leaf == "v"
                           else getattr(got, leaf))
            b = np.asarray(want[leaf])
            assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), leaf
        # every row of the tables moved when l2 decays them, only the
        # batch's rows when it does not
        moved = np.any(np.asarray(learner.factors(got))
                       != np.asarray(learner.factors(params)), axis=1)
        assert moved.all() if l2 else \
            set(np.flatnonzero(moved)) <= set(col[val != 0])
        params = got


def write_repeating_libsvm(path, rows=16384, seed=9):
    """Every row names one of 3 columns, one of 7 and three of 60,000 (a
    cubed draw, as the benchmark's generators skew): the 3-valued field
    alone puts 16,384 of a batch's entries on 3 rows of the tables."""
    rng = np.random.default_rng(seed)
    small = rng.integers(0, 3, rows)
    mid = 3 + rng.integers(0, 7, rows)
    big = 10 + (60000 * rng.random((rows, 3)) ** 3).astype(np.int64)
    with open(path, "w") as f:
        for r in range(rows):
            ids = sorted({int(small[r]), int(mid[r]), *big[r].tolist()})
            f.write(f"{r % 2} " + " ".join(f"{c}:1" for c in ids) + "\n")
    return str(path)


def test_fm_row_step_merges_a_features_repeats(tmp_path):
    """On a batch whose features recur by the thousand the row form's
    update is the table form's: the expansion's transpose sums a feature's
    entries at the gradient's magnitude and the scatter rounds once a
    feature. Adding every entry into the parameter by itself, as the row
    form did before the batch carried its distinct columns, lands an
    order of magnitude further off."""
    from dmlc_core_tpu.models.fm import FMRows, _fm_margin_entries
    from dmlc_core_tpu.models.linear import objective_loss
    from dmlc_core_tpu.tpu.device_iter import unpack_shard
    F, K, R = 60010, 8, 16384
    learner = FMLearner(F, k=K, learning_rate=0.1, init_scale=0.1)
    params = learner.init(seed=3)
    params = params._replace(w=0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (F,)))
    with DeviceRowBlockIter(write_repeating_libsvm(tmp_path / "h.libsvm"),
                            batch_rows=R, layout="csr") as it:
        batch = next(iter(it))
    tree = batch.tree()
    assert learner._takes_row_form(tree)
    shard = unpack_shard({k: v[0] for k, v in tree.items()})
    real = np.asarray(shard["val"]) != 0
    counts = np.bincount(np.asarray(shard["col"])[real], minlength=F)
    assert counts[:3].sum() == R and counts[:3].min() > 5000
    assert batch.total_distinct < 0.6 * batch.total_nnz

    (_, wsum), grads = jax.value_and_grad(
        lambda p: learner._shard_loss(p, shard, R), has_aux=True)(params)
    denom = jax.numpy.maximum(wsum, 1.0)
    want = learner._apply(params, grads, denom)
    got, _ = learner.step(params, batch)

    # the row form as it was: each entry's own row, scattered by itself
    col = shard["col"]
    V = learner.factors(params)
    entry_grads = jax.grad(lambda rows: objective_loss(
        _fm_margin_entries(rows.b, rows.w, rows.v, shard["row"],
                           shard["val"], R), shard, R, "logistic")[0])(
        FMRows(params.b, params.w[col], V[col]))
    by_entry = {"w": params.w.at[col].add(-0.1 * entry_grads.w / denom),
                "v": V.at[col].add(-0.1 * entry_grads.v / denom)}

    def leaf_of(p, leaf):
        return np.asarray(learner.factors(p) if leaf == "v"
                          else getattr(p, leaf))

    def off(a, leaf):
        """Largest error of an update, in units of the update's largest
        step."""
        before, b = leaf_of(params, leaf), leaf_of(want, leaf)
        return np.abs(np.asarray(a) - b).max() / np.abs(b - before).max()

    for leaf in ("w", "v"):
        merged, entrywise = off(leaf_of(got, leaf), leaf), \
            off(by_entry[leaf], leaf)
        assert merged <= 2e-5, (leaf, merged)
        assert entrywise > 10 * merged, (leaf, merged, entrywise)


@pytest.mark.parametrize("what", ["linear-step", "linear-predict",
                                  "fm-predict"])
def test_reading_col_back_from_the_list_changes_no_bit(tmp_path, what):
    """The linear learner and both ``predict``s still read a column per
    entry: ``cols[slot]``, expanded by the unpack. Against the same batch
    handed over with its columns as the assemblers used to send them, a
    named ``col`` plane, nothing differs."""
    from dmlc_core_tpu.models.fm import _fm_margin_csr
    from dmlc_core_tpu.tpu.device_iter import PaddedBatch, _expand_cols
    uri = write_recurring_libsvm(tmp_path / "b.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, layout="csr",
                            min_nnz_bucket=2048, to_device=False) as it:
        batches = list(it)
    assert len(batches) == 2
    for b in batches:
        assert b.tree().keys() == {"big", "cols", "aux"}
        named = PaddedBatch(row=b.row, col=_expand_cols(b.cols, b.slot),
                            val=b.val, label=b.label,
                            weight=b.weight, nrows=b.nrows,
                            total_rows=b.total_rows)
        assert named.tree().keys() == {"row", "col", "val", "label",
                                       "weight", "nrows"}
        if what == "fm-predict":
            learner = FMLearner(F_ROWS, k=K_ROWS, init_scale=0.3)
            params = learner.init(seed=2)._replace(
                w=jax.numpy.linspace(-1.0, 1.0, F_ROWS))
            got = learner.predict(params, b)
            want = jax.vmap(lambda r, c, v: _fm_margin_csr(
                params, r, c, v, 256))(named.row, named.col, named.val)
        else:
            learner = LinearLearner(F_ROWS, learning_rate=0.3, l2=0.01)
            params = learner.init()._replace(
                w=jax.numpy.linspace(-1.0, 1.0, F_ROWS))
            if what == "linear-predict":
                got, want = learner.predict(params, b), \
                    learner.predict(params, named)
            else:
                got, want = learner.step(params, b), \
                    learner.step(params, named)
        for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.asarray(a).tobytes() == np.asarray(w).tobytes()


@pytest.mark.parametrize("call", ["step", "predict"])
def test_a_packed_batch_without_its_list_is_named(tmp_path, call):
    """The two packs alone, as they travelled before the list did: plane 1
    holds slots now, so no consumer can read them."""
    from dmlc_core_tpu.base import DMLCError
    from dmlc_core_tpu.tpu.device_iter import PaddedBatch
    uri = write_recurring_libsvm(tmp_path / "p.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, layout="csr",
                            min_nnz_bucket=2048, to_device=False) as it:
        b = next(iter(it))
    two = PaddedBatch(big=b.big, aux=b.aux, total_rows=b.total_rows)
    for learner in (FMLearner(F_ROWS, k=K_ROWS), LinearLearner(F_ROWS)):
        with pytest.raises(DMLCError, match="col_slots"):
            getattr(learner, call)(learner.init(), two)


@pytest.mark.parametrize("call", ["step", "predict"])
def test_fm_names_the_list_a_csr_batch_came_without(tmp_path, call):
    """A hand-built batch of named row/col/val leaves, which the linear
    learner takes, has no distinct list: the FM says so by name."""
    from dmlc_core_tpu.base import DMLCError
    from dmlc_core_tpu.tpu.device_iter import PaddedBatch, _expand_cols
    uri = write_recurring_libsvm(tmp_path / "n.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, layout="csr",
                            min_nnz_bucket=2048, to_device=False) as it:
        b = next(iter(it))
    named = PaddedBatch(row=b.row, col=_expand_cols(b.cols, b.slot),
                        val=b.val, label=b.label, weight=b.weight,
                        nrows=b.nrows, total_rows=b.total_rows)
    learner = FMLearner(F_ROWS, k=K_ROWS)
    with pytest.raises(DMLCError, match="col_slots"):
        getattr(learner, call)(learner.init(seed=1), named)
    LinearLearner(F_ROWS).step(LinearLearner(F_ROWS).init(), named)


@pytest.mark.parametrize("start", ["init", "nonzero"])
@pytest.mark.parametrize("devices", [2, 8])
def test_fm_one_device_and_mesh_steps_agree(tmp_path, devices, start):
    """From ``init()`` a row of one entry has a margin of exactly 0 or of a
    rounding's residue, by how each program's compiler contracts
    ``s1*s1 - s2`` (the file has 55 such rows in its first batch): the
    logistic loss's derivative may not step there (ISSUE 35's review;
    test_logistic_derivative_has_no_step_at_margin_zero). The second start
    keeps every margin off 0."""
    uri = write_recurring_libsvm(tmp_path / "m.libsvm")

    def epoch(mesh):
        learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh, learning_rate=0.3,
                            l2=0.01, init_scale=0.2)
        params = learner.init(seed=7)
        if start == "nonzero":
            rng = np.random.default_rng(1)
            params = params._replace(
                b=jax.numpy.float32(0.25), w=jax.numpy.asarray(
                    0.05 * rng.normal(size=F_ROWS).astype(np.float32)))
        for batch in _batches(uri, mesh):
            params, _ = learner.step(params, batch)
        return jax.tree.map(np.asarray, params)

    one, many = epoch(None), epoch(data_mesh(devices))
    for a, b in zip(one, many):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_devices", [0, 1], ids=["nomesh", "mesh1"])
@pytest.mark.parametrize("l2", [0.0, 0.03])
def test_fm_row_step_makes_no_table_but_the_parameters(tmp_path, l2,
                                                       mesh_devices):
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh, l2=l2)
    params = learner.init()
    batch = _batches(write_recurring_libsvm(tmp_path / "l.libsvm"), mesh)[0]
    tree = batch.tree()
    step = learner._build_step(batch.rows_per_shard,
                               tuple(sorted(tree.keys())))
    # the benchmark finds the step's module by this name
    assert step.__name__ == "sharded_step"
    lowered = step.lower(params, tree)
    text = lowered.as_text(debug_info=True)
    assert "transpose(jvp(fm.gather))" not in text
    assert "dp.allreduce" not in text
    # the expansion by slot and, in the hand-written backward, the merge
    # of a feature's repeats carry a scope of their own
    assert "dp.loss_grad/jvp(fm.expand)/gather" in text
    assert BACKWARD + "jvp(fm.expand)/scatter-add" in text
    made = _table_ops(lowered, {(F_ROWS, K_ROWS), (F_ROWS,)})
    # one scatter-add into each table, under dp.apply; with l2 the decayed
    # operand of each (a scalar's broadcast and a multiply) and nothing else
    names = sorted(name for name, _ in made)
    decay = ["stablehlo.broadcast_in_dim", "stablehlo.multiply"] * 2
    assert names == sorted(["stablehlo.scatter"] * 2 + (decay if l2 else []))
    for name, loc in made:
        assert "dp.apply" in loc, (name, loc)
        if name == "stablehlo.scatter":
            assert "scatter-add" in loc


# where an operation of the margin's hand-written backward stands: jax
# names what a custom VJP's backward traces by the scopes open around the
# gradient (dp.loss_grad, transposed) and then the backward's own, where
# autodiff's transposes read dp.loss_grad/transpose(jvp(<scope>))
BACKWARD = "dp.loss_grad/transpose(dp.loss_grad)/"


def _indexed_ops(lowered):
    """(op, scope path) of every gather and scatter in the lowered module,
    in program order; the path without the jitted function's own name."""
    import re
    return [(op.name.split(".")[1],
             re.match(r'loc\("([^"]*)"', str(op.location)).group(1)
             .split("sharded_step)/")[-1])
            for op in _lowered_ops(lowered)
            if op.name in ("stablehlo.gather", "stablehlo.scatter")]


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["nomesh", "mesh4"])
def test_fm_csr_step_indexes_an_entry_four_times(tmp_path, mesh_devices):
    """The passes over a batch's entries that share their indices are lanes
    of one pass: one expansion by slot ([U, K+1]), one segment sum by row
    ([NNZ, 2K+1]), in the backward one gather back by row ([R+1, K+1]) and
    one merge by slot; beside them the two gathers from the tables and the
    two scatter-adds into them. An edit that splits a pass again shows
    here and not on the chip (PERF.md section 6, PR 35: a pass costs by its
    indices, one lane what K lanes cost). Since ISSUE 37 the two backward
    ones are the hand-written backward's, under the forward's scopes."""
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh)
    params = learner.init()
    batch = _batches(write_recurring_libsvm(tmp_path / "i.libsvm"), mesh)[0]
    tree = batch.tree()
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree.keys()))).lower(params, tree)
    assert _indexed_ops(lowered) == [
        ("gather", "dp.loss_grad/fm.linear/gather"),            # w[cols]
        ("gather", "dp.loss_grad/fm.gather/gather"),            # v[cols]
        ("gather", "dp.loss_grad/jvp(fm.expand)/gather"),       # by slot
        ("scatter", "dp.loss_grad/jvp(fm.interaction)/scatter-add"),  # row
        ("gather", BACKWARD + "jvp(fm.interaction)/gather"),    # by row
        ("scatter", BACKWARD + "jvp(fm.expand)/scatter-add"),   # by slot
        ("scatter", "dp.apply/scatter-add"),                    # into w
        ("scatter", "dp.apply/scatter-add"),                    # into v
    ]


def test_fm_mesh_step_exchanges_the_rows_as_their_own_leaves(tmp_path):
    """The mesh step settles the row gradient's leaves before the exchange
    (an optimization barrier before ``dp.allreduce``, under no scope): w's rows go into the
    all-gather as ``[U]`` and come out ``[D, U]``, not as the ``[U, 1]``
    slice of the merged ``[U, K+1]`` gradient that the chip's compiler
    pads to 128 lanes (1.84 ms a step for 0.02 in cell
    kdd2012-fm-dp4.libfm: PERF.md section 6, PR 35;
    tests/test_step_compiles_for_v5e.py holds the compiled layout); and
    v's rows go in flat, ``[U·K]`` out as ``[D, U·K]``, which the compiler
    cannot exchange with K lanes padded to 128."""
    mesh = data_mesh(4)
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh)
    batch = _batches(write_recurring_libsvm(tmp_path / "x.libsvm"), mesh)[0]
    tree = batch.tree()
    U = tree["cols"].shape[1]
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree.keys()))).lower(
            learner.init(), tree)
    gathers = [op for op in _lowered_ops(lowered)
               if op.name == "stablehlo.all_gather"
               and "f32" in str(op.results[0].type)]
    assert sorted(tuple(op.results[0].type.shape) for op in gathers) == [
        (4, U), (4, U * K_ROWS)]
    for op in gathers:
        src = op.operands[0].owner
        while src.name != "stablehlo.optimization_barrier":
            assert src.name in ("stablehlo.broadcast_in_dim",
                                "stablehlo.reshape"), src.name
            src = src.operands[0].owner
        assert [tuple(r.type.shape) for r in src.results] == [
            (), (U,), (U, K_ROWS)]


@pytest.mark.parametrize("y", [0.0, 1.0])
def test_logistic_derivative_has_no_step_at_margin_zero(y):
    """The loss is composed of a maximum and a minimum whose kinks cancel:
    at a margin of exactly 0, which a zero start gives every row and
    ``FMLearner.init()`` every row of one entry, the derivative is
    sigmoid(0) - y as on either side (with ``-abs`` for the minimum it
    read -y there: abs' is 1 at 0)."""
    import jax.numpy as jnp
    from dmlc_core_tpu.models.linear import objective_loss
    margin = jnp.asarray([0.0, -0.0, 1e-30, -1e-30, 1e-9, -1e-9, 0.7, -3.0],
                         jnp.float32)
    shard = {"label": jnp.full(margin.shape, y, jnp.float32),
             "weight": jnp.ones(margin.shape, jnp.float32)}
    got = jax.grad(lambda m: objective_loss(m, shard, margin.size,
                                            "logistic")[0])(margin)
    np.testing.assert_allclose(got, jax.nn.sigmoid(margin) - y, atol=1e-7)
    assert np.all(np.asarray(got)[:6] == 0.5 - y)


@pytest.mark.parametrize("k", [8, 16, 32])
def test_fm_merged_passes_equal_the_lane_by_lane_sums(k):
    """The margin and its row gradients against the same sums stated a
    lane at a time (two expansions, three segment sums), on entries that
    repeat their columns, with padding entries on the sacrificial row id,
    rows that hold no entry and rows of the list that no entry names."""
    import jax.numpy as jnp
    from dmlc_core_tpu.models.fm import FMRows, _fm_margin_rows
    R, U, NNZ, real = 64, 96, 1024, 700
    rng = np.random.default_rng(k)
    # rows 54.. hold no entry; the padding entries name row R, value 0
    row = np.concatenate([np.sort(rng.integers(0, R - 10, real)),
                          np.full(NNZ - real, R)]).astype(np.int32)
    slot = np.concatenate([(80 * rng.random(real) ** 3).astype(np.int32),
                           np.full(NNZ - real, U - 1, np.int32)])
    assert len(set(slot[:real])) < real / 4            # repeats
    val = np.concatenate([rng.normal(size=real),
                          np.zeros(NNZ - real)]).astype(np.float32)
    rows = FMRows(jnp.float32(0.3),
                  jnp.asarray(rng.normal(size=U).astype(np.float32)),
                  jnp.asarray(0.3 * rng.normal(size=(U, k))
                              .astype(np.float32)))
    coef = jnp.asarray(rng.normal(size=R).astype(np.float32))

    def lane_by_lane(rows):
        w, v = rows.w[slot], rows.v[slot]

        def seg(x):
            return jax.ops.segment_sum(x, row, num_segments=R + 1)[:R]
        vx = v * val[:, None]
        s1, s2 = seg(vx), seg(vx * vx)
        return rows.b + seg(val * w) + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)

    def merged(rows):
        return _fm_margin_rows(rows, slot, row, val, R)

    def readings(margin):
        def scalar(rows):
            m = margin(rows)
            return jnp.sum(coef * m + m * m), m
        (_, m), grads = jax.jit(jax.value_and_grad(scalar, has_aux=True))(
            rows)
        return {"margin": m, **grads._asdict()}

    got, want = readings(merged), readings(lane_by_lane)
    assert np.all(np.asarray(want["margin"])[R - 10:] == 0.3)
    for name, b in want.items():
        a, b = np.asarray(got[name]), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b), name
    # rows of the list that no entry names take no gradient; the padding's
    # slot takes none either (its entries have value 0, and row R is cut)
    unnamed = np.setdiff1d(np.arange(U), slot[:real])
    assert unnamed.size and U - 1 in unnamed
    assert not np.asarray(got["v"])[unnamed].any()
    assert not np.asarray(got["w"])[unnamed].any()


# -- the row form's hand-written backward (models/fm.py, ISSUE 37) ------------
def _shard_by_hand(k, seed=0, R=64, F=200, U=96, NNZ=1024, real=700):
    """A shard's arrays and tables to read them from: 80 distinct columns
    that recur (a cubed draw), the list padded beyond the tables, padding
    entries (value 0) on the sacrificial row ``R`` and the list's last
    padding slot, row 0 of one entry, rows 54.. of none, some rows of
    weight 0."""
    import jax.numpy as jnp
    from dmlc_core_tpu.models.fm import FMParams
    rng = np.random.default_rng(100 * k + seed)
    cols = np.concatenate([np.sort(rng.choice(F, 80, replace=False)),
                           np.full(U - 80, F)]).astype(np.int32)
    row = np.concatenate([[0], np.sort(rng.integers(1, R - 10, real - 1)),
                          np.full(NNZ - real, R)]).astype(np.int32)
    slot = np.concatenate([(80 * rng.random(real) ** 3).astype(np.int32),
                           np.full(NNZ - real, U - 1, np.int32)])
    assert len(set(slot[:real])) < real / 4 and (row == 0).sum() == 1
    val = np.concatenate([rng.normal(size=real),
                          np.zeros(NNZ - real)]).astype(np.float32)
    shard = {"cols": jnp.asarray(cols), "slot": jnp.asarray(slot),
             "row": jnp.asarray(row), "val": jnp.asarray(val),
             "label": jnp.asarray(rng.integers(0, 2, R).astype(np.float32)),
             "weight": jnp.asarray((rng.random(R) < 0.9).astype(np.float32))}
    params = FMParams(
        jnp.float32(0.3), jnp.asarray(rng.normal(size=F).astype(np.float32)),
        jnp.asarray(0.3 * rng.normal(size=(F, k)).astype(np.float32)))
    return params, shard


def _plain_rows(params, cols):
    """The rows of ``[F]`` and ``[F, K]`` tables at one shard's list."""
    learner = _plain_layout(FMLearner(*params.v.shape))
    return learner._gather_rows(params, {"cols": cols})


def _plain_margin(params, shard, R, val=None):
    """The margin by its plain composition, which autodiff differentiates:
    the gathered rows, each expanded by itself, into the entries' sums."""
    from dmlc_core_tpu.models.fm import _fm_margin_entries
    rows = _plain_rows(params, shard["cols"])
    return _fm_margin_entries(
        rows.b, rows.w[shard["slot"]], rows.v[shard["slot"]], shard["row"],
        shard["val"] if val is None else val, R)


def _hand_margin(params, shard, R, val=None):
    from dmlc_core_tpu.models.fm import _fm_margin_rows
    return _fm_margin_rows(
        _plain_rows(params, shard["cols"]), shard["slot"], shard["row"],
        shard["val"] if val is None else val, R)


def _close(got, want, rel=1e-6):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= rel * np.linalg.norm(b), (a, b)


@pytest.mark.parametrize("objective", ["logistic", "squared"])
@pytest.mark.parametrize("k", [1, 8, 16, 32])
def test_fm_hand_written_backward_equals_autodiff(k, objective):
    """The loss's gradient through ``_fm_margin_rows`` and its hand-written
    backward against ``jax.grad`` of the plain composition, down to the
    tables and to the entries' values; the margins agree to the bit (the
    forward sums the same lanes)."""
    from dmlc_core_tpu.models.linear import objective_loss
    R = 64
    params, shard = _shard_by_hand(k)

    def readings(margin):
        def loss(params, val):
            m = margin(params, shard, R, val)
            return objective_loss(m, shard, R, objective)[0], m
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, shard["val"])

    (loss, m), grads = readings(_hand_margin)
    (want_loss, want_m), want = readings(_plain_margin)
    assert np.asarray(m).tobytes() == np.asarray(want_m).tobytes()
    assert float(loss) == float(want_loss)
    assert all(np.asarray(g).any() for g in jax.tree.leaves(want))
    _close(grads, want)
    # what no entry names takes no gradient: the list's padding, the
    # tables' other rows
    named = np.asarray(shard["cols"])[np.unique(np.asarray(shard["slot"])[
        np.asarray(shard["val"]) != 0])]
    rest = np.setdiff1d(np.arange(params.w.shape[0]), named)
    assert not np.asarray(grads[0].v)[rest].any()
    assert not np.asarray(grads[0].w)[rest].any()


@pytest.mark.parametrize("objective", ["logistic", "squared"])
@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["nomesh", "mesh4"])
def test_fm_step_equals_autodiff_of_the_plain_margin(tmp_path, mesh_devices,
                                                     objective):
    """A whole step, on one device and on a mesh of 4, against the update
    from ``jax.grad`` of the plain composition over every shard: the file
    has rows of one entry, recurring columns, padded entries and a last
    batch with padding rows."""
    from dmlc_core_tpu.models.linear import objective_loss
    from dmlc_core_tpu.tpu.device_iter import unpack_shard
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh, objective=objective,
                        learning_rate=0.3, init_scale=0.2)
    rng = np.random.default_rng(1)
    params = learner.init(seed=7)._replace(
        b=jax.numpy.float32(0.25),
        w=jax.numpy.asarray(rng.normal(size=F_ROWS).astype(np.float32)))
    plain = FMLearner(F_ROWS, k=K_ROWS, learning_rate=0.3)
    for batch in _batches(write_recurring_libsvm(tmp_path / "a.libsvm"),
                          mesh):
        tree = batch.tree()
        R = batch.rows_per_shard
        shards = [unpack_shard({k: v[d] for k, v in tree.items()})
                  for d in range(tree["aux"].shape[0])]
        assert any((np.bincount(np.asarray(s["row"]), minlength=R + 1)[:R]
                    == 1).any() for s in shards)        # one-entry rows
        assert all((np.asarray(s["val"]) == 0).any() for s in shards)
        host = jax.tree.map(np.asarray, params)

        def loss(p):
            sums = [objective_loss(_plain_margin(p, s, R), s, R, objective)
                    for s in shards]
            return sum(x[0] for x in sums), sum(x[1] for x in sums)
        (loss_sum, wsum), grads = jax.value_and_grad(loss, has_aux=True)(
            type(params)(*host))
        denom = jax.numpy.maximum(wsum, 1.0)
        want = plain._apply(type(params)(*host), grads, denom)
        params, got_loss = learner.step(params, batch)
        np.testing.assert_allclose(float(got_loss), float(loss_sum / denom),
                                   rtol=1e-6)
        for leaf in ("b", "w", "v"):
            a, b = np.asarray(getattr(params, leaf)), \
                np.asarray(getattr(want, leaf))
            assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), leaf


@pytest.mark.parametrize("what", ["margin", "gradient"])
def test_fm_hand_written_backward_under_vmap(what):
    """``predict`` maps the margin over a batch's shards: mapped, the
    margin is the plain composition's to the bit and its gradient the
    plain one's, shard by shard."""
    import jax.numpy as jnp
    R, k = 64, 8
    params, _ = _shard_by_hand(k)
    shards = [_shard_by_hand(k, seed)[1] for seed in (1, 2, 3)]
    stacked = {key: jnp.stack([s[key] for s in shards]) for key in shards[0]}
    coef = jnp.asarray(np.random.default_rng(0).normal(size=R)
                       .astype(np.float32))

    def mapped(margin):
        def f(params):
            m = jax.vmap(lambda s: margin(params, s, R))(stacked)
            return jnp.sum(coef * m + m * m), m
        return jax.jit(jax.value_and_grad(f, has_aux=True))(params)

    (_, m), grads = mapped(_hand_margin)
    if what == "margin":
        for i, s in enumerate(shards):
            want = jax.jit(lambda p, s=s: _plain_margin(p, s, R))(params)
            assert np.asarray(m[i]).tobytes() == np.asarray(want).tobytes()
    else:
        _close(grads, mapped(_plain_margin)[1])


@pytest.mark.parametrize("what", ["second-order", "keywords", "unjitted"])
def test_fm_hand_written_backward_takes_what_autodiff_took(what):
    """A gradient of a gradient goes through the backward as through any
    jax code; the arguments may be named; nothing needs a jit around it
    (the entries' values have a cotangent too, dead code in a step:
    test_fm_hand_written_backward_equals_autodiff holds it)."""
    import jax.numpy as jnp
    from dmlc_core_tpu.models.fm import _fm_margin_rows
    R = 64
    params, shard = _shard_by_hand(8)

    def hand(params, val):
        if what != "keywords":
            return _hand_margin(params, shard, R, val)
        return _fm_margin_rows(
            rows=_plain_rows(params, shard["cols"]), slot=shard["slot"],
            row=shard["row"], val=val, num_rows=R)

    def scalar(margin):
        return lambda params, val: jnp.sum(jnp.sin(margin(params, val)))

    def grad_norm(margin):
        return lambda params, val: sum(
            jnp.sum(g * g) for g in jax.tree.leaves(
                jax.grad(scalar(margin))(params, val)))

    def readings(margin):
        f = grad_norm(margin) if what == "second-order" else scalar(margin)
        wrap = (lambda f: f) if what == "unjitted" else jax.jit
        return wrap(jax.grad(f, argnums=(0, 1)))(params, shard["val"])

    want = readings(lambda params, val: _plain_margin(params, shard, R, val))
    assert all(np.asarray(g).any() for g in jax.tree.leaves(want))
    _close(readings(hand), want, rel=1e-5 if what == "second-order" else 1e-6)


@pytest.mark.parametrize("mesh_devices", [0, 4], ids=["nomesh", "mesh4"])
def test_fm_csr_step_makes_few_arrays_of_the_entries(tmp_path, mesh_devices):
    """The operations of the lowered step that produce a float ``[NNZ, n]``
    tensor, a scalar's or a column's broadcast aside: 14, where autodiff's
    backward left 19 (ISSUE 37; on the chip every such array is padded to
    128 lanes and costs its bytes whatever ``n``: compiled for the v5e the
    step reads and writes one 13 times for 24,
    tests/test_step_compiles_for_v5e.py). A slice, a pad or a
    concatenation more around the indexed passes shows here."""
    from jaxlib.mlir import ir
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh)
    with DeviceRowBlockIter(
            write_recurring_libsvm(tmp_path / "n.libsvm"), batch_rows=256,
            layout="csr", mesh=mesh, min_nnz_bucket=128) as it:
        batch = next(iter(it))
    tree = batch.tree()
    nnz = tree["big"].shape[-1]
    # a shape of the entries' own: not the list's, not the rows'
    assert nnz not in (tree["cols"].shape[-1], batch.rows_per_shard,
                       batch.rows_per_shard + 1)
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree.keys()))).lower(
            learner.init(), tree)
    made = [(op.name, tuple(r.type.shape)) for op in _lowered_ops(lowered)
            for r in op.results
            if isinstance(r.type, ir.RankedTensorType)
            and len(r.type.shape) == 2 and r.type.shape[0] == nnz
            and str(r.type.element_type) == "f32"
            and op.name != "stablehlo.broadcast_in_dim"]
    assert len(made) == 14, sorted(made)
    # none wider than the segment sum's 2K+1 lanes, and that one once
    assert sorted(n for _, (_, n) in made)[-2:] == [K_ROWS + 1,
                                                    2 * K_ROWS + 1]


@pytest.mark.parametrize("mesh_devices", [0, 2], ids=["nomesh", "mesh2"])
def test_fm_dense_steps_keep_the_table_form(tmp_path, mesh_devices):
    """A dense batch has no columns to keep a gradient's rows by: with and
    without a mesh its gradient is a table (a CSR batch's is not, on any
    mesh: tests/test_fm_dp.py)."""
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(F_ROWS, k=K_ROWS, mesh=mesh)
    params = learner.init()
    uri = write_recurring_libsvm(tmp_path / "d.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, layout="dense", mesh=mesh,
                            min_nnz_bucket=2048,
                            dense_dtype="float32") as it:
        batch = next(iter(it))
    tree = batch.tree()
    assert not learner._takes_row_form(tree)
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree.keys()))).lower(params, tree)
    text = lowered.as_text(debug_info=True)
    assert "dp.loss_grad/transpose(jvp(fm.dense))" in text
    assert ("dp.allreduce" in text) == bool(mesh_devices)
    # the gradient is a table, and _apply writes the new ones from it: the
    # rule's step added to each whole table under dp.apply
    ops = _table_ops(lowered, {(F_ROWS, K_ROWS), (F_ROWS,)})
    names = {name for name, _ in ops}
    assert sum(name == "stablehlo.add" and "dp.apply" in loc
               for name, loc in ops) == 2, ops
    if mesh_devices:
        assert "stablehlo.all_reduce" in names
    # and the step counts as no row update
    from dmlc_core_tpu import telemetry
    rows = telemetry.counter("model_step_row_updates_total",
                             {"model": "FMLearner"})
    before = rows.value
    learner.step(params, batch)
    assert rows.value == before


# -- the step donates its state (models/_dp.py _own_state, ISSUE 40) ----------
def _state_copies(model="FMLearner"):
    from dmlc_core_tpu import telemetry
    return telemetry.counter("model_step_state_copies_total",
                             {"model": model})


def _deleted(params):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(params)]


def test_a_state_the_caller_made_survives_a_step(tmp_path):
    """``init()``'s state is the caller's: copied in once, never deleted,
    and as readable after the step as before it."""
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    learner = FMLearner(F_ROWS, k=K_ROWS)
    p0 = learner.init(3)
    before = jax.tree.map(np.asarray, p0)
    copies = _state_copies()
    at = copies.value
    p1, _ = learner.step(p0, batches[0])
    assert copies.value == at + 1
    assert _deleted(p0) == [False] * 3
    for a, b in zip(before, p0):
        assert np.array_equal(a, np.asarray(b))
    assert not np.array_equal(np.asarray(p1.v), before.v)
    # and again: the same foreign state steps to the same place, bit for bit
    again, _ = learner.step(p0, batches[0])
    assert copies.value == at + 2
    for a, b in zip(p1, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_state_a_step_returned_is_consumed_when_handed_back(tmp_path):
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    learner = FMLearner(F_ROWS, k=K_ROWS)
    copies = _state_copies()
    p1, _ = learner.step(learner.init(3), batches[0])
    at = copies.value
    assert _deleted(p1) == [False] * 3
    p2, _ = learner.step(p1, batches[1])
    assert copies.value == at            # the learner's own: no copy
    assert _deleted(p1) == [True] * 3
    assert _deleted(p2) == [False] * 3
    # the same leaves in a tree built anew are still the learner's own
    p3, _ = learner.step(type(p2)(*p2), batches[0])
    assert copies.value == at and _deleted(p2) == [True] * 3
    # a caller that kept the old state is told by the runtime
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(p1.v)
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        learner.step(p1, batches[0])
    assert _deleted(p3) == [False] * 3   # the refused call consumed nothing


@pytest.mark.parametrize("leaf", ["b", "w", "v"])
def test_a_tree_with_one_leaf_replaced_is_foreign(tmp_path, leaf):
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    learner = FMLearner(F_ROWS, k=K_ROWS)
    copies = _state_copies()
    p1, _ = learner.step(learner.init(3), batches[0])
    mixed = p1._replace(**{leaf: jax.numpy.copy(getattr(p1, leaf))})
    at = copies.value
    p2, _ = learner.step(mixed, batches[1])
    assert copies.value == at + 1
    assert _deleted(p1) == [False] * 3 and _deleted(mixed) == [False] * 3
    # an equal state is not the same state: identity decides, not equality
    own, _ = learner.step(p2, batches[0])
    twin = jax.tree.map(jax.numpy.copy, own)
    learner.step(twin, batches[0])
    assert copies.value == at + 2 and _deleted(own) == [False] * 3


def test_another_learners_state_is_foreign(tmp_path):
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    one, other = FMLearner(F_ROWS, k=K_ROWS), FMLearner(F_ROWS, k=K_ROWS)
    p1, _ = one.step(one.init(3), batches[0])
    at = _state_copies().value
    other.step(p1, batches[1])
    assert _state_copies().value == at + 1
    assert _deleted(p1) == [False] * 3
    one.step(p1, batches[1])             # still its own learner's to consume
    assert _state_copies().value == at + 1
    assert _deleted(p1) == [True] * 3


@pytest.mark.parametrize("model", ["fm-dense", "linear-csr"])
def test_table_steps_donated_equal_steps_each_from_a_fresh_copy(tmp_path,
                                                                model):
    """Six steps through the states the learner returned (updated where
    they lie) and six steps each from a copy the caller made (copied in):
    the same parameters and losses, bit for bit; one copy against six. The
    table form here; the row form in every layout: tests/test_fm_ps.py."""
    uri = write_recurring_libsvm(tmp_path / "r.libsvm", rows=768)
    with DeviceRowBlockIter(uri, batch_rows=256, min_nnz_bucket=2048,
                            layout="dense" if model == "fm-dense" else "csr",
                            dense_dtype="float32") as it:
        batches = list(it) * 2

    def run(fresh):
        learner = (LinearLearner(F_ROWS) if model == "linear-csr"
                   else FMLearner(F_ROWS, k=K_ROWS))
        copies = _state_copies(type(learner).__name__)
        at = copies.value
        params, losses = learner.init(), []
        for batch in batches:
            if fresh:
                params = jax.tree.map(jax.numpy.copy, params)
            params, loss = learner.step(params, batch)
            losses.append(float(loss))
        return jax.tree.map(np.asarray, params), losses, copies.value - at

    own, own_losses, own_copies = run(fresh=False)
    each, each_losses, each_copies = run(fresh=True)
    assert (own_copies, each_copies) == (1, len(batches))
    assert own_losses == each_losses and own_losses[0] != own_losses[-1]
    for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(each)):
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("model", ["fm-csr", "fm-dense", "linear-csr"])
@pytest.mark.parametrize("mesh_devices", [0, 2], ids=["nomesh", "mesh2"])
def test_the_lowered_step_marks_its_parameters_as_donated(tmp_path, model,
                                                          mesh_devices):
    """Every form of the step (row and table, with and without a mesh)
    donates its state and nothing of the batch."""
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = (LinearLearner(F_ROWS, mesh=mesh) if model == "linear-csr"
               else FMLearner(F_ROWS, k=K_ROWS, mesh=mesh))
    uri = write_recurring_libsvm(tmp_path / "r.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, min_nnz_bucket=2048,
                            layout="dense" if model == "fm-dense" else "csr",
                            dense_dtype="float32", mesh=mesh) as it:
        batch = next(iter(it))
    tree = batch.tree()
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree))).lower(learner.init(), tree)
    (params, batch_info), _ = lowered.args_info
    assert all(a.donated for a in jax.tree.leaves(params))
    assert not any(a.donated for a in jax.tree.leaves(batch_info))


# -- FTRL-proximal on w, AdaGrad on v (FMLearner(updater="ftrl")) -----------
F_FTRL, K_FTRL, B_FTRL = 40, 3, 128
RULE = dict(alpha=0.3, beta=1.0, l1=0.02, l2=0.1, v_lr=0.2, v_beta=0.5)


def _ftrl_reference():
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import fm as ref_fm, ftrl as ref_ftrl
    return ref_fm, ref_ftrl


def write_ftrl_libsvm(path, rows=300, seed=13, twice=True):
    """Rows of 2 to 6 features from a cubed draw over the first 36 of 40
    (so they recur across a batch and across batches; never one alone: the
    plain reference's loss has no derivative at a margin of exactly 0,
    which a lone feature's row has at init, ROADMAP D16), every third
    row naming one column twice (with ``twice``: a dense row holds a column
    once), the last row feature 39 too (a dense
    batch is as wide as its largest id), so that 36..38 are never named;
    values a text round trip keeps. 300 rows in batches of 128: the last
    batch has padding rows. Returns (label, lens, col, val)."""
    rng = np.random.default_rng(seed)
    label, lens, col, val = [], [], [], []
    with open(path, "w") as f:
        for r in range(rows):
            ids = (36 * rng.random(rng.integers(2, 7)) ** 3).astype(int)
            ids = sorted(set(ids.tolist()) | {35})[-6:]
            if twice and r % 3 == 0:
                ids.append(ids[0])                 # a column twice in a row
            if r == rows - 1:
                ids.append(F_FTRL - 1)
            vals = rng.choice([0.5, 1.0, 1.5, 2.0], len(ids))
            y = int(rng.integers(0, 2))
            f.write(f"{y} " + " ".join(f"{c}:{v}" for c, v in zip(ids, vals))
                    + "\n")
            label.append(y)
            lens.append(len(ids))
            col += ids
            val += vals.tolist()
    return (np.array(label, np.float32), np.array(lens), np.array(col),
            np.array(val, np.float32))


def _ftrl_expected(rows, seed, epochs):
    """The plain reference (benchmarks/reference/ftrl.py) batch by batch
    over the file's rows, every batch of 128 rows as the iterator cuts
    them, on the tables' touched rows: the losses and the compact state."""
    import jax.numpy as jnp
    ref_fm, ref_ftrl = _ftrl_reference()
    label, lens, col, val = rows
    uniq, inv = np.unique(col, return_inverse=True)
    v0 = ref_fm.initial_factors(seed, F_FTRL, K_FTRL, 0.3, uniq)
    s = ref_ftrl.initial_state(v0, jnp.float32)
    rule = ref_ftrl.Rule(**RULE)
    off = np.concatenate([[0], np.cumsum(lens)])
    losses = []
    for _ in range(epochs):
        for r0 in range(0, label.size, B_FTRL):
            r1 = min(r0 + B_FTRL, label.size)
            c, x = ref_fm.pad_rows(lens[r0:r1], inv[off[r0]:off[r1]],
                                   val[off[r0]:off[r1]], int(lens.max()))
            batch = ref_fm.Batch(jnp.asarray(label[r0:r1]), jnp.asarray(c),
                                 jnp.asarray(x))
            value, grad = jax.value_and_grad(ref_fm.loss)(
                ref_fm.Params(s.b, s.w, s.v), batch)
            losses.append(float(value))
            s = ref_ftrl.update(s, grad, rule)
    return losses, uniq, s


def _ftrl_program(uri, layout, epochs, seed):
    learner = FMLearner(F_FTRL, k=K_FTRL, init_scale=0.3, updater="ftrl",
                        **RULE)
    state = learner.init(seed)
    start = jax.tree.map(np.asarray, state)
    losses = []
    with DeviceRowBlockIter(uri, batch_rows=B_FTRL, layout=layout,
                            min_nnz_bucket=1024,
                            dense_dtype="float32") as it:
        for _ in range(epochs):
            for batch in it:
                state, loss = learner.step(state, batch)
                losses.append(float(loss))
            it.before_first()
    return learner, start, state, losses


@pytest.mark.parametrize("layout", ["csr", "dense"])
def test_fm_ftrl_steps_equal_the_plain_reference(tmp_path, layout):
    """Six steps (two epochs of three batches, the last short) of the row
    form (csr) and of the table form (dense) against the reference's
    equations: the losses and every leaf of the state, ``b`` under its own
    ``z`` and ``n``; both branches of the L1 closed form taken; rows no
    batch names, and the list's padding beyond the tables, left bit for
    bit as ``init`` made them."""
    uri = str(tmp_path / "f.libsvm")
    rows = write_ftrl_libsvm(uri, twice=layout == "csr")
    want_losses, uniq, want = _ftrl_expected(rows, seed=5, epochs=2)
    learner, start, got, losses = _ftrl_program(uri, layout, 2, seed=5)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    assert type(got).__name__ == "FMState"
    p, s = got
    for name, a, b in (("b", p.b, want.b), ("zb", s.zb, want.zb),
                       ("nb", s.nb, want.nb)):
        np.testing.assert_allclose(float(a), float(b), rtol=2e-5,
                                   err_msg=name)
    assert float(s.nb) > 0 and float(p.b) != 0     # b has its own z, n
    for name, table, ref in (("w", p.w, want.w), ("v", p.v, want.v),
                             ("z", s.z, want.z), ("n", s.n, want.n),
                             ("g", s.g, want.g)):
        a, b = np.asarray(table)[uniq], np.asarray(ref)
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max(), name
    w = np.asarray(p.w)[uniq]
    z = np.asarray(s.z)[uniq]
    # the L1 term holds some weights at exactly 0 and lets others through
    assert (w == 0).any() and (w != 0).any()
    assert (np.abs(z[w == 0]) <= RULE["l1"]).all()
    assert (np.abs(z[w != 0]) > RULE["l1"]).all()
    # features 36..38 are never named: every leaf's row as init made it
    rest = np.setdiff1d(np.arange(F_FTRL), uniq)
    assert rest.size == 3
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(start)):
        if np.ndim(b):
            assert np.asarray(a)[rest].tobytes() == b[rest].tobytes()
            assert np.shape(a) == np.shape(b)


def test_fm_ftrl_padding_beyond_the_tables_writes_nothing(tmp_path):
    """The CSR step's list is padded past the tables' last row (col_slots):
    its reads fill zeros and its writes are dropped, in every leaf; what
    the batch names moved."""
    uri = str(tmp_path / "f.libsvm")
    write_ftrl_libsvm(uri, rows=B_FTRL)
    learner = FMLearner(F_FTRL, k=K_FTRL, init_scale=0.3, updater="ftrl",
                        **RULE)
    with DeviceRowBlockIter(uri, batch_rows=B_FTRL, layout="csr",
                            min_nnz_bucket=1024) as it:
        batch = next(iter(it))
    cols = np.asarray(batch.tree()["cols"])[0]
    named = cols[cols < F_FTRL]
    assert (cols >= F_FTRL).sum() > 0              # padding past the tables
    state = learner.init(2)
    before = jax.tree.map(np.asarray, state)
    after, _ = learner.step(state, batch)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        a = np.asarray(a)
        assert a.shape == b.shape
        if a.ndim:
            untouched = np.setdiff1d(np.arange(F_FTRL), named)
            assert a[untouched].tobytes() == b[untouched].tobytes()
    assert (np.asarray(after.slots.n)[named] > 0).all()


def test_fm_ftrl_state_is_donated_and_counted(tmp_path):
    """One pytree of eight leaves, donated as SGD's three are: init's state
    copied in once (model_step_state_copies_total 1 over a loop), every
    later state consumed; each step counted under its rule, the state's
    bytes by part."""
    from dmlc_core_tpu import telemetry
    uri = str(tmp_path / "f.libsvm")
    write_ftrl_libsvm(uri)
    copies = _state_copies()
    rule = telemetry.counter("model_step_rule_updates_total",
                             {"model": "FMLearner", "rule": "ftrl"})
    at, rules_at = copies.value, rule.value
    learner, start, state, losses = _ftrl_program(uri, "csr", 2, seed=1)
    assert copies.value == at + 1
    assert rule.value == rules_at + len(losses) == rules_at + 6
    assert len(jax.tree.leaves(state)) == 8
    assert telemetry.gauge("model_state_bytes", {
        "model": "FMLearner", "part": "params"}).value == 4 * (
            1 + F_FTRL * (K_FTRL + 1))
    assert telemetry.gauge("model_state_bytes", {
        "model": "FMLearner", "part": "slots"}).value == 4 * (
            2 + F_FTRL * (K_FTRL + 2))
    batches = _batches(write_recurring_libsvm(tmp_path / "r.libsvm"))
    learner = FMLearner(F_ROWS, k=K_ROWS, updater="ftrl", l1=0.01)
    p1, _ = learner.step(learner.init(3), batches[0])
    p2, _ = learner.step(p1, batches[1])
    assert _deleted(p1) == [True] * 8 and _deleted(p2) == [False] * 8
    tree = batches[0].tree()
    lowered = learner._build_step(batches[0].rows_per_shard,
                                  tuple(sorted(tree))).lower(p2, tree)
    (params, _), _ = lowered.args_info
    assert [a.donated for a in jax.tree.leaves(params)] == [True] * 8
    assert "opt.update" in lowered.as_text(debug_info=True)


@pytest.mark.parametrize("layout", ["csr", "dense"])
def test_fm_sgd_state_and_step_carry_no_slot(tmp_path, layout):
    """The default rule keeps today's state and program: three leaves, and
    no operation of the lowered step (row form for CSR, table form for
    dense) under ``opt.update``; the rule counter does not rise."""
    from dmlc_core_tpu import telemetry
    learner = FMLearner(F_ROWS, k=K_ROWS)
    params = learner.init()
    assert type(params).__name__ == "FMParams"
    assert len(jax.tree.leaves(params)) == 3
    uri = write_recurring_libsvm(tmp_path / "r.libsvm")
    with DeviceRowBlockIter(uri, batch_rows=256, layout=layout,
                            min_nnz_bucket=2048,
                            dense_dtype="float32") as it:
        batch = next(iter(it))
    tree = batch.tree()
    assert learner._takes_row_form(tree) == (layout == "csr")
    text = learner._build_step(batch.rows_per_shard, tuple(sorted(tree))
                               ).lower(params, tree).as_text(debug_info=True)
    assert "dp.apply" in text and "opt.update" not in text
    rule = telemetry.counter("model_step_rule_updates_total",
                             {"model": "FMLearner", "rule": "sgd"})
    learner.step(params, batch)
    assert rule.value == 0


@pytest.mark.parametrize("devices,layout", [
    (2, "replicated"), (8, "replicated"), (4, "range_sharded")])
def test_fm_mesh_forms_refuse_ftrl(devices, layout):
    with pytest.raises(ValueError, match="one device.*summed gradient"):
        FMLearner(64, k=2, mesh=data_mesh(devices), table_layout=layout,
                  updater="ftrl")
    # one device, with or without a mesh, is where the rule runs
    FMLearner(64, k=2, mesh=data_mesh(1), updater="ftrl")


@pytest.mark.parametrize("kw", [dict(updater="adam"), dict(updater="ftrl",
                                                            alpha=0.0),
                                dict(updater="ftrl", l1=-1.0)])
def test_fm_rejects_an_unknown_rule_or_its_bad_settings(kw):
    with pytest.raises(ValueError, match="updater|ftrl wants"):
        FMLearner(16, k=2, **kw)


# -- lane-dense factor tables (models/fm.py _Lanes) ---------------------------
F_LANES = 1003   # no multiple of 128/K: the last row holds pad features


def write_lane_libsvm(path, rows=300, seed=21):
    """Rows of 2 to 6 features from a cubed draw over ``F_LANES`` (they
    recur), each also naming 8 and 9 (one 128-lane row of v at K = 16 and
    at K = 32) and one of the last three features, which share the tables'
    last row with its pad features. 300 rows in batches of 128: three
    steps, the last a short batch."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for r in range(rows):
            ids = set((F_LANES * rng.random(rng.integers(2, 7)) ** 3)
                      .astype(int).tolist())
            ids |= {8, 9, F_LANES - 1 - r % 3}
            f.write(f"{rng.integers(0, 2)} " + " ".join(
                f"{c}:{rng.choice([0.5, 1.0, 1.5])}" for c in sorted(ids))
                + "\n")
    return str(path)


def _lane_batches(uri, mesh=None, col_owners=(1, 0)):
    with DeviceRowBlockIter(uri, batch_rows=128, layout="csr", mesh=mesh,
                            min_nnz_bucket=1024, col_owners=col_owners) as it:
        return list(it)


def _plain_layout(learner):
    """``learner`` with its factor tables held ``[F, K]``, as K and the
    layout would choose where K does not divide 128."""
    from dmlc_core_tpu.models.fm import _Plain
    learner._layout = learner._layout._replace(
        v=_Plain((learner.num_features, learner.k)))
    return learner


@pytest.mark.parametrize("updater", ["sgd", "ftrl"])
@pytest.mark.parametrize("k", [16, 32])
def test_lane_dense_tables_step_as_plain_ones_bit_for_bit(tmp_path, k,
                                                          updater):
    """Three steps of a learner whose ``v`` (and ``G``) is lane-dense and
    of the same learner on ``[F, K]`` tables: the same losses and states to
    the bit, on batches that name two columns of one 128-lane row and whose
    list is padded beyond the tables."""
    kw = (dict(updater="ftrl", init_scale=0.3, **RULE) if updater == "ftrl"
          else dict(learning_rate=0.3, init_scale=0.2))
    batches = _lane_batches(write_lane_libsvm(tmp_path / "l.libsvm"))
    assert [b.total_rows for b in batches] == [128, 128, 44]
    p = 128 // k
    for batch in batches:
        cols = np.asarray(batch.tree()["cols"])[0]
        real = cols[cols < F_LANES]
        assert np.unique(real // p).size < real.size   # rows shared
        assert (cols >= F_LANES).any()                 # the list's padding
    runs = []
    for learner in (FMLearner(F_LANES, k=k, **kw),
                    _plain_layout(FMLearner(F_LANES, k=k, **kw))):
        state, losses = learner.init(5), []
        for batch in batches:
            state, loss = learner.step(state, batch)
            losses.append(float(loss))
        runs.append((learner, state, losses))
    (lane, lane_state, lane_losses), (plain, plain_state, plain_losses) = runs
    assert lane.lane_dense and not plain.lane_dense
    assert lane.factors(lane_state).shape == (F_LANES, k)
    assert jax.tree.leaves(lane_state)[2].shape == (-(-F_LANES // p), 128)
    assert lane_losses == plain_losses
    assert np.asarray(lane.factors(lane_state)).tobytes() == np.asarray(
        plain.factors(plain_state)).tobytes()
    # every other leaf to the bit, G too: both layouts scatter-add the
    # rounded square (the layout's ``add``)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(lane.saved(lane_state)),
            jax.tree.leaves(plain.saved(plain_state))):
        name = jax.tree_util.keystr(path)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name


@pytest.mark.parametrize("form", ["one-device", "replicated",
                                  "range_sharded"])
def test_lane_dense_init_draws_the_plain_table_and_steps_are_counted(
        tmp_path, form):
    """``factors(init(seed))`` is the ``[F, K]`` draw bit for bit; every
    step of lane-dense tables is counted beside the row form's count, on
    one device and on a replicated mesh; range-sharded tables keep ``[F,
    K]`` (an owner's range is rows of v) and are never counted. The steps
    that sort the mesh's list are counted on the replicated mesh alone."""
    import jax.numpy as jnp
    from dmlc_core_tpu import telemetry
    k, seed, scale = 16, 11, 0.2
    F = F_LANES + 1 if form == "range_sharded" else F_LANES   # 4 ranges
    mesh = {"one-device": None, "replicated": data_mesh(2),
            "range_sharded": data_mesh(4)}[form]
    learner = FMLearner(F, k=k, mesh=mesh, init_scale=scale,
                        table_layout=("range_sharded" if form ==
                                      "range_sharded" else "replicated"))
    assert learner.lane_dense == (form != "range_sharded")
    params = learner.init(seed)
    want = scale * jax.random.normal(jax.random.PRNGKey(seed), (F, k),
                                     jnp.float32)
    assert np.asarray(learner.factors(params)).tobytes() == \
        np.asarray(want).tobytes()
    assert learner.from_saved(learner.saved(params)).v.shape == \
        params.v.shape
    lane, rows, sorted_lists = (
        telemetry.counter(name, {"model": "FMLearner"}) for name in
        ("model_step_lane_dense_updates_total",
         "model_step_row_updates_total", "model_step_sorted_lists_total"))
    lane_at, rows_at, sorted_at = lane.value, rows.value, sorted_lists.value
    for batch in _lane_batches(write_lane_libsvm(tmp_path / "c.libsvm"),
                               mesh, learner.col_owners):
        params, _ = learner.step(params, batch)
    assert rows.value == rows_at + 3
    assert lane.value == lane_at + (0 if form == "range_sharded" else 3)
    assert sorted_lists.value == sorted_at + (3 if form == "replicated"
                                              else 0)


def test_the_scoring_model_takes_the_saved_state_and_names_a_packed_one():
    """The scoring server reads ``v`` as ``[F, K]``: ``saved`` gives it
    that, and a lane-dense table saved as it lies is refused by name."""
    from dmlc_core_tpu.base import DMLCError
    from dmlc_core_tpu.serving.model import ScoringModel
    learner = FMLearner(F_LANES, k=16)
    params = learner.init(3)
    as_saved = {k: np.asarray(v) for k, v in
                learner.saved(params)._asdict().items()}
    assert as_saved["v"].shape == (F_LANES, 16)
    ScoringModel("fm", as_saved, F_LANES)
    with pytest.raises(DMLCError, match="serving model v has shape"):
        ScoringModel("fm", {**as_saved, "v": np.asarray(params.v)}, F_LANES)


@pytest.mark.parametrize("mesh_devices,features", [
    (0, F_LANES), (0, 40 * 1024 * 8), (4, F_LANES), (4, 160 * 1024 * 8)],
    ids=["nomesh", "nomesh-large-table", "mesh4", "mesh4-large-table"])
def test_lane_dense_step_scatters_whole_rows_and_makes_no_plain_table(
        tmp_path, mesh_devices, features):
    """The lowered row step on lane-dense ``v``: one scatter-add into it
    under ``dp.apply``, of ``[U, 128]`` rows, beside the one into ``w``;
    nothing of the plain ``[F, K]`` shape is made (no relayout of the
    table); the gather reads whole rows too. The scatter is told that its
    list ascends where the table holds fewer rows than
    ``STREAM_ROWS_PER_UPDATE`` a column of the one list the step scatters,
    ``D·U`` long: on one device the shard's list, on a mesh the shards'
    lists sorted once under ``dp.apply`` (one sort, and then ``w``'s
    scatter is told too); where the table is too large for that, a mesh
    step sorts nothing and tells neither scatter."""
    from dmlc_core_tpu.models.fm import STREAM_ROWS_PER_UPDATE
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    learner = FMLearner(features, k=16, mesh=mesh)
    params = jax.eval_shape(learner.init)
    batch = _lane_batches(write_lane_libsvm(tmp_path / "s.libsvm"), mesh)[0]
    tree = batch.tree()
    lowered = learner._build_step(
        batch.rows_per_shard, tuple(sorted(tree))).lower(params, tree)
    packed = tuple(params.v.shape)
    assert packed == (-(-features // 8), 128)
    assert not _table_ops(lowered, {(features, 16)})
    ops = _lowered_ops(lowered)
    scatters = [op for op in ops if op.name == "stablehlo.scatter"
                and tuple(op.results[0].type.shape) == packed]
    assert len(scatters) == 1, scatters
    assert "dp.apply" in str(scatters[0].location) \
        and "scatter-add" in str(scatters[0].location)
    D, U = tree["cols"].shape
    small = packed[0] < STREAM_ROWS_PER_UPDATE * D * U
    assert small == (features == F_LANES), (packed, D, U)   # both sides held
    assert ("indices_are_sorted = true" in str(scatters[0])) == small
    sorts = [op for op in ops if op.name == "stablehlo.sort"]
    assert len(sorts) == (1 if mesh is not None and small else 0), sorts
    for op in sorts:
        assert "dp.apply" in str(op.location)
        assert tuple(op.operands[0].type.shape) == (D * U,)
    into_w = [op for op in ops if op.name == "stablehlo.scatter"
              and tuple(op.results[0].type.shape) == (features,)]
    assert len(into_w) == 1, into_w
    assert ("indices_are_sorted = true" in str(into_w[0])) == (
        mesh is None or small)
    gathers = [op for op in ops
               if op.name == "stablehlo.gather"
               and tuple(op.operands[0].type.shape) == packed]
    assert len(gathers) == 1
    assert gathers[0].results[0].type.shape[-1] == 128


def test_lane_dense_mesh_step_sorted_equals_unsorted_bit_for_bit(tmp_path):
    """Three steps on 4 devices whose shards share columns (8 and 9 in every
    row) and whose lists are padded beyond the tables: the step that sorts
    the shards' one list before its scatters gives the losses and tables of
    the step that scatters it unsorted, bit for bit (the sort is stable, so
    a column several shards name is added in the mesh's order; on the CPU
    a scatter adds a repeated index in the list's order), and every replica
    holds the same bits."""
    mesh = data_mesh(4)
    batches = _lane_batches(write_lane_libsvm(tmp_path / "m.libsvm"), mesh)
    for batch in batches:
        cols = np.asarray(batch.tree()["cols"])
        real = [set(c[c < F_LANES].tolist()) for c in cols]
        assert set.intersection(*real)                  # shards share columns
        assert (cols >= F_LANES).any()                  # the lists' padding
    runs = []
    for sorts in (True, False):
        learner = FMLearner(F_LANES, k=16, mesh=mesh, learning_rate=0.3,
                            init_scale=0.2)
        if not sorts:   # the unsorted step: the mesh's list flattened
            order = learner._list_order
            learner._list_order = lambda shape: (
                "flat" if order(shape) == "sorts" else order(shape))
        state, losses = learner.init(5), []
        for batch in batches:
            state, loss = learner.step(state, batch)
            losses.append(float(loss))
        runs.append((losses, state))
    (losses, state), (plain_losses, plain_state) = runs
    assert losses == plain_losses
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(plain_state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        first = np.asarray(a.addressable_shards[0].data).tobytes()
        assert len(a.addressable_shards) == 4
        assert all(np.asarray(s.data).tobytes() == first
                   for s in a.addressable_shards)


# the list's order and each table's layout, as the step decides them (the
# lists here are 1,024 columns a shard; a lane-dense table at rank 16 holds
# ceil(F / 8) rows and is told where that is under 39 rows a column of the
# one list it takes): (rank, devices, table layout, rule, features; whether
# the step sorts the shards' one list, whether the scatters into v's and
# into w's layout are told that their list ascends)
F_AT_BOUND = 39 * 1024 * 8   # 39·1,024 rows: one device's bound, not under
F_BETWEEN = 100000 * 8       # over two shards' bound, under eight's
ORDERS = {
    "plain-one-device-sgd": (3, 0, "replicated", "sgd", F_LANES,
                             False, True, True),
    "plain-one-device-ftrl": (3, 0, "replicated", "ftrl", F_LANES,
                              False, True, True),
    "lanes-one-device-sgd": (16, 0, "replicated", "sgd", F_LANES,
                             False, True, True),
    "lanes-one-device-ftrl": (16, 0, "replicated", "ftrl", F_LANES,
                              False, True, True),
    "lanes-one-device-large-table": (16, 0, "replicated", "sgd",
                                     F_AT_BOUND, False, False, True),
    "lanes-mesh2": (16, 2, "replicated", "sgd", F_LANES, True, True, True),
    "lanes-mesh2-large-table": (16, 2, "replicated", "sgd", F_BETWEEN,
                                False, False, False),
    "lanes-mesh8-large-table": (16, 8, "replicated", "sgd", F_BETWEEN,
                                True, True, True),
    "plain-mesh2": (3, 2, "replicated", "sgd", F_LANES, False, False, False),
    "range-sharded-mesh4": (16, 4, "range_sharded", "sgd", F_LANES + 1,
                            False, False, False),
}


@pytest.mark.parametrize("case", list(ORDERS))
def test_each_scatter_is_told_as_the_list_order_and_the_layout_decide(
        tmp_path, case):
    """The lowered row step against the decision table above: one stable
    sort of the ``D·U`` columns under ``dp.apply`` exactly where the step
    sorts, and every scatter into a table (``w``, ``v``; FTRL's ``z``,
    ``n``, ``G`` beside them) told that its list ascends exactly as the
    table says; ``model_step_sorted_lists_total`` rises with the sort and
    ``_list_order`` gives the same answer for the batch."""
    from dmlc_core_tpu import telemetry
    k, devices, layout, rule, F, sorts, v_told, w_told = ORDERS[case]
    mesh = data_mesh(devices) if devices else None
    learner = FMLearner(F, k=k, mesh=mesh, table_layout=layout,
                        updater=rule, learning_rate=0.3, init_scale=0.2,
                        **(RULE if rule == "ftrl" else {}))
    assert learner.lane_dense == (k == 16 and layout == "replicated")
    state = learner.init(1)
    batch = _lane_batches(write_lane_libsvm(tmp_path / "o.libsvm"), mesh,
                          learner.col_owners)[0]
    tree = batch.tree()
    D, U = tree["cols"].shape
    assert U == 1024 * learner.col_owners[0]   # a stretch an owner
    assert (learner._list_order(tree["cols"].shape) == "sorts") == sorts
    lowered = learner._build_step(batch.rows_per_shard,
                                  tuple(sorted(tree))).lower(state, tree)
    ops = _lowered_ops(lowered)
    sort_ops = [op for op in ops if op.name == "stablehlo.sort"]
    assert len(sort_ops) == int(sorts), sort_ops
    for op in sort_ops:
        assert "dp.apply" in str(op.location)
        assert "is_stable = true" in str(op)
        assert tuple(op.operands[0].type.shape) == (D * U,)
    # a range-sharded step holds its owner's range of every table and
    # scatters at the D stretches asked of it, D·C = U columns
    owners = learner.col_owners[0]
    v = (state.params if rule == "ftrl" else state).v
    w_shape, v_shape = (F // owners,), (v.shape[0] // owners,) + v.shape[1:]
    scatters = {shape: [str(op) for op in ops
                        if op.name == "stablehlo.scatter"
                        and tuple(op.results[0].type.shape) == shape]
                for shape in (w_shape, v_shape)}
    leaves = (3, 2) if rule == "ftrl" else (1, 1)
    for shape, told, n in zip((w_shape, v_shape), (w_told, v_told), leaves):
        assert len(scatters[shape]) == n, (shape, scatters)
        assert all(("indices_are_sorted = true" in op) == told
                   for op in scatters[shape]), (shape, told)
    counter = telemetry.counter("model_step_sorted_lists_total",
                                {"model": "FMLearner"})
    at = counter.value
    learner.step(state, batch)
    assert counter.value == at + int(sorts)
