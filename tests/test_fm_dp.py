"""The mesh step of the FM (models/_dp.py: for CSR batches the row form under
shard_map, an all-gather of every shard's distinct columns and of the rows of
its gradient and one scatter-add of them all on every replica; the table
form, with one psum of a gradient of the parameters' shapes, for dense
batches and models without the row hooks) against the benchmark's plain
reference (benchmarks/reference/fm.py: FM by its definition on the global
batch), as cell kdd2012-fm-dp4.libfm compares them on the chip, here at a
small size on 2, 4 and 8 host devices: losses, first gradient norms and
change norms after three steps inside that cell's own limits, shards of
unequal weight, a shard of padding rows alone and rows that all name one of
three columns included; the replicas bit-identical after every step; a
column that every shard names updated by each shard's gradient once; the
lowered step free of collectives and intermediates of a table's shape; which
steps count as row updates; ``model_step_allreduce_bytes_total`` counting
what the collectives are handed; and the benchmark's configurations landing
every batch of an epoch on one rung of the distinct-column list (ISSUE 31)."""

import json
import os
import sys

import numpy as np
import pytest

import jax

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import FMLearner, LinearLearner
from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter, nnz_bucket
from dmlc_core_tpu.tpu.sharding import data_mesh

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)

from harness import check, datagen, datagen_criteo  # noqa: E402
from reference import criteo as criteo_rule  # noqa: E402
from reference import fm as ref  # noqa: E402

F, K, LR, SCALE, SEED = 3000, 4, 0.1, 0.1, 11
BATCH, STEPS = 256, 3

# (shards, rows in the file, nonzeros of a row by its place in the batch)
CASES = {
    "even-2": (2, STEPS * BATCH, lambda r: 6),
    "even-4": (4, STEPS * BATCH, lambda r: 6),
    "even-8": (8, STEPS * BATCH, lambda r: 6),
    # the second of four shards holds rows of 40 nonzeros, the others of 3:
    # the hot shard sets the bucket every shard is padded to
    "hot-4": (4, STEPS * BATCH, lambda r: 40 if 64 <= r % BATCH < 128 else 3),
    # 192 rows in batches of 256: the fourth shard is padding rows alone,
    # and an epoch is one step
    "padding-4": (4, 192, lambda r: 6),
    # every row's first feature is one of three (FEW): each shard's backward
    # scatters its distinct columns, a third of the entries a row
    "repeats-4": (4, STEPS * BATCH, lambda r: 3),
}
FEW = {"repeats-4": 3}


def limits():
    with open(os.path.join(BENCH, "configs", "kdd2012-fm-dp4.json")) as f:
        return json.load(f)["limits"]


def write_rows(path, rows, nnz_of, few=0, fmt="libfm"):
    """Seeded libfm rows with distinct features within a row and values a
    text round trip keeps; returns them as the reference takes them. With
    ``few``, a row's first feature is one of the first ``few`` and the
    others lie above them. As ``libsvm`` the same rows without fields."""
    rng = np.random.default_rng(SEED)
    lens = np.array([nnz_of(r) for r in range(rows)])
    label = rng.integers(0, 2, size=rows).astype(np.float32)
    if few:
        col = np.concatenate([np.concatenate(
            [rng.integers(0, few, 1),
             few + rng.choice(F - few, size=n - 1, replace=False)])
            for n in lens])
    else:
        col = np.concatenate([rng.choice(F, size=n, replace=False)
                              for n in lens])
    val = rng.choice(np.array([0.5, 1.0, 1.5, 2.0], np.float32),
                     size=col.size)
    with open(path, "w") as f:
        at = 0
        for r in range(rows):
            feats = " ".join(
                f"{i % 7}:{c}:{v}" if fmt == "libfm" else f"{c}:{v}"
                for i, (c, v) in enumerate(
                    zip(col[at:at + lens[r]], val[at:at + lens[r]])))
            f.write(f"{int(label[r])} {feats}\n")
            at += lens[r]
    return label, lens, col, val


def reference_readings(label, lens, col, val):
    """As ``runners/fm.py`` feeds the reference: the touched rows of the
    tables alone, one stacked batch a step; a file shorter than a batch is
    stepped once an epoch."""
    rows = min(BATCH, label.size)
    uniq, inv = np.unique(col, return_inverse=True)
    v0 = ref.initial_factors(SEED, F, K, SCALE, uniq)
    off = np.concatenate([[0], np.cumsum(lens)])
    stacked = []
    for i in range(STEPS):
        r0 = (i * rows) % label.size
        lo, hi = off[r0], off[r0 + rows]
        c, x = ref.pad_rows(lens[r0:r0 + rows], inv[lo:hi], val[lo:hi],
                            int(lens.max()))
        stacked.append((label[r0:r0 + rows], c, x))
    batches = ref.Batch(*(jax.numpy.asarray(np.stack(leaf))
                          for leaf in zip(*stacked)))
    out = ref.readings(v0, batches, LR)
    return check.Readings(*([float(x) for x in out[k]] for k in
                            ("losses", "grad_norms", "change_norms")))


def replicas(params):
    """The bytes of every leaf on each device's own copy."""
    return [[np.asarray(s.data).tobytes() for s in leaf.addressable_shards]
            for leaf in jax.tree.leaves(params)]


def program_steps(uri, shards):
    """STEPS steps of the mesh learner through the data path; yields the
    state before the first and after every step, and the loss."""
    mesh = data_mesh(shards)
    learner = FMLearner(F, k=K, mesh=mesh, learning_rate=LR, init_scale=SCALE)
    params = learner.init(SEED)
    yield params, None
    done = 0
    with DeviceRowBlockIter(uri, mesh=mesh, batch_rows=BATCH, fmt="libfm",
                            min_nnz_bucket=64) as it:
        while done < STEPS:
            for batch in it:
                assert batch.tree()["big"].shape[0] == shards
                params, loss = learner.step(params, batch)
                yield params, float(loss)
                done += 1
                if done == STEPS:
                    break
            it.before_first()


def norms(a, b):
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(a, b)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_step_agrees_with_the_plain_reference(tmp_path, case):
    shards, rows, nnz_of = CASES[case]
    uri = str(tmp_path / "rows.libfm")
    reference = reference_readings(*write_rows(uri, rows, nnz_of,
                                               FEW.get(case, 0)))
    # read as the states are yielded: the state after step 1 is consumed
    # by step 2 (the step donates what it returned)
    losses, grad_norms = [], None
    for params, loss in program_steps(uri, shards):
        if loss is None:
            p0 = params
            continue
        if not losses:
            grad_norms = [n / LR for n in norms(p0, params)]
        losses.append(loss)
    program = check.Readings(losses, grad_norms, norms(params, p0))
    gaps = check.gaps(program, reference)
    lim = limits()
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        assert gaps[name] <= lim[name], (name, gaps)
    assert reference.change_norms[2] > 0  # the factors moved


@pytest.mark.parametrize("case", sorted(CASES))
def test_replicas_are_bit_identical_after_every_step(tmp_path, case):
    shards, rows, nnz_of = CASES[case]
    uri = str(tmp_path / "rows.libfm")
    write_rows(uri, rows, nnz_of, FEW.get(case, 0))
    seen = 0
    for params, _ in program_steps(uri, shards):
        for copies in replicas(params):
            assert len(copies) == shards
            assert all(c == copies[0] for c in copies[1:])
        seen += 1
    assert seen == STEPS + 1


def test_hot_shard_sets_the_bucket_and_padding_shard_is_empty(tmp_path):
    """The two uneven cases are what their names say."""
    hot, pad = str(tmp_path / "h.libfm"), str(tmp_path / "p.libfm")
    write_rows(hot, STEPS * BATCH, CASES["hot-4"][2])
    write_rows(pad, 192, CASES["padding-4"][2])
    mesh = data_mesh(4)
    with DeviceRowBlockIter(hot, mesh=mesh, batch_rows=BATCH, fmt="libfm",
                            min_nnz_bucket=64, to_device=False) as it:
        batch = next(iter(it))
    real = (np.asarray(batch.val) != 0).sum(axis=1)
    assert list(real) == [192, 2560, 192, 192]
    assert batch.nnz_bucket >= 2560        # every shard padded to the hot one
    with DeviceRowBlockIter(pad, mesh=mesh, batch_rows=BATCH, fmt="libfm",
                            min_nnz_bucket=64, to_device=False) as it:
        batch = next(iter(it))
    assert list(np.asarray(batch.nrows)) == [64, 64, 64, 0]
    assert (np.asarray(batch.weight)[3] == 0).all()


def one_batch(uri, shards, fmt="libfm", **kw):
    mesh = data_mesh(shards) if shards else None
    with DeviceRowBlockIter(uri, mesh=mesh, batch_rows=BATCH, fmt=fmt,
                            min_nnz_bucket=64, **kw) as it:
        return mesh, next(iter(it))


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_a_column_every_shard_names_gets_each_shards_gradient_once(
        tmp_path, shards):
    """Every row names column 0: every shard's list holds it, so the
    gathered list holds it once a shard, and its rows of ``w`` and ``v``
    have to move by the sum of the shards' gradients, as they do when one
    device steps the same rows as one batch."""
    uri = str(tmp_path / "rows.libfm")
    write_rows(uri, BATCH, lambda r: 4, few=1)
    got = {}
    for n in (0, shards):
        mesh, batch = one_batch(uri, n)
        cols = np.asarray(batch.tree()["cols"])
        assert (cols[:, 0] == 0).all() and cols.shape[0] == max(n, 1)
        learner = FMLearner(F, k=K, mesh=mesh, learning_rate=LR,
                            init_scale=SCALE)
        p0 = learner.init(SEED)
        p1, loss = learner.step(p0, batch)
        got[n] = jax.tree.map(np.asarray, p1), float(loss), \
            jax.tree.map(np.asarray, p0)
    (one, loss_one, p0), (many, loss_many, _) = got[0], got[shards]
    assert loss_many == pytest.approx(loss_one, rel=1e-6)
    for leaf in ("w", "v"):
        a, b, start = (getattr(t, leaf) for t in (many, one, p0))
        step0 = np.abs(b[0] - start[0]).max()
        assert step0 > 0
        # a shard's share of the step is about 1/shards of it: one left out
        # or counted twice is a hundred thousand times this tolerance
        assert np.abs(a[0] - b[0]).max() <= 1e-5 * step0, leaf
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(many.b, one.b, rtol=1e-5, atol=1e-8)


def lowered_ops(lowered):
    """(op name, [(shape, element type) of each result], location) of every
    operation of the lowered module."""
    from jaxlib.mlir import ir
    found = []

    def visit(op):
        found.append((op.name, [(tuple(r.type.shape), str(r.type.element_type))
                                for r in op.results
                                if isinstance(r.type, ir.RankedTensorType)],
                      str(op.location)))
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir(dialect="stablehlo").operation.walk(visit)
    return found


COLLECTIVES = ("stablehlo.all_reduce", "stablehlo.all_gather")


@pytest.mark.parametrize("shards", [2, 4])
def test_mesh_step_makes_and_exchanges_nothing_of_a_tables_shape(
        tmp_path, shards):
    uri = str(tmp_path / "rows.libfm")
    write_rows(uri, BATCH, lambda r: 6)
    mesh, batch = one_batch(uri, shards)
    tree = batch.tree()
    U = tree["cols"].shape[1]
    learner = FMLearner(F, k=K, mesh=mesh)
    step = learner._build_step(batch.rows_per_shard, tuple(sorted(tree)))
    assert step.__name__ == "sharded_step"   # the benchmark's name for it
    lowered = step.lower(learner.init(SEED), tree)
    ops = lowered_ops(lowered)
    # of a table's shape: the two scatter-adds under dp.apply and the
    # shard_map's own results, the parameters out; no gradient table
    made = [(name, loc) for name, results, loc in ops
            if any(shape in {(F,), (F, K)} for shape, _ in results)]
    assert sorted(name for name, _ in made) == \
        ["sdy.manual_computation"] + ["stablehlo.scatter"] * 2, made
    for name, loc in made:
        if name == "stablehlo.scatter":
            assert "dp.apply" in loc and "scatter-add" in loc, loc
    assert "transpose(jvp(fm.gather))" not in lowered.as_text(debug_info=True)
    # the collectives, all under dp.allreduce: three scalars summed, and
    # the shards' lists and rows gathered, [U] a shard to [shards, U]
    exchanged = [(name, results, loc) for name, results, loc in ops
                 if name in COLLECTIVES]
    assert all("dp.allreduce" in loc for _, _, loc in exchanged), exchanged
    assert sorted((name, results[0]) for name, results, _ in exchanged) == \
        sorted([("stablehlo.all_reduce", ((), "f32"))] * 3 + [
            ("stablehlo.all_gather", ((shards, U), "i32")),
            ("stablehlo.all_gather", ((shards, U), "f32")),
            ("stablehlo.all_gather", ((shards, U, K), "f32"))]), exchanged


def row_updates(model):
    return telemetry.counter("model_step_row_updates_total",
                             {"model": model}).value


@pytest.mark.parametrize("what", ["fm-csr", "fm-dense", "linear-csr"])
def test_which_mesh_steps_take_the_row_form(tmp_path, what):
    """By what the step can see: the batch's leaves and the model's hooks.
    A dense batch has no columns and the linear learner no row hooks: both
    keep the table form on a mesh, with its all-reduce of a gradient of the
    parameters' shapes, and count as no row update."""
    uri = str(tmp_path / "rows.libsvm")
    write_rows(uri, 2 * BATCH, lambda r: 6, fmt="libsvm")
    dense = what == "fm-dense"
    mesh, batch = one_batch(
        uri, 4, "libsvm", **(dict(layout="dense", dense_dtype="float32")
                             if dense else dict(layout="csr")))
    tree = batch.tree()
    assert ("x" in tree) == dense and ("cols" in tree) == (not dense)
    learner = (LinearLearner(F, mesh=mesh) if what == "linear-csr"
               else FMLearner(F, k=K, mesh=mesh))
    name = type(learner).__name__
    rows = what == "fm-csr"
    assert learner._takes_row_form(tree) == rows
    params = learner.init()
    telemetry.enable(True)
    before = row_updates(name)
    for i in range(3):
        params, loss = learner.step(params, batch)
        assert row_updates(name) - before == (i + 1 if rows else 0)
    assert np.isfinite(float(loss))
    exchanged = [(name, results[0][0]) for name, results, _ in lowered_ops(
        next(iter(learner._step_fn.values())).lower(params, tree))
        if name in COLLECTIVES]
    table = (F, K) if name == "FMLearner" else (F,)
    assert (("stablehlo.all_reduce", table) in exchanged) == (not rows)
    assert ("stablehlo.all_gather" in dict(exchanged)) == rows


@pytest.mark.parametrize("form", ["rows", "table"])
@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_allreduce_bytes_counter_counts_what_the_collectives_get(
        tmp_path, shards, form):
    """Row form (the FM's CSR batches): loss sum, weight sum and ``b``'s
    gradient summed, and every shard's list ``[U]`` int32 gathered with the
    rows of its gradient, ``[U]`` and ``[U, K]`` float32. Table form (the
    linear learner's): loss sum and weight sum, and a gradient of the
    parameters' shapes."""
    uri = str(tmp_path / "rows.libfm")
    write_rows(uri, STEPS * BATCH, lambda r: 6)
    mesh, batch = one_batch(uri, shards, layout="csr")
    U = batch.tree()["cols"].shape[1]
    if form == "rows":
        learner = FMLearner(F, k=K, mesh=mesh)
        a_step = 12 + shards * U * (K + 2) * 4
    else:
        learner = LinearLearner(F, mesh=mesh)
        a_step = 8 + 4 * (1 + F)
    telemetry.enable(True)
    counter = telemetry.counter("model_step_allreduce_bytes_total",
                                {"model": type(learner).__name__})
    params = learner.init()
    seen = [counter.value]
    for _ in range(STEPS):
        params, _ = learner.step(params, batch)
        seen.append(counter.value)
    rises = set(np.diff(seen))
    assert rises == ({0} if shards == 1 else {a_step}), rises


# -- the cells' files and the ladder of the distinct-column list ----------------
@pytest.mark.parametrize("config,traffic,chips,rung,nnz_rung", [
    ("kdd2012-fm", "libfm", 1, 106496, 180224),
    ("kdd2010b-fm", "libsvm", 1, 262144, 491520),
    ("kdd2012-fm-dp4", "libfm", 4, 106496, 180224),
    ("criteo1tb-fm", "tsv", 1, 212992, 589824),
])
def test_an_epoch_of_the_cells_file_lands_on_one_distinct_rung(
        config, traffic, chips, rung, nnz_rung):
    """A second rung in a cell's epoch is a second compiled shape inside the
    benchmark's window (``compiles_in_window``, limit 0): the generator's
    own rows, shard by shard as the assemblers cut them, say before any
    chip time whether a file straddles one, by its distinct columns or by
    its entries. A hashed configuration's columns are its cells' ids by the
    plain statement of the format."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        batches = int(json.load(f)["epoch_batches"])
    R = int(cfg["batch_rows"])
    counts, entries = [], []
    carry = None
    for block in datagen.iter_blocks(cfg["data"], 31, batches * chips * R):
        if cfg.get("format") == "criteo":
            c = datagen_criteo.cells(cfg["data"], block)
            block.col = criteo_rule.cell_ids(c.column, c.text, c.lens,
                                             cfg["hash_bits"])
        if carry is not None:
            block = datagen.concat_blocks([carry, block])
        whole = block.rows // R
        ends = np.concatenate([[0], np.cumsum(block.lens)])
        for i in range(whole):
            lo, hi = ends[i * R], ends[(i + 1) * R]
            counts.append(np.unique(block.col[lo:hi]).size)
            entries.append(hi - lo)
        carry = block.slice_rows(whole * R, block.rows) \
            if block.rows % R else None
    assert carry is None and len(counts) == batches * chips
    # a batch's capacity is its fullest shard's rung
    fullest = np.array(counts).reshape(batches, chips).max(axis=1)
    rungs = {nnz_bucket(int(c), 4096) for c in fullest}
    assert rungs == {rung}, (
        f"{config}: distinct columns a shard {min(counts)} to "
        f"{max(counts)} land on rungs {sorted(rungs)}")
    fullest = np.array(entries).reshape(batches, chips).max(axis=1)
    rungs = {nnz_bucket(int(n), 4096) for n in fullest}
    assert rungs == {nnz_rung}, (
        f"{config}: entries a shard {min(entries)} to {max(entries)} land "
        f"on rungs {sorted(rungs)}")
