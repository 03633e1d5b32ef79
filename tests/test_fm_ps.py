"""The FM step on range-sharded tables (models/_dp.py, the third form: every
device owns a contiguous range of the rows of ``w`` and ``v``, a batch's rows
are pulled from and pushed to their owners, ``b`` stays on every device) at a
small size on four host devices: against the replicated mesh step and the
one-device step, to float32 rounding (not bit for bit: an owner adds the
shards' gradients of a shared column into its range one scatter after the
pull, the replicas add every shard's rows in one, and the roundings of a
row several shards name differ in the last place); against the benchmark's
plain reference at cell kdd2012-fm-dp4.libfm's tolerances; ``init`` row for
row; the lowered step free of any array of a table's full shape; the
owner-major list of ``col_slots`` natively, in numpy and by the plain owner
rule (benchmarks/reference/owners.py), through every assembler; a short last
batch; the counters; a batch built without the owners refused; a checkpoint
through the path it has; and an epoch of cell criteo1tb-fm-ps4.tsv's file on
one rung (ISSUE 39)."""

import json
import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.base import DMLCError
from dmlc_core_tpu.io.convert import rows_to_csr_recordio
from dmlc_core_tpu.io.native import native_col_slots
from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter, col_slots,
                                           nnz_bucket, owner_counts,
                                           unpack_tree)
from dmlc_core_tpu.tpu.sharding import data_mesh
from dmlc_core_tpu.utils import restore_checkpoint, save_checkpoint

from tests.test_fm import _state_copies as state_copies
from tests.test_fm_dp import (BATCH, BENCH, CASES, F, FEW, LR, SCALE, SEED,
                              STEPS, limits, lowered_ops, norms,
                              reference_readings, write_rows)

sys.path.insert(0, BENCH)

from harness import check, datagen, datagen_criteo  # noqa: E402
from reference import criteo as criteo_rule  # noqa: E402
from reference import owners as owner_rule  # noqa: E402

OWNERS = 4
RANGE = F // OWNERS   # 750 rows an owner


def write_cols(path, cols_of_row, rows, seed=3):
    """libsvm rows whose columns ``cols_of_row(rng, r)`` chooses; labels real
    numbers in [0, 1] (a squared objective has something to fit)."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for r in range(rows):
            cols = np.unique(cols_of_row(rng, r))
            vals = rng.choice([0.5, 1.0, 1.5, 2.0], size=cols.size)
            f.write(f"{rng.integers(0, 2)} " + " ".join(
                f"{c}:{v}" for c, v in zip(cols, vals)) + "\n")


def anywhere(rng, r):
    return rng.integers(0, F, 6)


# what the shards of a batch of 256 rows (64 a shard) ask of the owners
BATCHES = {
    # every row names column 0: every shard asks owner 0 for it
    "shared-column": (3 * BATCH, lambda rng, r: np.append(anywhere(rng, r),
                                                          0)),
    # the second shard's rows lie in owner 2's range alone
    "one-owner-shard": (3 * BATCH, lambda rng, r: (
        rng.integers(2 * RANGE, 3 * RANGE, 6) if 64 <= r % BATCH < 128
        else anywhere(rng, r))),
    # no row names a column of owner 3
    "idle-owner": (3 * BATCH, lambda rng, r: rng.integers(0, 3 * RANGE, 6)),
    # 192 rows in batches of 256: the fourth shard is padding rows alone
    "padding-shard": (192, anywhere),
}


def steps(uri, layout, k, objective, fmt="libsvm", shards=OWNERS):
    """STEPS steps through the data path; the states before the first and
    after each, as numpy, and the losses."""
    mesh = data_mesh(shards) if shards else None
    learner = FMLearner(F, k=k, mesh=mesh, objective=objective,
                        learning_rate=LR, init_scale=SCALE,
                        **({"table_layout": layout} if shards else {}))
    params = learner.init(SEED)
    states, losses = [jax.tree.map(np.asarray, params)], []
    with DeviceRowBlockIter(uri, mesh=mesh, batch_rows=BATCH, fmt=fmt,
                            min_nnz_bucket=64,
                            col_owners=learner.col_owners) as it:
        while len(losses) < STEPS:
            for batch in it:
                params, loss = learner.step(params, batch)
                states.append(jax.tree.map(np.asarray, params))
                losses.append(float(loss))
                if len(losses) == STEPS:
                    break
            it.before_first()
    return states, losses, params


@pytest.mark.parametrize("objective", ["logistic", "squared"])
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("case", sorted(BATCHES))
def test_sharded_step_agrees_with_replicated_and_one_device(tmp_path, case,
                                                            k, objective):
    rows, cols_of_row = BATCHES[case]
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, cols_of_row, rows)
    sharded, loss_s, _ = steps(uri, "range_sharded", k, objective)
    replicated, loss_r, _ = steps(uri, "replicated", k, objective)
    one, loss_1, _ = steps(uri, None, k, objective, shards=0)
    np.testing.assert_allclose(loss_s, loss_r, rtol=1e-6)
    np.testing.assert_allclose(loss_s, loss_1, rtol=1e-6)
    for other in (replicated, one):
        for a, b in zip(sharded[-1], other[-1]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    moved = np.abs(sharded[-1].v - sharded[0].v).max(axis=1) > 0
    if case == "idle-owner":
        assert not moved[3 * RANGE:].any() and moved[:3 * RANGE].any()
    if case == "shared-column":
        assert moved[0]


@pytest.mark.parametrize("case", [c for c in sorted(CASES) if c.endswith("4")])
def test_sharded_step_agrees_with_the_plain_reference(tmp_path, case):
    shards, rows, nnz_of = CASES[case]
    uri = str(tmp_path / "rows.libfm")
    reference = reference_readings(*write_rows(uri, rows, nnz_of,
                                               FEW.get(case, 0)))
    states, losses, _ = steps(uri, "range_sharded", 4, "logistic", "libfm")
    p0, p1, p3 = states[0], states[1], states[-1]
    program = check.Readings(losses, [n / LR for n in norms(p0, p1)],
                             norms(p3, p0))
    gaps = check.gaps(program, reference)
    lim = limits()
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        assert gaps[name] <= lim[name], (name, gaps)


@pytest.mark.parametrize("seed", [0, 11, 2 ** 31 - 1])
def test_init_equals_the_replicated_init_row_for_row(seed):
    mesh = data_mesh(OWNERS)
    whole = FMLearner(F, k=16, mesh=mesh, init_scale=SCALE).init(seed)
    learner = FMLearner(F, k=16, mesh=mesh, init_scale=SCALE,
                        table_layout="range_sharded")
    cut = learner.init(seed)
    assert learner.col_owners == (OWNERS, RANGE)
    for a, b in zip(whole, cut):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert len(cut.b.addressable_shards) == OWNERS   # on every device
    assert all(s.data.shape == () for s in cut.b.addressable_shards)
    for table in (cut.w, cut.v):
        assert table.sharding.is_equivalent_to(
            NamedSharding(mesh, P("data")), table.ndim)
        for o, dev in enumerate(mesh.devices.flat):
            (shard,) = [s for s in table.addressable_shards
                        if s.device == dev]
            assert shard.index[0] == slice(o * RANGE, (o + 1) * RANGE)
            assert shard.data.shape[0] == RANGE


def test_a_layout_needs_a_mesh_and_equal_ranges():
    with pytest.raises(ValueError, match="unknown table_layout"):
        FMLearner(F, table_layout="sharded")
    with pytest.raises(ValueError, match="equal ranges"):
        FMLearner(F, table_layout="range_sharded")             # no mesh
    with pytest.raises(ValueError, match="equal ranges"):
        FMLearner(F + 1, mesh=data_mesh(4), table_layout="range_sharded")
    assert FMLearner(F, mesh=data_mesh(4)).col_owners == (1, 0)


def one_batch(uri, learner, **kw):
    with DeviceRowBlockIter(uri, mesh=learner.mesh, batch_rows=BATCH,
                            fmt="libsvm", min_nnz_bucket=64, **kw) as it:
        return next(iter(it))


def test_lowered_step_holds_no_array_of_a_tables_full_shape(tmp_path):
    """Inside the ``shard_map`` every array of the tables' rank is a range
    or a stretch; the five moves are all-to-alls of ``[D, C, ...]`` under
    ``dp.pull`` and ``dp.push``, and three scalars are summed."""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, BATCH)
    K = 4
    learner = FMLearner(F, k=K, mesh=data_mesh(OWNERS),
                        table_layout="range_sharded")
    batch = one_batch(uri, learner, col_owners=learner.col_owners)
    tree = batch.tree()
    C = tree["cols"].shape[1] // OWNERS
    step = learner._build_step(batch.rows_per_shard, tuple(sorted(tree)))
    assert step.__name__ == "sharded_step"   # the benchmark's name for it
    lowered = step.lower(learner.init(SEED), tree)
    ops = lowered_ops(lowered)
    whole = [(name, loc) for name, results, loc in ops
             if any(shape in {(F,), (F, K)} for shape, _ in results)]
    # the shard_map's own results, the parameters out as the mesh sees them
    assert [name for name, _ in whole] == ["sdy.manual_computation"], whole
    ranges = [(name, loc) for name, results, loc in ops
              if any(shape in {(RANGE,), (RANGE, K)} for shape, _ in results)
              and name != "sdy.manual_computation"]
    assert sorted(name for name, _ in ranges) == ["stablehlo.scatter"] * 2
    assert all("dp.apply" in loc for _, loc in ranges), ranges
    moves = [(results[0], loc) for name, results, loc in ops
             if name == "stablehlo.all_to_all"]
    assert sorted(r for r, loc in moves if "dp.pull" in loc) == sorted([
        ((OWNERS, C), "i32"), ((OWNERS, C), "f32"), ((OWNERS, C, K), "f32")])
    assert sorted(r for r, loc in moves if "dp.push" in loc) == sorted([
        ((OWNERS, C), "f32"), ((OWNERS, C, K), "f32")])
    assert len(moves) == 5
    sums = [(results[0], loc) for name, results, loc in ops
            if name == "stablehlo.all_reduce"]
    assert [r for r, _ in sums] == [((), "f32")] * 3
    assert all("dp.push" in loc for _, loc in sums)
    assert not [n for n, _, _ in ops if n == "stablehlo.all_gather"]


def test_exchange_bytes_count_the_lists_once_and_the_rows_twice(tmp_path):
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, BATCH)
    K = 4
    learner = FMLearner(F, k=K, mesh=data_mesh(OWNERS),
                        table_layout="range_sharded")
    batch = one_batch(uri, learner, col_owners=learner.col_owners)
    U = batch.tree()["cols"].shape[1]
    telemetry.enable(True)
    counter = telemetry.counter("model_step_allreduce_bytes_total",
                                {"model": "FMLearner"})
    before = counter.value
    learner.step(learner.init(), batch)
    assert counter.value - before == \
        12 + OWNERS * U * 4 + 2 * OWNERS * U * (K + 1) * 4


def test_a_batch_laid_out_without_the_owners_is_refused(tmp_path):
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, BATCH)
    learner = FMLearner(F, k=4, mesh=data_mesh(OWNERS),
                        table_layout="range_sharded")
    plain = one_batch(uri, learner)
    with pytest.raises(DMLCError, match="col_owners=learner.col_owners"):
        learner.step(learner.init(), plain)


# -- the owner-major list ------------------------------------------------------

def random_planes(seed, shards=4, nnz=300, owners=OWNERS, rows=RANGE):
    rng = np.random.default_rng(seed)
    n = rng.integers(0, nnz + 1, shards)
    if seed % 2:
        n[rng.integers(shards)] = 0        # a shard without entries
    col = np.zeros((shards, nnz), np.int32)
    for d in range(shards):
        # unevenly: some owner's range is named rarely or never
        hi = owners * rows if seed % 3 else rows * max(1, owners - 1)
        col[d, :n[d]] = rng.integers(0, hi, n[d])
    return col, n


@pytest.mark.parametrize("seed", range(6))
def test_owner_major_list_natively_in_numpy_and_by_the_owner_rule(seed):
    col, n = random_planes(seed)
    py, nat = col.copy(), col.copy()
    py_cols, py_distinct = col_slots(py, n, 8, OWNERS, RANGE)
    nat_cols, nat_distinct = native_col_slots(nat, n, 8, OWNERS, RANGE)
    assert py_distinct == nat_distinct
    assert py_cols.dtype == nat_cols.dtype == np.int32
    assert np.array_equal(py_cols, nat_cols)
    for d in range(len(n)):
        assert np.array_equal(py[d, :n[d]], nat[d, :n[d]])
        # an entry's column is the list's at its slot
        assert np.array_equal(py_cols[d][py[d, :n[d]]], col[d, :n[d]])
    # against the plain statement: a worker's sorted keys cut by the ranges
    C = py_cols.shape[1] // OWNERS
    fullest = max([len(s) for d in range(len(n)) for s in
                   owner_rule.slice_by_ranges(np.unique(col[d, :n[d]]),
                                              OWNERS * RANGE, OWNERS)] + [1])
    assert C == nnz_bucket(fullest, 8)
    for d in range(len(n)):
        keys = np.unique(col[d, :n[d]]) if n[d] else np.zeros(1, np.int64)
        assert np.array_equal(py_cols[d], owner_rule.owner_major(
            keys, OWNERS * RANGE, OWNERS, C))
    want = owner_rule.stretch_counts(
        [col[d, :n[d]] for d in range(len(n))], OWNERS * RANGE,
        OWNERS).sum(axis=0)
    assert np.array_equal(owner_counts(py_cols, n, OWNERS), want)
    assert py_distinct == want.sum()


@pytest.mark.parametrize("seed", range(3))
def test_one_owner_is_todays_list_to_the_byte(seed):
    col, n = random_planes(seed)
    for fn in (col_slots, native_col_slots):
        a, b = col.copy(), col.copy()
        today, d0 = fn(a, n, 8)
        one, d1 = fn(b, n, 8, 1, 0)
        assert d0 == d1 and today.tobytes() == one.tobytes()
        assert all(np.array_equal(a[d, :n[d]], b[d, :n[d]])
                   for d in range(len(n)))


def test_a_column_beyond_the_ranges_is_refused():
    col = np.array([[5, OWNERS * RANGE]], np.int32)
    with pytest.raises(DMLCError, match="beyond"):
        col_slots(col.copy(), [2], 8, OWNERS, RANGE)
    with pytest.raises(DMLCError, match="beyond"):
        native_col_slots(col.copy(), [2], 8, OWNERS, RANGE)


@pytest.mark.parametrize("lane", ["text", "index64", "crec"])
def test_every_assembler_lays_the_list_out_by_owner(tmp_path, lane):
    """The native text batcher, the numpy one (``index64``) and the ``.crec``
    replay send the same owner-major lists and slots, a short last batch
    (40 of 256 rows, lifted to the rungs of the batch before it) included;
    the three counters rise with them."""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, 2 * BATCH + 40)
    mesh = data_mesh(OWNERS)
    kw = {"fmt": "libsvm"}
    src = uri
    if lane == "crec":
        src = uri + ".crec"
        assert rows_to_csr_recordio(uri, src, fmt="libsvm") == 2 * BATCH + 40
        kw = {"fmt": "crec"}
    elif lane == "index64":
        kw["index64"] = True
    telemetry.enable(True)
    names = ("device_cols_owner_max_total", "device_stretch_sent_total",
             "device_stretch_real_total", "device_cols_distinct_total")
    before = [telemetry.counter(c).value for c in names]
    got = []
    with DeviceRowBlockIter(src, mesh=mesh, batch_rows=BATCH,
                            min_nnz_bucket=8, col_owners=(OWNERS, RANGE),
                            **kw) as it:
        for batch in it:
            got.append((unpack_tree(jax.tree.map(np.asarray, batch.tree())),
                        batch))
    rises = [telemetry.counter(c).value - b for c, b in zip(names, before)]
    assert len(got) == 3 and got[2][1].total_rows == 40
    assert got[2][1].tail_lifted
    # the plain statement from the file's own columns, shard by shard
    lines = [[int(t.split(":")[0]) for t in ln.split()[1:]]
             for ln in open(uri)]
    owner_max = sent = real = 0
    for i, (tree, batch) in enumerate(got):
        assert batch.owners == OWNERS
        C = tree["cols"].shape[1] // OWNERS
        assert tree["cols"].shape[1] == got[0][0]["cols"].shape[1]
        shards = [sum(lines[r0:r0 + 64], []) for r0 in range(
            i * BATCH, (i + 1) * BATCH, 64)]
        for d, ids in enumerate(shards):
            keys = np.unique(ids) if ids else np.zeros(1, np.int64)
            assert np.array_equal(tree["cols"][d], owner_rule.owner_major(
                keys, F, OWNERS, C)), (lane, i, d)
            assert np.array_equal(tree["col"][d, :len(ids)], ids)
        counts = owner_rule.stretch_counts(shards, F, OWNERS)
        assert batch.total_distinct == counts.sum()
        assert batch.owner_max == counts.sum(axis=0).max()
        owner_max += batch.owner_max
        sent += tree["cols"].size
        real += counts.sum()
    assert rises == [owner_max, sent, real, real]


def test_one_owner_raises_none_of_the_stretch_counters(tmp_path):
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, BATCH)
    telemetry.enable(True)
    names = ("device_cols_owner_max_total", "device_stretch_sent_total",
             "device_stretch_real_total")
    before = [telemetry.counter(c).value for c in names]
    with DeviceRowBlockIter(uri, mesh=data_mesh(OWNERS), batch_rows=BATCH,
                            fmt="libsvm") as it:
        batch = next(iter(it))
    assert batch.owners == 1 and batch.owner_max == 0
    assert [telemetry.counter(c).value for c in names] == before


# -- a checkpoint --------------------------------------------------------------

def test_a_range_sharded_state_is_saved_and_restored_onto_its_owners(
        tmp_path):
    """Through the path ``utils/checkpoint.py`` has: a leaf is assembled on
    the host shard by shard (never on a device) and, restored onto a
    template of the same layout, put back range by range. (A state of
    gigabytes is assembled in host memory whole, and resume under another
    layout is not offered: ROADMAP R5.)"""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, 3 * BATCH)
    _, _, params = steps(uri, "range_sharded", 4, "logistic")
    ckpt = str(tmp_path / "ps.ckpt")
    save_checkpoint(ckpt, params, step=3)
    mesh = data_mesh(OWNERS)
    like = FMLearner(F, k=4, mesh=mesh, table_layout="range_sharded").init(1)
    back, step, _ = restore_checkpoint(ckpt, like=like)
    assert step == 3
    for a, b, template in zip(params, back, like):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding.is_equivalent_to(template.sharding, b.ndim)
    assert {s.data.shape for s in back.v.addressable_shards} == {(RANGE, 4)}


# -- the step donates its state (models/_dp.py _own_state, ISSUE 40) ----------

def stepper(uri, layout, shards=OWNERS, k=4):
    """A learner of ``layout`` (``None``: one device, no mesh), its first
    state and two batches of ``uri``."""
    mesh = data_mesh(shards) if shards else None
    learner = FMLearner(F, k=k, mesh=mesh, learning_rate=LR,
                        init_scale=SCALE,
                        **({"table_layout": layout} if shards else {}))
    with DeviceRowBlockIter(uri, mesh=mesh, batch_rows=BATCH, fmt="libsvm",
                            min_nnz_bucket=64,
                            col_owners=learner.col_owners) as it:
        batches = list(it)[:2]
    return learner, learner.init(SEED), batches


LAYOUTS = {"one-device": (None, 0), "replicated": ("replicated", OWNERS),
           "range-sharded": ("range_sharded", OWNERS)}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_steps_donated_equal_steps_each_from_a_fresh_copy(tmp_path, layout):
    """Six steps through the states the learner returned and six steps each
    from a copy the caller made: the same tables and losses, bit for bit,
    on every device; one state copied in against six."""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, BATCHES["shared-column"][1], 2 * BATCH)

    def run(fresh):
        learner, params, batches = stepper(uri, *LAYOUTS[layout])
        at, losses = state_copies().value, []
        for batch in batches * 3:
            if fresh:
                params = jax.tree.map(jax.numpy.copy, params)
            params, loss = learner.step(params, batch)
            losses.append(float(loss))
        shards = [[np.asarray(s.data).tobytes()
                   for s in leaf.addressable_shards] for leaf in params]
        return shards, losses, state_copies().value - at

    own, own_losses, own_copies = run(fresh=False)
    each, each_losses, each_copies = run(fresh=True)
    assert (own_copies, each_copies) == (1, 6)
    assert own_losses == each_losses and own_losses[0] != own_losses[-1]
    assert own == each


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_foreign_state_is_copied_where_it_lies_and_survives(tmp_path,
                                                              layout):
    """``init()``'s state on each layout: alive after the step with every
    shard where it was, and the state the step returns laid out as it (a
    range-sharded table stays a range on its owner); handed back, that one
    is consumed and nothing is copied."""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, 2 * BATCH)
    learner, p0, batches = stepper(uri, *LAYOUTS[layout])
    before = jax.tree.map(np.asarray, p0)
    at = state_copies().value
    # the copy itself: new buffers, each shard on the device of its twin
    for kept, twin in zip(p0, learner._own_state(p0)):
        assert twin.sharding == kept.sharding
        for s, t in zip(kept.addressable_shards, twin.addressable_shards):
            assert (s.device, s.index) == (t.device, t.index)
            assert (s.data.unsafe_buffer_pointer()
                    != t.data.unsafe_buffer_pointer())
    assert state_copies().value == at + 1
    at += 1
    p1, _ = learner.step(p0, batches[0])
    assert state_copies().value == at + 1
    for kept, was, new in zip(p0, before, p1):
        assert not kept.is_deleted()
        assert np.array_equal(np.asarray(kept), was)
        assert new.sharding.is_equivalent_to(kept.sharding, new.ndim)
        assert ([s.data.shape for s in new.addressable_shards]
                == [s.data.shape for s in kept.addressable_shards])
    if layout == "range-sharded":
        assert {s.data.shape for s in p1.v.addressable_shards} == {(RANGE, 4)}
    p2, _ = learner.step(p1, batches[1])
    assert state_copies().value == at + 1
    assert all(leaf.is_deleted() for leaf in p1)
    assert not any(leaf.is_deleted() for leaf in tuple(p0) + tuple(p2))


@pytest.mark.parametrize("layout", ["range-sharded", "replicated"])
def test_the_lowered_mesh_step_marks_its_parameters_as_donated(tmp_path,
                                                               layout):
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, BATCH)
    learner, p0, batches = stepper(uri, *LAYOUTS[layout])
    tree = batches[0].tree()
    lowered = learner._build_step(
        batches[0].rows_per_shard, tuple(sorted(tree))).lower(p0, tree)
    (params, batch_info), _ = lowered.args_info
    assert [a.donated for a in jax.tree.leaves(params)] == [True] * 3
    assert not any(a.donated for a in jax.tree.leaves(batch_info))
    # and the compiler takes the offer: every leaf of the state is written
    # into the buffer it came in
    assert lowered.compile().memory_analysis().alias_size_in_bytes >= sum(
        s.data.nbytes for leaf in p0 for s in leaf.addressable_shards[:1])


def test_a_restored_checkpoint_is_foreign_once(tmp_path):
    """The state ``restore_checkpoint`` hands back is the caller's: copied
    in by the step it resumes with, and by no step after it."""
    uri = str(tmp_path / "rows.libsvm")
    write_cols(uri, anywhere, 2 * BATCH)
    learner, p0, batches = stepper(uri, "range_sharded")
    p1, _ = learner.step(p0, batches[0])
    ckpt = str(tmp_path / "ps.ckpt")
    save_checkpoint(ckpt, p1, step=1)
    want, _ = learner.step(p1, batches[1])
    back, _, _ = restore_checkpoint(ckpt, like=p0)
    at = state_copies().value
    got, _ = learner.step(back, batches[1])
    assert state_copies().value == at + 1
    assert not any(leaf.is_deleted() for leaf in back)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    learner.step(got, batches[0])
    assert state_copies().value == at + 1


# -- the cell's file -----------------------------------------------------------

def test_an_epoch_of_the_cells_file_lands_on_one_stretch_rung():
    """As ``tests/test_fm_dp.py`` walks the other cells' files: the
    generator's own rows, shard by shard, cut by the owners' ranges by the
    plain rule. A second rung of the nnz capacity or of a stretch would be a
    second compiled shape in the benchmark's window. A quarter of the epoch
    is walked (14 of the 56 global batches: the rows are independent draws,
    and the stretches sit 9 sigma inside their rung)."""
    with open(os.path.join(BENCH, "configs", "criteo1tb-fm-ps4.json")) as f:
        cfg = json.load(f)
    owners = int(cfg["deployment"]["servers"])
    R, nf = int(cfg["batch_rows"]), int(cfg["num_features"])
    assert nf == 1 << int(cfg["hash_bits"])
    batches = 14
    stretches, entries, distinct = [], [], []
    carry = None
    for block in datagen.iter_blocks(cfg["data"], 31, batches * owners * R):
        c = datagen_criteo.cells(cfg["data"], block)
        block.col = criteo_rule.cell_ids(c.column, c.text, c.lens,
                                         cfg["hash_bits"])
        if carry is not None:
            block = datagen.concat_blocks([carry, block])
        whole = block.rows // R
        ends = np.concatenate([[0], np.cumsum(block.lens)])
        for i in range(whole):
            lo, hi = ends[i * R], ends[(i + 1) * R]
            keys = np.unique(block.col[lo:hi])
            stretches.append([len(s) for s in owner_rule.slice_by_ranges(
                keys, nf, owners)])
            distinct.append(keys.size)
            entries.append(hi - lo)
        carry = block.slice_rows(whole * R, block.rows) \
            if block.rows % R else None
    assert carry is None and len(stretches) == batches * owners
    fullest = np.array(stretches).reshape(batches, -1).max(axis=1)
    rungs = {nnz_bucket(int(c), 4096) for c in fullest}
    assert rungs == {53248}, (
        f"stretches of {np.min(stretches)} to {np.max(stretches)} columns "
        f"land on rungs {sorted(rungs)}")
    rungs = {nnz_bucket(int(n), 4096) for n in
             np.array(entries).reshape(batches, owners).max(axis=1)}
    assert rungs == {589824}
    # the ranges fill evenly: the fullest owner of a batch against the mean
    by_owner = np.array(stretches).reshape(batches, owners, owners).sum(axis=1)
    assert (by_owner.max(axis=1) / by_owner.mean(axis=1)).max() < 1.02
    assert 204000 < np.mean(distinct) < 207000
