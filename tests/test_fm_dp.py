"""The mesh step of the FM (models/_dp.py: table form, shard_map, one psum of
loss, weight and gradient a step) against the benchmark's plain reference
(benchmarks/reference/fm.py: FM by its definition on the global batch), as
cell kdd2012-fm-dp4.libfm compares them on the chip, here at a small size on
2, 4 and 8 host devices: losses, first gradient norms and change norms after
three steps inside that cell's own limits, shards of unequal weight, a
shard of padding rows alone and rows that all name one of three columns
included; the replicas bit-identical after every step;
``model_step_allreduce_bytes_total`` counting what the psums are handed; and
the benchmark's three configurations landing every batch of an epoch on one
rung of the distinct-column list (ISSUE 31)."""

import json
import os
import sys

import numpy as np
import pytest

import jax

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import FMLearner
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


def write_rows(path, rows, nnz_of, few=0):
    """Seeded libfm rows with distinct features within a row and values a
    text round trip keeps; returns them as the reference takes them. With
    ``few``, a row's first feature is one of the first ``few`` and the
    others lie above them."""
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
            feats = " ".join(f"{i % 7}:{c}:{v}" for i, (c, v) in enumerate(
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
    states = list(program_steps(uri, shards))
    p0, p1, p3 = states[0][0], states[1][0], states[-1][0]
    program = check.Readings([loss for _, loss in states[1:]],
                             [n / LR for n in norms(p0, p1)], norms(p3, p0))
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


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_allreduce_bytes_counter_counts_what_the_psums_get(tmp_path, shards):
    uri = str(tmp_path / "rows.libfm")
    write_rows(uri, STEPS * BATCH, lambda r: 6)
    counter = telemetry.counter("model_step_allreduce_bytes_total",
                                {"model": "FMLearner"})
    # loss sum and weight sum, and a gradient of the parameters' shapes
    a_step = 8 + 4 * (1 + F + F * K)
    seen = [counter.value]
    for _, loss in program_steps(uri, shards):
        if loss is not None:
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
