"""The rule that chooses a CSR batch's nnz capacity (ISSUE 29): an
eighth-of-an-octave ladder, stated once natively (cpp/src/nnz_bucket.h) and
once in Python (device_iter.nnz_bucket), called by ``PaddedBatcher``,
``CsrRecBatcher`` and ``HostBatcher``.

- the rule's properties over a sweep of counts and floors, and the two
  statements equal on every point (and, at each floor, the two statements
  of the dedupe that sizes its list by the same rule: ISSUE 31,
  tests/test_col_slots.py has its own cases);
- streams shaped like the benchmark's corpora through all three batchers:
  kdd2012's 11 nonzeros a row fill the bucket exactly, kdd2010b's
  24 + Bernoulli x 12 stay on one rung for 50 batches;
- the shard cache replays at the capacity the text epoch had;
- padding is inert: the learners' step on the same rows at the next power
  of two and at the ladder's capacity gives the same loss and parameters;
- the device lane's fill counters (doc/observability.md "Device lane");
- a part's short last batch (ISSUE 34): ``tail_rung`` natively and in
  Python on every point of a sweep, all three batchers send an epoch's
  short last batch at the rungs of the batch before it and count it, full
  batches are the bytes they were, and two epochs build the step once.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.io.convert import rows_to_csr_recordio
from dmlc_core_tpu.io.native import (NativeParser, native_col_slots,
                                     native_nnz_bucket, native_tail_rung,
                                     native_telemetry_snapshot)
from dmlc_core_tpu.models import FMLearner, LinearLearner
from dmlc_core_tpu.tpu import device_iter
from dmlc_core_tpu.tpu.device_iter import (CsrRecHostBatcher,
                                           DeviceRowBlockIter, HostBatcher,
                                           NativeHostBatcher, PaddedBatch,
                                           col_slots, nnz_bucket, tail_rung)
from dmlc_core_tpu.tpu.sharding import data_mesh


# -- the rule -------------------------------------------------------------------
def _sweep():
    """Every count to 20,000, the neighbours of every power of two to 2^34,
    and 4,000 counts spread evenly over the octaves."""
    rng = np.random.default_rng(29)
    pows = 2 ** np.arange(0, 35, dtype=np.int64)
    spread = np.exp2(rng.uniform(0, 34, 4000)).astype(np.int64)
    ns = np.concatenate([np.arange(0, 20001), pows - 1, pows, pows + 1,
                         spread])
    return np.unique(ns[ns >= 0]).tolist()


@pytest.mark.parametrize("floor", [0, 1, 16, 64, 100, 128, 256, 4096, 5000])
def test_rule_properties_and_native_equals_python(floor):
    ns = _sweep()
    eff = max(floor, 1)
    granule_floor = min(eff, 128)
    got = [nnz_bucket(n, floor) for n in ns]
    assert got == [native_nnz_bucket(n, floor) for n in ns]
    # col_slots.h includes nnz_bucket.h: the distinct list's capacity is
    # this rule at this floor, in both statements of the dedupe
    rng = np.random.default_rng(floor)
    real = [3000, 0, 1]
    col = np.zeros((3, 3000), np.int32)
    col[0] = rng.integers(0, 2500, 3000)
    col[2, 0] = 7
    py, nat = col.copy(), col.copy()
    py_cols, py_n = col_slots(py, real, floor)
    nat_cols, nat_n = native_col_slots(nat, real, floor)
    assert np.array_equal(py, nat) and np.array_equal(py_cols, nat_cols)
    assert py_n == nat_n == np.unique(col[0]).size + 1
    assert py_cols.shape == (3, nnz_bucket(py_n - 1, floor))
    by_octave = {}
    for n, b in zip(ns, got):
        assert b >= n and b >= eff, (n, b)
        if n <= eff:
            assert b == eff, (n, b)          # the floor keeps its meaning
            continue
        p = 1 << (n - 1).bit_length()
        if p // 16 >= granule_floor:
            assert b <= 1.125 * n, (n, b)    # padding under an eighth
            assert b % (p // 16) == 0, (n, b)
            by_octave.setdefault(p, set()).add(b)
        else:
            assert b - n < granule_floor, (n, b)
        if n == p and p % granule_floor == 0:
            assert b == n, (n, b)            # a power of two maps to itself
    assert got == sorted(got)                # monotone
    assert by_octave
    for p, rungs in by_octave.items():
        assert len(rungs) <= 8, (p, sorted(rungs))
    # the cells' counts (PERF.md section 4): kdd2012 fills its bucket,
    # kdd2010b's mean batch pads 2.8%
    if floor == 4096:
        assert nnz_bucket(16384 * 11, floor) == 180224
        assert nnz_bucket(477757, floor) == 491520
        assert nnz_bucket(262144, floor) == 262144


# -- streams shaped like the corpora ----------------------------------------------
def _write_rows(path, lens, seed, features=100000):
    """libsvm text with ``lens[r]`` one-hot tokens in row r."""
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int64)
    cols = rng.integers(0, features, int(lens.sum()))
    off = np.concatenate([[0], np.cumsum(lens)])
    with open(path, "w") as f:
        for r in range(lens.size):
            toks = " ".join(f"{c}:1" for c in
                            np.sort(cols[off[r]:off[r + 1]]))
            f.write(f"{r % 2} {toks}\n")
    return str(path)


def _open_batcher(kind, path, batch_rows, shards, floor):
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
    return HostBatcher(parser, batch_rows, shards, floor, True,
                       layout="csr")


def _capacities(batcher):
    """(capacity, real entries of the fullest shard, total_nnz, rows) of
    every batch of one epoch."""
    out = []
    while True:
        b = batcher.next_batch()
        if b is None:
            return out
        R = b.rows_per_shard
        real = (b.row < R).sum(axis=1)
        assert b.total_nnz == int(real.sum())
        out.append((b.nnz_bucket, int(real.max()), b.total_nnz,
                    b.total_rows))


@pytest.fixture(scope="module")
def kdd2012_like(tmp_path_factory):
    """Eleven tokens in every row, 6 batches of 512 rows."""
    d = tmp_path_factory.mktemp("kdd2012")
    return _write_rows(d / "a.libsvm", np.full(6 * 512, 11), seed=1)


@pytest.fixture(scope="module")
def kdd2010b_like(tmp_path_factory):
    """24 tokens always and 12 more with p = 0.43 each (29.2 a row), 50
    batches of 1,024 rows."""
    d = tmp_path_factory.mktemp("kdd2010b")
    rng = np.random.default_rng(2)
    lens = 24 + rng.binomial(12, 0.43, 50 * 1024)
    return _write_rows(d / "b.libsvm", lens, seed=3), lens


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_eleven_a_row_fills_the_bucket_exactly(kdd2012_like, kind, shards):
    b = _open_batcher(kind, kdd2012_like, 512, shards, floor=128)
    for _ in range(2):  # one distinct shape an epoch, and the next one too
        caps = _capacities(b)
        assert len(caps) == 6
        n = 512 // shards * 11
        assert {c[:2] for c in caps} == {(n, n)}
        assert all(c[2:] == (512 * 11, 512) for c in caps)
        b.reset()
    # the next power of two would have sent 16 entries for every 11
    assert 1 << (n - 1).bit_length() == n * 16 // 11


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_bernoulli_rows_stay_on_one_rung(kdd2010b_like, kind):
    path, lens = kdd2010b_like
    per_batch = lens.reshape(50, 1024).sum(axis=1)
    caps = _capacities(_open_batcher(kind, path, 1024, 1, floor=4096))
    assert len(caps) == 50
    assert [c[2] for c in caps] == per_batch.tolist()
    shapes = {c[0] for c in caps}
    assert shapes == {30720}, shapes  # 15 sixteenths of 32,768
    # the text batchers size each batch by its own count, the .crec lane
    # by the file's window bound: both land on the rung of the fullest
    assert nnz_bucket(int(per_batch.max()), 4096) == 30720
    assert nnz_bucket(int(per_batch.min()), 4096) == 30720


def test_shard_cache_replays_at_the_text_epochs_capacity(kdd2012_like,
                                                         tmp_path):
    uri = f"{kdd2012_like}#cachefile={tmp_path / 'c'}"

    def epoch():
        b = NativeHostBatcher(uri, fmt="libsvm", batch_rows=512,
                              num_shards=1, min_nnz_bucket=128, layout="csr")
        try:
            return _capacities(b)
        finally:
            b.close()

    def hits():
        return sum(c["value"] for c in native_telemetry_snapshot()["counters"]
                   if c["name"] == "cache_hits_total")

    text = epoch()       # parses the text and writes the shard
    assert any(f.endswith(".manifest") for f in os.listdir(tmp_path / "c"))
    before = hits()
    replay = epoch()     # serves the stored row blocks
    assert hits() > before
    assert replay == text and {c[0] for c in text} == {512 * 11}


# -- padding is inert ---------------------------------------------------------------
def _hand_batch(D, R, per_row, cap, features, seed=4):
    """One packed CSR batch built by hand: ``per_row`` tokens in each of
    the D x R rows, every shard padded to ``cap`` entries the way the
    batchers pad (row = R, slot = 0, val = 0), its columns sent as the
    distinct list and the slots (``col_slots``)."""
    rng = np.random.default_rng(seed)
    n = R * per_row
    big = np.zeros((D, 3, cap), np.int32)
    big[:, 0, :] = R
    big[:, 0, :n] = np.repeat(np.arange(R, dtype=np.int32), per_row)
    big[:, 1, :n] = rng.integers(0, features, (D, n))
    big[:, 2, :n] = rng.uniform(0.5, 1.5, (D, n)).astype(
        np.float32).view(np.int32)
    aux = np.zeros((D, 3, R), np.int32)
    aux[:, 0] = rng.integers(0, 2, (D, R)).astype(np.float32).view(np.int32)
    aux[:, 1] = np.ones((D, R), np.float32).view(np.int32)
    aux[:, 2, 0] = R
    cols, distinct = col_slots(big[:, 1], [n] * D, 128)
    return PaddedBatch(big=big, cols=cols, aux=aux, total_rows=D * R,
                       total_nnz=D * n, total_distinct=distinct)


@pytest.mark.parametrize("model,devices", [("fm", 1), ("fm", 4),
                                           ("linear", 1)])
def test_step_is_the_same_at_either_capacity(model, devices):
    """Row form (one device) and table form (a mesh of four): the entries
    the next power of two adds change neither the loss nor the update."""
    F, R, per_row = 5000, 256, 11
    n = R * per_row
    old, new = 1 << (n - 1).bit_length(), nnz_bucket(n, 128)
    assert (old, new) == (4096, n)
    mesh = data_mesh(devices)
    if model == "fm":
        learner = FMLearner(num_features=F, k=8, mesh=mesh,
                            objective="logistic", learning_rate=0.1,
                            l2=0.0, init_scale=0.1)
    else:
        learner = LinearLearner(num_features=F, mesh=mesh,
                                objective="logistic", learning_rate=0.1)
    out = {}
    for cap in (old, new):
        params = learner.init(5) if model == "fm" else learner.init()
        batch = _hand_batch(devices, R, per_row, cap, F)
        losses = []
        for _ in range(3):
            params, loss = learner.step(params, batch)
            losses.append(float(loss))
        out[cap] = (losses, jax.tree.map(np.asarray, params))
    np.testing.assert_allclose(out[old][0], out[new][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(out[old][1]),
                    jax.tree.leaves(out[new][1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # and the step did move the parameters
    start = learner.init(5) if model == "fm" else learner.init()
    assert any(not np.array_equal(np.asarray(a), b) for a, b in
               zip(jax.tree.leaves(start), jax.tree.leaves(out[new][1])))


# -- the fill counters ----------------------------------------------------------------
@pytest.fixture
def _counters():
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()
    yield lambda name: telemetry.counter(name).value
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()


def _lane(kind, path, batch_rows, floor):
    kw = {"fmt": "libsvm"}
    if kind == "crec":
        crec = path + ".crec"
        if not os.path.exists(crec):
            rows_to_csr_recordio(path, crec, fmt="libsvm",
                                 rows_per_record=300)
        path, kw = crec, {"fmt": "crec"}
    elif kind == "python":
        kw["index64"] = True
    return DeviceRowBlockIter(path, batch_rows=batch_rows, layout="csr",
                              min_nnz_bucket=floor, **kw)


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_fill_counters_read_one_on_eleven_a_row(kdd2012_like, kind,
                                                _counters):
    with _lane(kind, kdd2012_like, 512, 128) as it:
        assert sum(b.total_rows for b in it) == 6 * 512
    assert _counters("device_batches_total") == 6
    assert _counters("device_nnz_sent_total") == 6 * 512 * 11
    assert (_counters("device_nnz_real_total")
            == _counters("device_nnz_sent_total"))


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_fill_counters_read_the_padded_share(tmp_path, kind, _counters):
    """A power of two and one more: 64 rows of 16 tokens and one row of 17
    make 1,025 entries a batch, which the ladder sends as 1,152 (9
    granules of 2,048 / 16) and the next power of two sent as 2,048."""
    lens = np.full(4 * 64, 16)
    lens[::64] = 17
    path = _write_rows(tmp_path / "p.libsvm", lens, seed=6)
    with _lane(kind, path, 64, 16) as it:
        batches = list(it)
    assert [b.nnz_bucket for b in batches] == [1152] * 4
    assert [b.total_nnz for b in batches] == [1025] * 4
    sent = _counters("device_nnz_sent_total")
    real = _counters("device_nnz_real_total")
    assert (sent, real) == (4 * 1152, 4 * 1025)
    assert real / sent == pytest.approx(1025 / 1152)


# -- a part's short last batch (ISSUE 34) ------------------------------------------
def test_tail_rung_native_equals_python_and_lifts_only_a_short_batch():
    rng = np.random.default_rng(34)
    rungs = sorted({nnz_bucket(int(n), 128)
                    for n in np.exp2(rng.uniform(0, 24, 200))}) + [0]
    for own in rungs[:-1]:
        for before in rungs:
            for take, batch_rows in ((0, 1), (1, 512), (511, 512),
                                     (512, 512), (16384, 16384)):
                got = tail_rung(own, before, take, batch_rows)
                assert got == native_tail_rung(own, before, take,
                                               batch_rows)
                if take == batch_rows or before == 0:
                    assert got == own      # full, or first of its epoch
                else:
                    assert got == max(own, before)


@pytest.fixture(scope="module")
def part_like(tmp_path_factory):
    """Four batches of 256 rows of 11 tokens and a fifth of 20 rows: a
    byte-range part's epoch."""
    d = tmp_path_factory.mktemp("part")
    return _write_rows(d / "t.libsvm", np.full(4 * 256 + 20, 11), seed=34)


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_short_last_batch_takes_the_shape_before_it(part_like, kind, shards):
    b = _open_batcher(kind, part_like, 256, shards, floor=128)
    whole = _open_batcher(kind, part_like, 256, shards, floor=128)
    for _ in range(2):  # the rule looks back within an epoch: both alike
        batches = []
        while (x := b.next_batch()) is not None:
            batches.append(x)
        assert [x.total_rows for x in batches] == [256] * 4 + [20]
        assert {x.big.shape for x in batches} == {batches[0].big.shape}
        assert {x.cols.shape for x in batches} == {batches[0].cols.shape}
        assert [x.tail_lifted for x in batches] == [False] * 4 + [True]
        last = batches[-1]
        assert last.total_nnz == 20 * 11
        # the fill is the ladder's own padding: entries on the sacrificial
        # row with slot 0 and value 0, the list's tail beyond any table
        R = last.rows_per_shard
        real = int((last.row < R).sum())
        assert real == 20 * 11
        pad = last.row == R
        assert not last.val[pad].any() and not last.slot[pad].any()
        own = max(1, max(np.unique(np.take_along_axis(
            last.cols, last.slot, 1)[d][~pad[d]]).size
            for d in range(shards)))
        assert nnz_bucket(own, 128) < last.cols.shape[1]
        assert (last.cols[:, own:] == np.iinfo(np.int32).max).all()
        # a full batch keeps its own rung (and its bytes, below)
        for x in batches[:-1]:
            assert x.nnz_bucket == nnz_bucket(R * 11, 128)
        b.reset()
    # full batches are bit for bit what they were without the rule: the
    # rule's only input besides the batch is the rung before it
    first = whole.next_batch()
    again = _open_batcher(kind, part_like, 256, shards, floor=128)
    assert np.array_equal(first.big, again.next_batch().big)


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_a_part_of_under_one_batch_keeps_its_own_rung(tmp_path, kind):
    path = _write_rows(tmp_path / "u.libsvm", np.full(60, 11), seed=35)
    b = _open_batcher(kind, path, 256, 1, floor=128)
    for _ in range(2):
        x = b.next_batch()
        assert x.total_rows == 60 and not x.tail_lifted
        if kind != "crec":  # the .crec lane's capacity is the file's bound
            assert x.nnz_bucket == nnz_bucket(60 * 11, 128)
        assert x.cols.shape[1] == nnz_bucket(x.total_distinct, 128)
        assert b.next_batch() is None
        b.reset()


@pytest.mark.parametrize("kind", ["native", "crec", "python"])
def test_two_epochs_of_a_part_are_one_shape_and_one_build(part_like, kind,
                                                          _counters):
    learner = FMLearner(num_features=100000, k=4, mesh=data_mesh(1),
                        learning_rate=0.1)
    params = learner.init(0)
    builds = telemetry.counter("model_step_builds_total",
                               {"model": "FMLearner"})
    with _lane(kind, part_like, 256, 128) as it:
        for _ in range(2):
            for batch in it:
                params, loss = learner.step(params, batch)
            assert np.isfinite(float(loss))
            it.before_first()
    assert builds.value == 1
    gauges = {g["name"]: g["value"] for g in telemetry.snapshot()["gauges"]}
    assert gauges["device_distinct_shapes"] == 1
    assert _counters("device_tail_batches_total") == 2
    assert _counters("device_batches_total") == 10
    # the lifted entries are inside the fill counters' difference
    sent = _counters("device_nnz_sent_total")
    assert sent == 10 * nnz_bucket(256 * 11, 128)
    assert _counters("device_nnz_real_total") == 2 * (4 * 256 + 20) * 11
