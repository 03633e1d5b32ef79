"""A data set of many objects read as workers read it (ISSUE 34): the
directory URI with a format's arguments and a part, over the S3 lane.

- all parts of ``npart`` in {16, 5} over 24 and 3 small objects, with and
  without a final newline, through ``NativeParser`` on ``s3://``: each part
  is the row sequence the plain statement of the rule gives
  (``benchmarks/reference/split.py``, which never sees the program) and
  together they cover every row once;
- the same route through ``data.Parser.create``, ``NativeBatcher``,
  ``DeviceRowBlockIter`` and ``examples/train.py`` (whose part is the
  launcher's ``DMLC_TASK_ID`` / ``DMLC_NUM_WORKER``);
- the instruments of the split over objects (doc/observability.md):
  ``split_open_us`` one observation an open, ``split_objects_opened_total``,
  ``split_bytes_read_total{scheme=}``, the native span ``split.open``;
- the configuration ``criteo1tb-fm-s3`` pinned to ``criteo1tb-fm``, and
  cell 5's full batches untouched by the rule for a part's short last one.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tests.s3_shared import STATE

from dmlc_core_tpu import data, telemetry
from dmlc_core_tpu.io.native import (NativeBatcher, NativeParser,
                                     native_telemetry_snapshot,
                                     native_trace_snapshot)
from dmlc_core_tpu.tpu import DeviceRowBlockIter
from dmlc_core_tpu.tpu.device_iter import (NativeHostBatcher, col_slots,
                                           nnz_bucket)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
from reference import split as rule  # noqa: E402

BUCKET = "days"


def _put_days(objects, lines, final_newline=True, bucket=BUCKET):
    """``objects`` day objects of ``lines`` libsvm rows each; a row's label
    is its number in the whole set, its tokens vary its length. Returns
    (sizes, line ends) in the listing's order."""
    for key in [k for k in STATE.objects if k[0] == bucket]:
        del STATE.objects[key]
    rng = np.random.default_rng(objects * 1000 + lines)
    sizes, ends = [], []
    for k in range(objects):
        rows = [f"{k * lines + i} " + " ".join(
            f"{c}:1" for c in np.sort(rng.integers(1, 5000,
                                                   rng.integers(1, 9))))
            for i in range(lines)]
        text = ("\n".join(rows) + ("\n" if final_newline else "")).encode()
        STATE.objects[(bucket, f"day_{k:02d}")] = text
        sizes.append(len(text))
        ends.append(rule.line_ends_of_text(np.frombuffer(text, np.uint8)))
    return sizes, ends


def _labels(uri, part, npart, **kw):
    out = []
    with NativeParser(uri, part=part, npart=npart, **kw) as p:
        for b in p:
            out += b.label.astype(np.int64).tolist()
    return out


@pytest.mark.parametrize("final_newline", [True, False],
                         ids=["eol", "noeol"])
@pytest.mark.parametrize("objects,lines", [(24, 37), (3, 211)])
@pytest.mark.parametrize("npart", [16, 5])
def test_every_part_over_s3_is_the_reference_s_row_sequence(
        npart, objects, lines, final_newline):
    sizes, ends = _put_days(objects, lines, final_newline)
    uri = f"s3://{BUCKET}/?format=libsvm"
    seen = []
    for part in range(npart):
        want = rule.row_sequence(rule.part_of(sizes, ends, part, npart))
        got = _labels(uri, part, npart, fmt="auto")
        assert got == (want[:, 0] * lines + want[:, 1]).tolist(), part
        seen += got
    assert seen == list(range(objects * lines))


def test_the_route_of_a_worker_through_every_entry_point(tmp_path):
    """``s3://bucket/?format=criteo&hash_bits=..`` with ``part=1,
    npart=16``: the parser factory, the native batcher, the device iterator
    and the trainer read the same rows."""
    rng = np.random.default_rng(34)
    lines = 90
    sizes, ends = [], []
    for key in [k for k in STATE.objects if k[0] == "criteo"]:
        del STATE.objects[key]
    for k in range(24):
        rows = []
        for i in range(lines):
            ints = [str(v) if rng.random() < 0.7 else ""
                    for v in rng.integers(0, 50, 13)]
            cats = [f"{v:08x}" if rng.random() < 0.8 else ""
                    for v in rng.integers(0, 2 ** 32, 26)]
            rows.append("\t".join([str((k * lines + i) % 2)] + ints + cats))
        text = ("\n".join(rows) + "\n").encode()
        STATE.objects[("criteo", f"day_{k:02d}")] = text
        sizes.append(len(text))
        ends.append(rule.line_ends_of_text(np.frombuffer(text, np.uint8)))
    part = rule.part_of(sizes, ends, 1, 16)
    assert part.first[0] == 1 and part.first[1] > 0   # mid day_01
    uri = "s3://criteo/?format=criteo&hash_bits=12"
    with data.Parser.create(uri, part=1, npart=16) as p:
        assert sum(b.num_rows for b in p) == part.rows
    nb = NativeBatcher(uri, part=1, npart=16, fmt="auto", batch_rows=64,
                       num_shards=1, min_nnz_bucket=128)
    takes = []
    while (meta := nb.next_meta()) is not None:
        takes.append(meta[0])
        big = np.empty((1, 3, meta[1]), np.int32)
        aux = np.empty((1, 3, 64), np.int32)
        nb.fill_packed(big, aux, np.empty(1, np.int32))
    nb.close()
    assert sum(takes) == part.rows and takes[:-1] == [64] * (len(takes) - 1)
    with DeviceRowBlockIter(uri, part=1, npart=16, batch_rows=64,
                            min_nnz_bucket=128) as it:
        rows = [b.total_rows for b in it]
    assert rows == takes
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               DMLC_TASK_ID="1", DMLC_NUM_WORKER="16", DMLC_ROLE="worker")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train.py"), uri,
         "--model", "fm", "--fm-rank", "4", "--num-features", "4096",
         "--batch-rows", "64", "--epochs", "2"],
        env=env, capture_output=True, text=True, timeout=600, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    summary = [json.loads(l[len("summary: "):])
               for l in r.stdout.splitlines() if l.startswith("summary: ")]
    epochs = summary[-1]["epochs"]
    assert [e["rows"] for e in epochs] == [part.rows] * 2, r.stdout
    # the short last batch of the part's epoch is no second shape
    assert [e["new_shapes"] for e in epochs] == [1, 0]


def test_the_split_s_instruments_count_every_open(tmp_path):
    sizes, ends = _put_days(24, 37)
    telemetry.reset()
    telemetry.enable(True)

    def native(kind, name):
        snap = native_telemetry_snapshot()
        return [m for m in snap[kind] if m["name"] == name]
    # part 1 of 16 of 24 equal objects: begins in day_01, ends at the edge
    # of day_02 (or a line into day_03): two or three opens an epoch
    part = rule.part_of(sizes, ends, 1, 16)
    opens = len(part.spans)
    assert opens in (2, 3)
    with NativeParser(f"s3://{BUCKET}/?format=libsvm", part=1, npart=16,
                      fmt="auto") as p:
        for epoch in (1, 2):
            assert sum(b.num_rows for b in p) == part.rows
            assert native("counters", "split_objects_opened_total")[0][
                "value"] == epoch * opens
            hist = native("histograms", "split_open_us")[0]
            assert hist["count"] == epoch * opens and hist["sum"] > 0
            p.before_first()
    read = {c["labels"]["scheme"]: c["value"]
            for c in native("counters", "split_bytes_read_total")}
    # (a reset zeroes a counter another test registered, it does not drop it)
    assert read["s3"] == 2 * (part.end - part.begin)
    assert not read.get("file")
    spans = [s for s in native_trace_snapshot()["spans"]
             if s["name"] == "split.open"]
    assert len(spans) == 2 * opens
    assert sorted({s["arg"] for s in spans}) == [k for k, _, _ in part.spans]
    # a local file is scheme "file", and one open an epoch
    local = tmp_path / "a.libsvm"
    local.write_bytes(STATE.objects[(BUCKET, "day_00")])
    with NativeParser(str(local), fmt="libsvm") as p:
        assert sum(b.num_rows for b in p) == 37
    read = {c["labels"]["scheme"]: c["value"]
            for c in native("counters", "split_bytes_read_total")}
    assert read["file"] == sizes[0]
    assert native("counters", "split_objects_opened_total")[0][
        "value"] == 2 * opens + 1
    telemetry.reset()


# -- the configuration, and cell 5's batches ----------------------------------------

def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_criteo1tb_fm_s3_is_criteo1tb_fm_letter_for_letter():
    one, s3 = _config("criteo1tb-fm"), _config("criteo1tb-fm-s3")
    for key in ("format", "hash_bits", "num_features", "fm_rank",
                "batch_rows", "data", "objective", "learning_rate",
                "init_scale", "l2", "published", "model", "dtype",
                "train_rows", "reference", "reduced"):
        assert s3[key] == one[key], key
    assert s3["limits"] == dict(one["limits"], part_rows_gap=0,
                                window_lacks=0)
    assert len(one["limits"]) == 8
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = {c["name"]: c for c in spec["configs"]}["criteo1tb-fm-s3"]
    assert entry["source"] == s3["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["train_rows"]
    cell = {w["name"]: w for w in spec["workloads"]}["criteo1tb-fm-s3.tsv-s3"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "criteo1tb-fm-s3", "tsv-s3", 1)
    assert len(cell["why"]) <= 200


def test_full_batches_at_cell_5_s_shapes_are_what_they_were(tmp_path):
    """Three full batches of 16,384 click-log rows and a short fourth:
    the full ones keep the rung of their own counts and the oracle's list
    (the rule reads nothing of a full batch), the short one takes theirs."""
    from harness import datagen, datagen_criteo
    cfg = _config("criteo1tb-fm")
    rows = 3 * 16384 + 1000
    path = str(tmp_path / "c.tsv")
    datagen.write_text(
        path, cfg["data"], 34, rows, "criteo", 2,
        lambda block, fmt: datagen_criteo.render_text(cfg["data"], block))
    b = NativeHostBatcher(f"{path}?hash_bits=25", fmt="criteo",
                          batch_rows=16384, num_shards=1)
    got = []
    while (x := b.next_batch()) is not None:
        got.append(x)
    assert [x.total_rows for x in got] == [16384] * 3 + [1000]
    for x in got[:3]:
        assert not x.tail_lifted
        assert x.nnz_bucket == nnz_bucket(x.total_nnz, 4096) == 589824
        col = np.take_along_axis(x.cols, x.slot, 1)
        want_cols, distinct = col_slots(col, [x.total_nnz], 4096)
        assert np.array_equal(x.cols, want_cols)
        assert np.array_equal(x.slot, col) and distinct == x.total_distinct
        assert x.cols.shape[1] == 212992
    last = got[3]
    assert last.tail_lifted
    assert (last.nnz_bucket, last.cols.shape[1]) == (589824, 212992)
    assert nnz_bucket(last.total_nnz, 4096) < 589824
    b.close()
