"""Tests of what PR 39 adds to the benchmark for the cell on range-sharded
tables, ``criteo1tb-fm-ps4.tsv``: the configuration against the one it
deploys, the owner rule, the work count of one worker-and-server by hand, the
readers on a sample cut from a chip trace of that PR and on two snapshots, and
the runner rehearsed at a small size on four host devices (a traced run needs
a device's planes and is left to the chip): correct, every exact number 0,
and a batch laid out for the wrong ranges seen by ``rows_lost``; and
``owner_gap`` on table leaves of each layout a range may take, placed right
and placed wrong, on four host devices.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_ps.py -q

``recorded_ps4.json`` (``trace_cut_dp.py`` of the cell's traced run, my chip
run, PR 39): three whole steps from the middle of the traced window on all
four chips, cut as ``recorded_dp4.json`` was. The step program takes 57.19,
57.71 and 57.16 ms on the first chip: ``dp.apply`` 22.97, ``dp.loss_grad``
19.48, ``dp.pull`` 7.30, the untagged ``%copy`` of the range 6.92, ``dp.push``
0.32 ms a step.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cells, links, result_line, trace, work, work_ps  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402
from readers import (_xplane, counter_ratio, module_roofline_ps,  # noqa: E402
                     scope_time)
from reference import owners  # noqa: E402

CELL = "criteo1tb-fm-ps4.tsv"
KIND = "TPU v5 lite"
# the cell's step: 65,536 rows x 33.6 hashed cells; aux [4,3,16384], big
# [4,3,589824] and cols [4,212992] int32
NNZ, ROWS, RANK, CHIPS = 2203400, 65536, 16, 4
BATCH_BYTES = 4 * (4 * 3 * 16384 + 4 * 3 * 589824 + 4 * 212992)


def how(name):
    return cells.load_json("metrics", name + ".json")


# -- the configuration -----------------------------------------------------------

def test_ps4_configuration_is_criteo1tb_fm_but_for_what_the_deployment_owns():
    one = cells.load_json("configs", "criteo1tb-fm.json")
    ps4 = cells.load_json("configs", "criteo1tb-fm-ps4.json")
    owned = {"runner", "source", "hash_bits", "num_features", "deployment",
             "guarantees", "limits", "assumed"}
    assert set(ps4) - set(one) == {"deployment"}
    for key in set(one) - owned:
        assert ps4[key] == one[key], key
    assert ps4["runner"] == "fm_ps" and ps4["reference"] == "fm"
    assert ps4["hash_bits"] == 27 and ps4["num_features"] == 1 << 27
    assert ps4["source"].startswith(one["source"])
    # the limits are the one-chip cell's, and three exact checks more
    assert ps4["limits"] == dict(one["limits"], replica_gap=0, owner_gap=0,
                                 rows_lost=0)
    # every guarantee of the logs stands (but for the ids' width), four more
    for name, text in one["guarantees"].items():
        assert ps4["guarantees"][name] == text.replace(", 25)", ", 27)")
    assert set(ps4["guarantees"]) - set(one["guarantees"]) == {
        "synchronous", "one_owner_a_row", "no_row_lost", "b_identical"}
    # everything criteo1tb-fm assumes, but for what the size changes
    resized = {"hash_bits", "train_rows", "batch_rows", "batch_shape"}
    for name, text in one["assumed"].items():
        if name not in resized:
            assert ps4["assumed"][name] == text, name
    assert set(ps4["assumed"]) - set(one["assumed"]) == {
        "servers", "ranges", "nodes", "driver"}
    d = ps4["deployment"]
    assert d["workers"] == d["servers"] == 4 and d["chips_per_worker"] == 1
    assert d["table_layout"] == "range_sharded"
    assert d["global_batch_rows"] == 4 * ps4["batch_rows"] == 65536
    assert ps4["reduced"] == ["train_rows"]


def test_benchmark_json_lists_the_cell_and_its_metrics():
    spec = cells.load_spec()
    cell = cells.load_cell(spec, CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "tsv"
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert four == ["kdd2012-fm-dp4.libfm", CELL]
    assert len(spec["workloads"]) // 4 >= len(four)   # the quarter allowed
    want = result_line.expected_metrics(spec, CELL, True)
    assert set(want) == {
        "parse.us_per_krow", "parse.ns_per_cell", "stage.us_per_batch",
        "put.us_per_batch", "input_wait_share", "step_ms_p95",
        "fm_step.mfu_hbm", "device_idle_share", "fm_step.apply_ms",
        "fm_step.expand_ms", "fm_step_roofline.ps", "ps.pull_ms",
        "ps.push_ms", "ps.owner_imbalance", "ps.stretch_fill"}
    for name in ("fm_step_roofline.ps", "ps.pull_ms", "ps.push_ms",
                 "ps.owner_imbalance", "ps.stretch_fill"):
        assert want[name]["workloads"] == [CELL]
    # the replicated exchange's metrics describe another step
    assert "dp.allreduce_ms" not in want
    assert "fm_step_roofline.dp" not in want


# -- the owner rule ----------------------------------------------------------------

def test_a_sorted_key_list_is_cut_where_the_ranges_end():
    keys = np.array([0, 3, 7, 8, 15, 16, 30])
    cut = owners.slice_by_ranges(keys, 32, 4)
    assert [s.tolist() for s in cut] == [[0, 3, 7], [8, 15], [16], [30]]
    assert owners.owner_of(keys, 32, 4).tolist() == [0, 0, 0, 1, 1, 2, 3]
    assert owners.stretch_counts([[30, 3, 3, 8], []], 32, 4).tolist() == \
        [[1, 1, 0, 1], [0, 0, 0, 0]]
    assert owners.owner_major(keys, 32, 4, 3).tolist() == [
        0, 3, 7, 8, 15, owners.PAD, 16, owners.PAD, owners.PAD,
        30, owners.PAD, owners.PAD]
    with pytest.raises(ValueError, match="does not fit"):
        owners.owner_major(keys, 32, 4, 2)      # never a dropped key
    with pytest.raises(ValueError, match="sorted"):
        owners.slice_by_ranges([3, 3], 32, 4)
    with pytest.raises(ValueError, match="in the table"):
        owners.slice_by_ranges([3, 32], 32, 4)
    with pytest.raises(ValueError, match="divide"):
        owners.owner_rows(30, 4)


# -- what one worker-and-server needs ------------------------------------------------

def test_owner_work_by_hand_on_a_three_row_batch():
    """Three rows of 2, 1 and 3 entries at rank 2 on two chips, a batch of
    96 bytes: the six entries' rows of 3 floats are read and written once,
    144 bytes, half of them in this chip's range; it reads half the batch;
    of its own shard's three entries' rows, 36 bytes, half are another
    chip's, pulled and pushed."""
    need = work_ps.fm_sgd_step_owner(nnz=6, rows=3, rank=2, batch_bytes=96,
                                     chips=2)
    assert need["bytes"] == (2 * 6 * 3 * 4 + 96) / 2 == 120
    assert need["link_bytes"] == 2 * 0.5 * (3 * 3 * 4) == 36
    assert need["flops"] == (8 * 6 * 2 + 4 * 6 + 12 * 3) / 2
    # one chip owns everything: the one-chip count, and no link
    alone = work_ps.fm_sgd_step_owner(6, 3, 2, 96, 1)
    assert alone["link_bytes"] == 0
    assert alone["bytes"] == work.fm_sgd_step(6, 3, 2, 96)["bytes"]
    assert "num_features" not in \
        work_ps.fm_sgd_step_owner.__code__.co_varnames


def test_owner_work_at_the_cells_numbers():
    need = work_ps.fm_sgd_step_owner(NNZ, ROWS, RANK, BATCH_BYTES, CHIPS)
    assert need["bytes"] == pytest.approx((299.7e6 + 32.5e6) / 4, rel=2e-3)
    assert need["link_bytes"] == pytest.approx(56.2e6, rel=2e-3)
    least = work_ps.least_seconds(need, peaks_for(KIND),
                                  links.links_for(KIND))
    assert least["by"]["bytes"] == pytest.approx(101.4e-6, rel=2e-3)
    assert least["by"]["link_bytes"] == pytest.approx(280.9e-6, rel=2e-3)
    assert least["bound"] == "link_bytes"


# -- the readers ---------------------------------------------------------------------

def snap(owner_max, sent, real):
    return {"counters": [
        {"name": "device_cols_owner_max_total", "value": owner_max},
        {"name": "device_stretch_sent_total", "value": sent},
        {"name": "device_stretch_real_total", "value": real},
        {"name": "device_cols_distinct_total", "value": 7 * real}]}


def test_counter_ratio_reads_the_imbalance_and_the_fill():
    ctx = {"telemetry": (snap(1000, 5000, 3000), snap(1550, 13000, 5000)),
           "device": {"count": 4}}
    # 550 columns to the fullest owner of 2,000 real ones: the mean is 500
    assert counter_ratio.read(ctx, how("ps.owner_imbalance")) == \
        pytest.approx(10.0)
    # 2,000 real of 8,000 positions sent
    assert counter_ratio.read(ctx, how("ps.stretch_fill")) == \
        pytest.approx(25.0)
    # the parent's program has no such counters: nothing, and no error
    bare = {"counters": [{"name": "device_cols_distinct_total", "value": 9}]}
    for name in ("ps.owner_imbalance", "ps.stretch_fill"):
        assert counter_ratio.read({"telemetry": (bare, bare),
                                   "device": {"count": 4}}, how(name)) is None


@pytest.fixture(scope="module")
def recorded():
    return cells.load_json("tests", "recorded_ps4.json")


def ctx_of(recorded):
    steps = 3
    return {"traced": types.SimpleNamespace(steps=steps, nnz=steps * NNZ,
                                            rows=steps * ROWS),
            "session": types.SimpleNamespace(cfg={"fm_rank": RANK},
                                             bytes_per_batch=BATCH_BYTES),
            "events": recorded, "peaks": peaks_for(KIND),
            "device": {"count": CHIPS, "kind": KIND}}


def test_recorded_sample_holds_four_chips_and_three_whole_steps(recorded):
    planes = trace.device_planes(recorded)
    assert planes == [f"/device:TPU:{i}" for i in range(4)]
    lo, hi = trace.window_of(recorded)
    for p in planes:
        whole = [m for m in recorded["planes"][p][trace.MODULES_LINE]
                 if "sharded_step" in m[0] and m[1] >= lo
                 and m[1] + m[2] <= hi]
        assert len(whole) == 3, p


def test_module_roofline_ps_on_the_recorded_sample(recorded):
    got = module_roofline_ps.read(ctx_of(recorded), how("fm_step_roofline.ps"))
    lo, hi = trace.window_of(recorded)
    mods = [m for m in recorded["planes"]["/device:TPU:0"][trace.MODULES_LINE]
            if "sharded_step" in m[0] and m[1] >= lo and m[1] + m[2] <= hi]
    a_step = sum(m[2] for m in mods) / len(mods) / 1e9
    # the links bound it: 56.2 MB at 200 GB/s
    assert got == pytest.approx(100.0 * 280.9e-6 / a_step, rel=2e-3)
    assert 0 < got < 100
    assert module_roofline_ps.read(
        ctx_of(recorded), dict(how("fm_step_roofline.ps"),
                               module="no_such_module")) is None


def test_pull_and_push_time_on_the_recorded_sample(recorded):
    scopes = recorded["scopes"]
    mods, ops = _xplane.step_ops(scopes, "sharded_step")
    assert len(mods) == 3
    for name, scope in (("ps.pull_ms", "dp.pull"), ("ps.push_ms", "dp.push")):
        mine = [op for op in ops if scope in op[3]]
        assert mine, scope
        assert scope_time.reduce(scopes, how(name)) == pytest.approx(
            sum(op[2] for op in mine) / 3 / 1e6)
    # the pull holds the table gathers and three all-to-alls, the push two
    # and the scalars' all-reduce
    kinds = {scope: {op[0].lstrip("%").split(".")[0] for op in ops
                     if scope in op[3]} for scope in ("dp.pull", "dp.push")}
    assert "all_to_all" in kinds["dp.pull"] and "all_to_all" in kinds["dp.push"]
    assert "all-reduce" in kinds["dp.push"]
    # hand-checked from the file's own numbers: the two table gathers 4.30
    # and 2.76 ms and three all-to-alls of 0.03, 0.04 and 0.16 ms; the push
    # two all-to-alls of 0.01 and 0.15 ms, a relayout of 0.15 and the sum
    assert scope_time.reduce(scopes, how("ps.pull_ms")) == pytest.approx(
        7.30, abs=0.02)
    assert scope_time.reduce(scopes, how("ps.push_ms")) == pytest.approx(
        0.316, abs=0.005)
    # a trace of the replicated step has neither scope: nothing, no error
    old = cells.load_json("tests", "recorded_dp4.json")["scopes"]
    assert scope_time.reduce(old, how("ps.pull_ms")) is None
    assert scope_time.reduce(old, how("ps.push_ms")) is None


# -- the runner, on four host devices --------------------------------------------------

SCENARIOS = r"""
import json, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import run
out = {}
CELL = "tiny-ps4.tinytsv"
out["sound"] = run.run_cell(CELL, 2**31 + 80, 0.5, False, require_chip=False)


def wrong_ranges(s):
    # the iterator lays the lists out for ranges half as wide as the
    # learner's: columns travel to chips that do not own them
    import numpy as np
    from dmlc_core_tpu.tpu import DeviceRowBlockIter
    s.it.close()
    owners, rows = s.learner.col_owners
    s.it = DeviceRowBlockIter(s.uri, mesh=s.learner.mesh,
                              batch_rows=s.batch_rows, fmt=s.fmt,
                              col_owners=(owners, rows * 2))
    s._stream = iter(s.it)


out["wrong_ranges"] = run.run_cell(CELL, 2**31 + 80, 0.3, False,
                                   require_chip=False,
                                   faults={"after_build": wrong_ranges})
print("SCENARIOS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy_ps"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = cells.load_json("configs", "criteo1tb-fm-ps4.json")
    cfg.update(hash_bits=12, num_features=4096, fm_rank=4, batch_rows=64)
    for f in cfg["data"]["fields"]:
        f["cardinality"] = min(f["cardinality"], 300)
    with open(os.path.join(dst, "benchmarks/configs/tiny-ps4.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = dict(cells.load_json("traffic", "tsv.json"), epoch_batches=8)
    with open(os.path.join(dst, "benchmarks/traffic/tinytsv.json"),
              "w") as f:
        json.dump(traffic, f)
    spec = cells.load_spec()
    spec["configs"].append({"name": "tiny-ps4", "source": "a test",
                            "file": "benchmarks/configs/tiny-ps4.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "tiny-ps4.tinytsv",
                              "config": "tiny-ps4", "traffic": "tinytsv",
                              "chips": 4, "why": "a test"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCENARIOS], cwd=dst, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    last = [l for l in r.stdout.splitlines() if l.startswith("SCENARIOS ")]
    return json.loads(last[-1][len("SCENARIOS "):])


def test_the_cell_is_correct_and_every_exact_check_reads_zero(scenarios):
    line = scenarios["sound"]
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    c = line["checks"]
    for name in ("epoch_rows_gap", "epoch_nnz_gap", "replica_gap",
                 "owner_gap", "rows_lost", "compiles_in_window",
                 "new_shapes_in_window", "failed_batches"):
        assert c[name] == {"value": 0, "limit": 0}, name
    assert c["loss_gap"]["value"] < 1e-6
    assert c["grad_norm_gap"]["value"] < 1e-5
    assert c["change_norm_gap"]["value"] < 1e-5
    assert line["notes"]["epochs_finished"] >= 2


def test_lists_laid_out_for_other_ranges_are_not_correct(scenarios):
    """Columns sent to chips that do not own them read zeros and their
    updates are dropped: the comparison with the reference fails, and the
    owners received fewer real columns than the shards' distinct counts."""
    line = scenarios["wrong_ranges"]
    assert line["correct"] is False
    c = line["checks"]
    assert c["rows_lost"]["value"] > 0
    assert c["grad_norm_gap"]["value"] > c["grad_norm_gap"]["limit"]
    assert c["owner_gap"]["value"] == 0 and c["replica_gap"]["value"] == 0


# -- owner_gap on the layouts a range may take, on four host devices ----------------

LAYOUTS = r"""
import json, sys
sys.path.insert(0, "benchmarks")
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from runners.fm_ps import owner_gap
F, K, OWNERS = 4096, 16, 4
devs = np.array(jax.devices()[:4])
mesh = Mesh(devs, ("data",))
place = {d: o for o, d in enumerate(mesh.devices.flat)}


def put(shape, spec, on=mesh):
    x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    return jax.device_put(x, NamedSharding(on, spec))


def gap(*leaves):
    return owner_gap(list(leaves), place, OWNERS, F, K, [0] * 4)


# w, then v as [F, K] (one feature a row) or lane-dense (128 / K = 8 a row)
flat, plain, lanes = (F,), (F, K), (F * K // 128, 128)
swapped = Mesh(devs[[1, 0, 2, 3]], ("data",))
out = {"flat": gap(put(flat, P("data"))),
       "plain": gap(put(flat, P("data")), put(plain, P("data"))),
       "lanes": gap(put(flat, P("data")), put(lanes, P("data"))),
       "replicated": gap(put(plain, P())),
       "columns": gap(put(plain, P(None, "data"))),
       "misaligned": gap(put((1200, 128), P("data"))),
       "factor_major": gap(put((K, F), P("data"))),
       "part": gap(put((F // 2, K), P("data"))),
       "wide_rows": gap(put((F * K // 256, 256), P("data"))),
       "split_rows": gap(put((2 * F, K // 2), P("data"))),
       "short": gap(put(plain, P("data"), Mesh(devs[:2], ("data",))))}
for name, shape in (("flat", flat), ("plain", plain), ("lanes", lanes)):
    out["swapped_" + name] = gap(put(shape, P("data"), swapped))
w, v = put(flat, P("data")), put(lanes, P("data"))
whole = w.nbytes + v.nbytes
out["peaks"] = owner_gap([w, v], place, OWNERS, F, K,
                         [whole, whole - 1, 0, 2 * whole])
print("LAYOUTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def layouts():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", LAYOUTS], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    last = [l for l in r.stdout.splitlines() if l.startswith("LAYOUTS ")]
    return json.loads(last[-1][len("LAYOUTS "):])


@pytest.mark.parametrize("layout", ["flat", "plain", "lanes"])
def test_owner_gap_reads_zero_on_every_layout_of_the_ranges(layouts, layout):
    """``[F]``, the parent's ``[F, K]`` and a lane-dense ``[F/8, 128]`` range
    each hold the features ``[o*F/4, (o+1)*F/4)`` on chip o."""
    assert layouts[layout] == 0


@pytest.mark.parametrize("layout,want", [
    # chips 0 and 1 hold each other's range: two shards wrong a leaf
    ("swapped_flat", 2), ("swapped_plain", 2), ("swapped_lanes", 2),
    # every chip holds the whole table
    ("replicated", 4),
    # every chip holds every feature's row, a quarter of its columns
    ("columns", 4),
    # 4,096 features do not fall into 1,200 rows a whole number a row
    ("misaligned", 1),
    # [K, F] cut on its first axis: each chip holds 4 factors of every
    # feature, a quarter of the bytes in four whole blocks of rows
    ("factor_major", 1),
    # [F/2, K]: a table of half the features, ids folded
    ("part", 1),
    # 16 features a row of 256: a row wider than one lane-dense row
    ("wide_rows", 1),
    # [2F, K/2]: each feature split over two rows
    ("split_rows", 1),
    # two shards of half the table: two shards missing, both too wide
    ("short", 4),
    # two chips whose peak reached the bytes of w and v together
    ("peaks", 2)])
def test_owner_gap_counts_every_leaf_placed_wrong(layouts, layout, want):
    assert layouts[layout] == want


def test_owner_gap_counts_a_range_cut_by_columns_too():
    """A shard with its chip's rows but half of the columns is not the
    chip's range: the clause on the other axes alone sees it."""
    sys.path.insert(0, REPO)
    from runners.fm_ps import owner_gap
    F, K = 64, 16

    def leaf(cols):
        return types.SimpleNamespace(shape=(F, K), nbytes=F * K * 4,
                                     addressable_shards=[
            types.SimpleNamespace(
                device=o, index=(slice(o * 16, (o + 1) * 16), cols),
                data=types.SimpleNamespace(shape=(16, K)))
            for o in range(4)])
    place = {o: o for o in range(4)}
    assert owner_gap([leaf(slice(None))], place, 4, F, K, []) == 0
    assert owner_gap([leaf(slice(0, K))], place, 4, F, K, []) == 0
    assert owner_gap([leaf(slice(0, K // 2))], place, 4, F, K, []) == 4
