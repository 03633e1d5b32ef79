"""The cell that reads one worker's part of 24 day objects over S3, rehearsed
at a small size on the CPU: the configuration's pin against ``criteo1tb-fm``,
the part rule's plain statement on cases worked by hand, ``runners/fm_s3.py``
through ``run.run_cell`` with an origin in its own process tree (a traced run
needs a device's planes and is left to the chip), the three planted faults
and the control each coming out not correct by the number that names them,
the runner's refusal of a program without the split's histogram, and no
origin left behind by any of it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_s3.py -q

The scenarios run once, in a child process, from a temporary copy of
``BENCHMARK.json`` + ``benchmarks/`` to which a tiny configuration, a traffic
mix and their entries were added, as ``test_criteo.py`` does.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

from harness import cells, result_line  # noqa: E402
from reference import split as rule  # noqa: E402

CELL = "tiny-s3.tinys3"
REAL = "criteo1tb-fm-s3.tsv-s3"
CONFIG = cells.load_json("configs", "criteo1tb-fm-s3.json")


# -- the configuration and BENCHMARK.json -------------------------------------

def test_the_configuration_is_criteo1tb_fm_but_for_what_the_deployment_owns():
    one = cells.load_json("configs", "criteo1tb-fm.json")
    s3 = CONFIG
    owned = {"runner", "source", "deployment", "guarantees", "limits",
             "assumed"}
    assert set(s3) - set(one) == {"deployment"}
    for key in set(one) - owned:   # data, model, format, hash_bits, ...
        assert s3[key] == one[key], key
    assert s3["runner"] == "fm_s3" and s3["reference"] == "fm"
    # the limits are cell 5's eight, and the part's two
    assert s3["limits"] == dict(one["limits"], part_rows_gap=0,
                                window_lacks=0)
    assert {k: v for k, v in s3["assumed"].items()
            if k in one["assumed"]} == one["assumed"]
    assert set(s3["assumed"]) - set(one["assumed"]) == {
        "objects", "workers", "store", "part_rule"}
    d = s3["deployment"]
    assert (d["objects"], d["workers"], d["rank"]) == (24, 16, 1)
    assert d["store"]["first_byte_ms"] == 100
    # 256 KiB every 3 ms is the stated rate a request
    rate = d["store"]["body_block_bytes"] / d["store"]["body_block_ms"] / 1e3
    assert abs(rate - d["store"]["request_mb_per_s"]) < 1
    assert set(s3["guarantees"]) == {
        "the_part_exactly", "every_present_cell", "ids_by_the_rule",
        "one_shape", "bytes_as_stored"}
    assert s3["reduced"] == ["train_rows"] and len(s3["source"]) <= 200


def test_benchmark_json_lists_the_cell_and_its_three_metrics():
    spec = cells.load_spec()
    cell = cells.load_cell(spec, REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "tsv-s3"
    assert cell["traffic_file"]["store"] == "s3"
    assert cell["traffic_file"]["cache"] == "never"
    entry = [c for c in spec["configs"] if c["name"] == "criteo1tb-fm-s3"][0]
    assert entry["source"] == CONFIG["source"]
    want = result_line.expected_metrics(spec, REAL, True)
    five = result_line.expected_metrics(spec, "criteo1tb-fm.tsv", True)
    new = {"split.open_us", "s3.first_byte_us", "s3.range_wait_share"}
    assert set(want) == set(five) | new
    for name in new:   # the parent has nothing to read in any other cell
        assert want[name]["workloads"] == [REAL]
        assert want[name]["layer"] == "read + split"
        how = cells.load_json("metrics", name + ".json")
        assert how["reader"] == "hist_ratio"


# -- the part rule's plain statement -------------------------------------------

def _objects(lines_per_object, final_newline=True):
    """Objects of lines ``<object>.<line>`` of growing length."""
    texts = []
    for k, n in enumerate(lines_per_object):
        body = "\n".join(f"{k}.{i}" + "x" * (i % 7) for i in range(n))
        texts.append((body + ("\n" if final_newline else "")).encode())
    return texts


@pytest.mark.parametrize("final_newline", [True, False])
@pytest.mark.parametrize("npart", [1, 2, 5, 16])
def test_parts_cover_every_line_once_and_in_order(npart, final_newline):
    texts = _objects([9, 1, 14, 3], final_newline)
    sizes = [len(t) for t in texts]
    ends = [rule.line_ends_of_text(np.frombuffer(t, np.uint8))
            for t in texts]
    seen = []
    for part in range(npart):
        p = rule.part_of(sizes, ends, part, npart)
        seq = rule.row_sequence(p)
        assert len(seq) == p.rows
        if p.rows:
            assert tuple(seq[0]) == p.first and tuple(seq[-1]) == p.last
        seen += [tuple(r) for r in seq]
    assert seen == [(k, i) for k, n in enumerate([9, 1, 14, 3])
                    for i in range(n)]


def test_an_edge_inside_a_line_and_on_a_lines_first_byte_both_move_on():
    # one object of four lines of 4 bytes: "aaa\n" x 4
    ends = [rule.line_ends(np.full(4, 4))]
    # two parts: the raw edge at byte 8 is line 2's first byte and moves
    # past that whole line, so part 0 holds three lines
    assert rule.part_of([16], ends, 0, 2).spans == [(0, 0, 3)]
    assert rule.part_of([16], ends, 1, 2).spans == [(0, 3, 4)]
    # an edge on an object's first byte stays: two such objects, two parts
    both = rule.part_of([16, 16], ends * 2, 1, 2)
    assert both.spans == [(1, 0, 4)] and both.begin == 16
    # three parts of 32 bytes, step 11: edges 11 and 22 lie inside lines
    parts = [rule.part_of([16, 16], ends * 2, k, 3).spans for k in range(3)]
    assert parts == [[(0, 0, 3)], [(0, 3, 4), (1, 0, 2)], [(1, 2, 4)]]


def test_rank_1_of_16_over_24_equal_objects_is_a_half_and_a_whole():
    texts = _objects([400])
    size, ends = len(texts[0]), rule.line_ends_of_text(
        np.frombuffer(texts[0], np.uint8))
    p = rule.part_of([size] * 24, [ends] * 24, 1, 16)
    assert p.first[0] == 1 and 0 < p.first[1] < 400
    assert p.spans[1][:2] == (2, 0) and p.spans[1][2] == 400
    # an odd size puts the raw end one byte into day_03: one line of it
    assert len(p.spans) == 2 + (size % 2)
    assert abs(p.rows - 600) <= 12   # lines grow, so bytes lead rows


# -- the cell end to end, its faults, its control ------------------------------

SCENARIOS = r"""
import json, os, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import run
from faults_s3 import FAULTS
from harness import cells, check
from runners import fm_s3
out = {}
CELL = "tiny-s3.tinys3"
SEED = 2**31 + 34
pids = []
spawn = fm_s3.loadrig.spawn_origin
def spawned(*a, **kw):
    o = spawn(*a, **kw)
    pids.extend([o.proc.pid] + o.pids)
    return o
fm_s3.loadrig.spawn_origin = spawned
out["sound"] = run.run_cell(CELL, SEED, 1.5, False, require_chip=False)
for name, fault in FAULTS.items():
    out[name] = run.run_cell(CELL, SEED, 1.0, False, require_chip=False,
                             faults=fault)
spec = cells.load_spec()
cell = cells.load_cell(spec, CELL)
s = fm_s3.Session(cell, SEED, 1)
s.write_data(2)
ref = s.reference_readings()
out["control"] = check.judge(check.gaps(s.reference_readings("bfloat16"), ref),
                             cell["config_file"]["limits"])
s.close()
def alive(pid):
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    with open(f"/proc/{pid}/stat") as f:
        return f.read().rsplit(")", 1)[1].split()[0] != "Z"
import time
deadline = time.time() + 10
while any(alive(p) for p in pids) and time.time() < deadline:
    time.sleep(0.1)
out["origins_left"] = [p for p in pids if alive(p)]
out["origins_started"] = len(pids)
print("SCENARIOS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy_s3"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(hash_bits=12, num_features=4096, fm_rank=4, batch_rows=64)
    for f in cfg["data"]["fields"]:
        f["cardinality"] = min(f["cardinality"], 300)
    cfg["deployment"]["store"].update(first_byte_ms=5, origin_workers=1)
    with open(os.path.join(dst, "benchmarks/configs/tiny-s3.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = dict(cells.load_json("traffic", "tsv-s3.json"),
                   epoch_batches=8, trace_window_s=0.3)
    with open(os.path.join(dst, "benchmarks/traffic/tinys3.json"), "w") as f:
        json.dump(traffic, f)
    spec = cells.load_spec()
    spec["configs"].append({"name": "tiny-s3", "source": "a test",
                            "file": "benchmarks/configs/tiny-s3.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-s3",
                              "traffic": "tinys3", "chips": 1,
                              "why": "a test"})
    for m in spec["per_layer"]:
        if REAL in m["workloads"]:
            m["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", SCENARIOS], cwd=dst, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    last = [l for l in r.stdout.splitlines() if l.startswith("SCENARIOS ")]
    return json.loads(last[-1][len("SCENARIOS "):])


def test_the_cell_is_correct_and_every_exact_check_reads_zero(scenarios):
    line = scenarios["sound"]
    assert line["correct"] is True, line["checks"]
    c = line["checks"]
    for name in ("part_rows_gap", "epoch_rows_gap", "epoch_nnz_gap",
                 "window_lacks", "compiles_in_window",
                 "new_shapes_in_window", "failed_batches"):
        assert c[name] == {"value": 0, "limit": 0}, name
    assert c["loss_gap"]["value"] < 1e-6
    assert c["grad_norm_gap"]["value"] < 1e-5
    assert c["change_norm_gap"]["value"] < 1e-5
    part = line["notes"]["setup"]["part"]
    assert part["first"][0] == 1 and part["first"][1] > 0   # mid day_01
    assert abs(part["rows"] - 768) <= 5
    assert line["notes"]["epochs_finished"] >= 2
    if part["short_last_batch_rows"]:
        assert c["short_batches_in_window"]["value"] >= 1
    assert c["crossings_in_window"]["value"] >= 1
    assert line["notes"]["setup"]["origin_start_s"] > 0


@pytest.mark.parametrize("fault,by", [
    ("part_begins_a_line_late", ("part_rows_gap", "epoch_nnz_gap")),
    ("object_left_out", ("part_rows_gap", "loss_gap", "grad_norm_gap",
                         "change_norm_gap")),
    ("short_batch_dropped", ("part_rows_gap", "window_lacks"))])
def test_a_fault_of_the_part_is_not_correct(scenarios, fault, by):
    line = scenarios[fault]
    part = scenarios["sound"]["notes"]["setup"]["part"]
    if fault == "short_batch_dropped" and not part["short_last_batch_rows"]:
        pytest.skip("this seed's part holds whole batches")
    assert line["correct"] is False
    c = line["checks"]
    for name in by:
        assert c[name]["value"] > c[name]["limit"], (name, c[name])
    if fault == "part_begins_a_line_late":
        assert c["part_rows_gap"]["value"] == 1
    if fault == "short_batch_dropped":
        assert c["part_rows_gap"]["value"] == part["short_last_batch_rows"]
        for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                     "epoch_nnz_gap"):   # only the count sees it
            assert c[name]["value"] <= c[name]["limit"], name


def test_the_control_in_bfloat16_is_not_correct(scenarios):
    assert scenarios["control"]["ok"] is False


def test_no_origin_outlives_its_run(scenarios):
    assert scenarios["origins_started"] >= 5 * 2
    assert scenarios["origins_left"] == []


def test_a_program_without_the_splits_histogram_is_refused_at_once(
        monkeypatch, tmp_path):
    from dmlc_core_tpu import telemetry
    from runners import fm_s3
    spec = cells.load_spec()
    cell = cells.load_cell(spec, REAL)
    help_ = dict(telemetry.METRIC_HELP)
    help_.pop("split_open_us")
    monkeypatch.setattr(telemetry, "METRIC_HELP", help_)
    started = []
    monkeypatch.setattr(fm_s3.loadrig, "spawn_origin",
                        lambda *a, **kw: started.append(a))
    with pytest.raises(RuntimeError, match="split_open_us"):
        fm_s3.Session(cell, 1, 1)
    assert not started
