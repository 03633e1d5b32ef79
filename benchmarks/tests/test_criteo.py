"""The click-log cell rehearsed at a small size on the CPU: the generator's
lines against the plain statement of the format, ``runners/fm_criteo.py``
through ``run.run_cell`` (a traced run needs a device's planes and is left
to the chip), the reader of ``parse.ns_per_cell`` on two snapshots, the
control in bfloat16 and the three planted faults of the lane each coming out
not correct.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_criteo.py -q

The scenarios run once, in a child process, from a temporary copy of
``BENCHMARK.json`` + ``benchmarks/`` to which a tiny configuration, a traffic
mix and their entries were added, as ``test_dp.py`` does.
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

from harness import cells, datagen, datagen_criteo  # noqa: E402
from reference import criteo as rule  # noqa: E402

CELL = "tiny-criteo.tinytsv"
CONFIG = cells.load_json("configs", "criteo1tb-fm.json")


def test_a_rendered_line_reads_back_as_the_generators_cells():
    """Line by line through the reference's one-cell statement: labels,
    counts and ids equal what ``cell_ids`` makes of the cells in memory."""
    data = CONFIG["data"]
    block = datagen.make_block(data, 2 ** 31 + 5, 3, 500)
    c = datagen_criteo.cells(data, block)
    want = rule.cell_ids(c.column, c.text, c.lens, 25)
    text = datagen_criteo.render_text(data, block)
    lines = text.split(b"\n")
    assert lines.pop() == b"" and len(lines) == block.rows
    got, lens = [], []
    for line, label in zip(lines, block.label):
        assert line.count(b"\t") == 39
        y, ids = rule.line_ids(line, 25)
        assert y == label
        got += ids
        lens.append(len(ids))
    assert lens == block.lens.tolist()
    assert got == want.tolist()


def test_cells_print_as_the_logs_do():
    data = CONFIG["data"]
    block = datagen.make_block(data, 7, 0, 2000)
    c = datagen_criteo.cells(data, block)
    is_int = c.column < datagen_criteo.INT_COLUMNS
    assert set(c.lens[~is_int]) == {8}
    assert c.lens[is_int].min() >= 1 and c.lens[is_int].max() <= 6
    chars = set(bytes(c.text[~is_int].ravel()))
    assert chars <= set(b"0123456789abcdef")
    present = np.bincount(c.column, minlength=39) / block.rows
    want = np.empty(39)
    for f in data["fields"]:
        want[f["column"]] = f["present"]
    assert np.abs(present - want).max() < 0.04


SCENARIOS = r"""
import json, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import run
from faults_criteo import FAULTS
from harness import cells, check, result_line
out = {}
CELL = "tiny-criteo.tinytsv"
out["sound"] = run.run_cell(CELL, 2**31 + 79, 0.5, False, require_chip=False)
for name, fault in FAULTS.items():
    out[name] = run.run_cell(CELL, 2**31 + 79, 0.3, False,
                             require_chip=False, faults=fault)
spec = cells.load_spec()
cell = cells.load_cell(spec, CELL)
s = cells.load_module("runners", "fm_criteo").Session(cell, 5, 1)
ref = s.reference_readings()
out["control"] = check.judge(check.gaps(s.reference_readings("bfloat16"), ref),
                             cell["config_file"]["limits"])
print("SCENARIOS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy_criteo"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(hash_bits=12, num_features=4096, fm_rank=4, batch_rows=64)
    for f in cfg["data"]["fields"]:
        f["cardinality"] = min(f["cardinality"], 300)
    with open(os.path.join(dst, "benchmarks/configs/tiny-criteo.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = dict(cells.load_json("traffic", "tsv.json"), epoch_batches=8,
                   trace_window_s=0.3)
    with open(os.path.join(dst, "benchmarks/traffic/tinytsv.json"),
              "w") as f:
        json.dump(traffic, f)
    spec = cells.load_spec()
    spec["configs"].append({"name": "tiny-criteo", "source": "a test",
                            "file": "benchmarks/configs/tiny-criteo.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-criteo",
                              "traffic": "tinytsv", "chips": 1,
                              "why": "a test"})
    for m in spec["per_layer"]:
        if "criteo1tb-fm.tsv" in m["workloads"]:
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
    for name in ("epoch_rows_gap", "epoch_nnz_gap", "compiles_in_window",
                 "new_shapes_in_window", "failed_batches"):
        assert c[name] == {"value": 0, "limit": 0}, name
    assert c["loss_gap"]["value"] < 1e-6
    assert c["grad_norm_gap"]["value"] < 1e-5
    assert c["change_norm_gap"]["value"] < 1e-5
    assert line["notes"]["epochs_finished"] >= 2


def test_ns_per_cell_is_the_histograms_rise_over_the_present_cells():
    from readers import hist_per_counter
    how = cells.load_json("metrics", "parse.ns_per_cell.json")

    def snap(cells_, missing, scan_us, parse_us):
        return {"counters": [
            {"name": "parse_cells_total", "labels": {"format": "criteo"},
             "value": cells_},
            {"name": "parse_cells_missing_total",
             "labels": {"format": "criteo"}, "value": missing},
            {"name": "parse_rows_total", "value": 7 * cells_}],
            "histograms": [
            {"name": "parse_stage_scan_us", "sum": scan_us, "count": 3},
            {"name": "parse_stage_parse_us", "sum": parse_us, "count": 3},
            {"name": "parse_stage_fill_us", "sum": 10 ** 9, "count": 3}]}
    ctx = {"telemetry": (snap(1000, 100, 50, 500),
                         snap(4000, 600, 60, 620))}
    # 3,000 cells met, 500 of them empty: the work is the 2,500 present
    assert hist_per_counter.read(ctx, how) == pytest.approx(
        1e3 * (10 + 120) / 2500)
    # without `less` the unit is the counter's own
    assert hist_per_counter.read(
        ctx, {k: v for k, v in how.items() if k != "less"}) == \
        pytest.approx(1e3 * (10 + 120) / 3000)
    # the parent's program has no such counter: nothing, and no error
    bare = {"counters": [], "histograms": snap(0, 0, 1, 1)["histograms"]}
    assert hist_per_counter.read({"telemetry": (bare, bare)}, how) is None


@pytest.mark.parametrize("fault,exact", [("drop_last_column", True),
                                         ("column_not_hashed", False),
                                         ("empty_cell_hashed", True)])
def test_a_fault_of_the_lane_is_not_correct(scenarios, fault, exact):
    """Each fault fails the comparison with the reference; the two that
    lose or invent entries also fail ``epoch_nnz_gap``, and the one that
    keeps every entry's count is seen by the reference alone."""
    line = scenarios[fault]
    assert line["correct"] is False
    c = line["checks"]
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        assert c[name]["value"] > c[name]["limit"], (name, c[name])
    assert (c["epoch_nnz_gap"]["value"] > 0) == exact
    assert c["epoch_rows_gap"]["value"] == 0


def test_the_control_in_bfloat16_is_not_correct(scenarios):
    assert scenarios["control"]["ok"] is False
