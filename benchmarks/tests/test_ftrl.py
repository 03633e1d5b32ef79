"""Tests of what the benchmark has for the click logs under
their trainers' update rule, ``criteo1tb-fm-ftrl.tsv``: the configuration
against the one whose data and model it shares, the work count of a step by
hand, the readers on a sample cut from a chip trace of that cell, and the
runner rehearsed at a small size on the CPU (a traced run needs a device's
planes and is left to the chip): correct with every exact number 0, and the
control in bfloat16 and a planted fault of the rule (``n`` updated before
``σ`` is taken) each not correct.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_ftrl.py -q

``recorded_ftrl.json`` (``trace_cut_dp.py`` of the cell's traced run on a TPU
v5e): three whole steps from the middle of the traced window.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cells, result_line, trace, work, work_ftrl  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402
from readers import (_xplane, module_roofline_ftrl, scope_time,  # noqa: E402
                     step_mfu_ftrl)

CELL = "criteo1tb-fm-ftrl.tsv"
RULE = ("updater", "alpha", "beta", "l1", "l2", "v_lr", "v_beta")
KIND = "TPU v5 lite"


def how(name):
    return cells.load_json("metrics", name + ".json")


# -- the configuration -----------------------------------------------------------

def test_configuration_is_criteo1tb_fm_but_for_the_rule():
    """Data, line, model shape and batch letter for letter; what differs is
    the model's description and the rule's keys (SGD's ``learning_rate``
    goes, ``l2`` becomes FTRL's λ2), and what names, states and checks the
    rule."""
    one = cells.load_json("configs", "criteo1tb-fm.json")
    ftrl = cells.load_json("configs", "criteo1tb-fm-ftrl.json")
    owned = {"runner", "reference", "source", "model", "learning_rate",
             "l2", "guarantees", "assumed", "deployment"} | set(RULE)
    for key in set(one) | set(ftrl):
        if key not in owned:
            assert ftrl[key] == one[key], key
    assert set(ftrl) - set(one) == {"deployment"} | set(RULE) - {"l2"}
    assert set(one) - set(ftrl) == {"learning_rate"}
    assert ftrl["runner"] == "fm_ftrl" and ftrl["reference"] == "ftrl"
    assert ftrl["updater"] == "ftrl" and ftrl["reduced"] == ["train_rows"]
    assert ftrl["source"].startswith(one["source"])
    assert ftrl["limits"] == one["limits"]
    for name, text in one["guarantees"].items():
        assert ftrl["guarantees"][name] == text
    assert set(ftrl["guarantees"]) - set(one["guarantees"]) == {
        "exactly_once_update"}
    for key in RULE[1:]:
        assert key in ftrl["assumed"], key     # each with its reason


def test_benchmark_json_lists_the_cell_and_its_metrics():
    spec = cells.load_spec()
    cell = cells.load_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "tsv"
    assert spec["workloads"][-1]["name"] == CELL     # appended
    want = result_line.expected_metrics(spec, CELL, True)
    assert set(want) == {
        "parse.us_per_krow", "parse.ns_per_cell", "stage.us_per_batch",
        "put.us_per_batch", "input_wait_share", "step_ms_p95",
        "device_idle_share", "fm_step.apply_ms", "fm_step.expand_ms",
        "opt.update_ms", "fm_step_roofline.ftrl", "fm_step.mfu_hbm.ftrl"}
    for name in ("opt.update_ms", "fm_step_roofline.ftrl",
                 "fm_step.mfu_hbm.ftrl"):
        assert want[name]["workloads"] == [CELL]
    # the SGD step's work counts describe another step
    assert "fm_step_roofline" not in want and "fm_step.mfu_hbm" not in want


# -- what a step needs -------------------------------------------------------------

def test_ftrl_work_by_hand_on_a_three_row_batch():
    """Three rows of 2, 1 and 3 entries over 4 distinct columns at rank 2, a
    batch of 96 bytes: each touched column's w, z, n and two lanes each of
    v and G, 7 floats, read and written once, 224 bytes; the SGD step's
    operations and ten an element of a touched row."""
    need = work_ftrl.fm_ftrl_step(distinct=4, nnz=6, rows=3, rank=2,
                                  batch_bytes=96)
    assert need["bytes"] == 2 * 4 * 7 * 4 + 96 == 320
    assert need["flops"] == (8 * 6 * 2 + 4 * 6 + 12 * 3) + 10 * 4 * 7
    # by distinct columns, where the SGD count is by entries
    assert need["bytes"] != work.fm_sgd_step(6, 3, 2, 96)["bytes"]
    least = work_ftrl.least_seconds(need, peaks_for(KIND))
    assert least["bound"] == "bytes"


def test_ftrl_work_at_the_cells_numbers():
    """About 205k distinct columns of 550.8k entries: 2 x 205,000 x 35 x 4
    = 57.4 MB of rows and the batch's 8.1 MB, 80 us at 819 GB/s."""
    batch_bytes = 4 * (3 * 16384 + 3 * 589824 + 212992)
    need = work_ftrl.fm_ftrl_step(205000, 550800, 16384, 16, batch_bytes)
    assert need["bytes"] == pytest.approx(57.4e6 + 8.13e6, rel=2e-3)
    least = work_ftrl.least_seconds(need, peaks_for(KIND))
    assert least["seconds"] == pytest.approx(80.0e-6, rel=5e-3)


# -- the readers ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return cells.load_json("tests", "recorded_ftrl.json")


def ctx_of(recorded, distinct=True):
    steps, nnz, rows = 3, 550800, 16384
    sec = types.SimpleNamespace(steps=steps, nnz=steps * nnz, rows=steps * rows,
                                seconds=0.3)
    if distinct:
        sec.distinct = steps * 205000
    return {"traced": sec, "plain": sec,
            "session": types.SimpleNamespace(cfg={"fm_rank": 16},
                                             bytes_per_batch=8130048),
            "events": recorded, "peaks": peaks_for(KIND),
            "device": {"count": 1, "kind": KIND}}


def test_recorded_sample_holds_three_whole_steps(recorded):
    lo, hi = trace.window_of(recorded)
    whole = [m for m in recorded["planes"]["/device:TPU:0"][trace.MODULES_LINE]
             if "sharded_step" in m[0] and m[1] >= lo and m[1] + m[2] <= hi]
    assert len(whole) == 3


def test_module_roofline_ftrl_on_the_recorded_sample(recorded):
    got = module_roofline_ftrl.read(ctx_of(recorded),
                                    how("fm_step_roofline.ftrl"))
    mod = trace.module_time(recorded, "sharded_step")
    a_step = mod["seconds"] / mod["count"]
    need = work_ftrl.fm_ftrl_step(205000, 550800, 16384, 16, 8130048)
    least = work_ftrl.least_seconds(need, peaks_for(KIND))["seconds"]
    assert got == pytest.approx(100.0 * least / a_step)
    assert 0 < got < 100
    # a section without the runner's count, a trace without the module
    assert module_roofline_ftrl.read(ctx_of(recorded, distinct=False),
                                     how("fm_step_roofline.ftrl")) is None
    assert module_roofline_ftrl.read(
        ctx_of(recorded), dict(how("fm_step_roofline.ftrl"),
                               module="no_such_module")) is None


def test_step_mfu_ftrl_by_hand():
    ctx = ctx_of(None)
    got = step_mfu_ftrl.read(ctx, how("fm_step.mfu_hbm.ftrl"))
    need = work_ftrl.fm_ftrl_step(3 * 205000, 3 * 550800, 3 * 16384, 16,
                                  3 * 8130048)
    least = work_ftrl.least_seconds(need, peaks_for(KIND))["seconds"]
    assert got == pytest.approx(100.0 * least / 0.3)
    assert step_mfu_ftrl.read(ctx_of(None, distinct=False),
                              how("fm_step.mfu_hbm.ftrl")) is None


def test_update_time_on_the_recorded_sample(recorded):
    scopes = recorded["scopes"]
    mods, ops = _xplane.step_ops(scopes, "sharded_step")
    assert len(mods) == 3
    mine = [op for op in ops if "opt.update" in op[3]]
    assert mine
    got = scope_time.reduce(scopes, how("opt.update_ms"))
    assert got == pytest.approx(sum(op[2] for op in mine) / 3 / 1e6)
    # the rule lies inside the apply phase, whose write-back is the rest
    apply = scope_time.reduce(scopes, how("fm_step.apply_ms"))
    assert 0 < got < apply
    # hand-checked from the file's own numbers: the gathers of G, n and z
    # rows 4.68, 3.25 and 3.16 ms and the elementwise rule 0.15; the apply
    # phase holds them and the five write-backs, G's and v's 20.8 each
    assert got == pytest.approx(11.24, abs=0.02)
    assert apply == pytest.approx(57.21, abs=0.02)
    # an SGD step's trace has no such scope: nothing, and no error
    old = cells.load_json("tests", "recorded_scopes.json")
    assert scope_time.reduce(old, how("opt.update_ms")) is None


# -- the runner, on the CPU ------------------------------------------------------------

SCENARIOS = r"""
import json, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import jax.numpy as jnp
import run
from dmlc_core_tpu.models import fm as program
from harness import cells, check
out = {}
CELL = "tiny-ftrl.tinytsv"
out["sound"] = run.run_cell(CELL, 2**31 + 81, 0.5, False, require_chip=False)


def n_before_sigma(w, z, n, g, alpha, beta, l1, l2):
    # the planted fault: n takes g^2 before sigma is worked out from it
    n = n + g * g
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
    z = z + g - sigma * w
    w = jnp.where(jnp.abs(z) <= l1, 0.0,
                  -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / alpha
                                             + l2))
    return w, z, n


rule = program._ftrl
program._ftrl = n_before_sigma
try:
    out["n_before_sigma"] = run.run_cell(CELL, 2**31 + 81, 0.3, False,
                                         require_chip=False)
finally:
    program._ftrl = rule
spec = cells.load_spec()
cell = cells.load_cell(spec, CELL)
s = cells.load_module("runners", "fm_ftrl").Session(cell, 5, 1)
ref = s.reference_readings()
out["control"] = check.judge(check.gaps(s.reference_readings("bfloat16"), ref),
                             cell["config_file"]["limits"])
print("SCENARIOS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy_ftrl"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = cells.load_json("configs", "criteo1tb-fm-ftrl.json")
    # at 64 rows a batch a column's batch-mean gradient is 256 times the
    # cell's: l1 so that both branches of the closed form run (0.14 of the
    # moved rows' w at 0)
    cfg.update(hash_bits=12, num_features=4096, fm_rank=4, batch_rows=64,
               l1=0.05)
    for f in cfg["data"]["fields"]:
        f["cardinality"] = min(f["cardinality"], 300)
    with open(os.path.join(dst, "benchmarks/configs/tiny-ftrl.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = dict(cells.load_json("traffic", "tsv.json"), epoch_batches=8)
    with open(os.path.join(dst, "benchmarks/traffic/tinytsv.json"),
              "w") as f:
        json.dump(traffic, f)
    spec = cells.load_spec()
    spec["configs"].append({"name": "tiny-ftrl", "source": "a test",
                            "file": "benchmarks/configs/tiny-ftrl.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "tiny-ftrl.tinytsv",
                              "config": "tiny-ftrl", "traffic": "tinytsv",
                              "chips": 1, "why": "a test"})
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
    notes = line["notes"]
    assert notes["epochs_finished"] >= 2
    # both branches of the closed form ran
    assert 0 < notes["setup"]["w_zero_share"] < 1
    assert notes["setup"]["rows_moved"] > 0


def test_n_updated_before_sigma_is_not_correct(scenarios):
    """The first step is blind to it (w is 0, so sigma * w is too); from the
    second on z drifts, and w with it: the change after the last step
    fails."""
    line = scenarios["n_before_sigma"]
    assert line["correct"] is False
    c = line["checks"]
    assert c["change_norm_gap"]["value"] > c["change_norm_gap"]["limit"]
    for name in ("epoch_rows_gap", "epoch_nnz_gap"):
        assert c[name]["value"] == 0


def test_the_control_in_bfloat16_is_not_correct(scenarios):
    assert scenarios["control"]["ok"] is False
