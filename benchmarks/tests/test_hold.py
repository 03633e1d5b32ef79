"""The cell ``kdd2010b-fm.crec`` and the three metrics that read the consumer's
hold and the pulse (ISSUE 36). The cell is in ``BENCHMARK.json`` and reports
what ``kdd2012-fm.crec`` does. The three entries wait in
``proposed_per_layer_hold.json`` (run by ``traced_proposed_hold.py``): the
parent runs the cell, its program has no such histograms, and ``run.py``
refuses its own line when a listed metric is missing, so the driver refused
the PR that listed them (PERF.md section 7). Held here: the entries and their
files, their values read by ``readers/hist_ratio.py`` from a pair of
telemetry snapshots recorded round the untraced half of a traced run of the
cell on the chip (``recorded_hold.json``), a line built from them passing
``result_line.validate`` under the extended spec, the line a program without
the histograms would print being refused by name, and the six cells the
benchmark had keeping their metrics letter for letter.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_hold.py -q
"""

from __future__ import annotations

import copy
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from harness import cells, result_line  # noqa: E402
from readers import hist_ratio  # noqa: E402

CELL = "kdd2010b-fm.crec"
NEW = {"hold.us_per_batch": ("us", "device_hold_us", "batch"),
       "hold.excess_share": ("%", "device_hold_excess_us", "window_pct"),
       "pulse.late_share": ("%", "pulse_py_late_us", "window_pct")}
# what a traced run of each cell reported before this cell came, in order
OLD = {
    "kdd2012-fm.libfm": [
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step_roofline",
        "fm_step.mfu_hbm", "device_idle_share"],
    "kdd2010b-fm.libsvm": [
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step_roofline",
        "fm_step.mfu_hbm", "device_idle_share"],
    "kdd2012-fm.crec": [
        "stage.us_per_batch", "put.us_per_batch", "input_wait_share",
        "step_ms_p95", "fm_step_roofline", "fm_step.mfu_hbm",
        "device_idle_share"],
    "kdd2012-fm-dp4.libfm": [
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step.mfu_hbm",
        "device_idle_share", "dp.allreduce_ms", "fm_step.apply_ms",
        "fm_step_roofline.dp"],
    "criteo1tb-fm.tsv": [
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step_roofline",
        "fm_step.mfu_hbm", "device_idle_share", "fm_step.apply_ms",
        "parse.ns_per_cell", "fm_step.expand_ms"],
    "criteo1tb-fm-s3.tsv-s3": [
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step_roofline",
        "fm_step.mfu_hbm", "device_idle_share", "fm_step.apply_ms",
        "parse.ns_per_cell", "fm_step.expand_ms", "split.open_us",
        "s3.first_byte_us", "s3.range_wait_share"],
}


@pytest.fixture(scope="module")
def spec():
    return cells.load_spec()


@pytest.fixture(scope="module")
def proposed():
    return cells.load_json("tests", "proposed_per_layer_hold.json")


@pytest.fixture(scope="module")
def extended(spec, proposed):
    """``BENCHMARK.json`` with the three entries appended, as
    ``traced_proposed_hold.py`` runs the cell."""
    return dict(spec, per_layer=spec["per_layer"] + proposed)


@pytest.fixture(scope="module")
def recorded():
    return cells.load_json("tests", "recorded_hold.json")


def test_the_cell_is_two_files_that_stood_and_reports_what_its_sibling_does(
        spec):
    cell = cells.load_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kdd2010b-fm", "crec", 1)
    assert cell["traffic_file"]["store"] == "crec"
    assert spec["workloads"][-1]["name"] == CELL   # appended, not put between
    sibling = list(result_line.expected_metrics(spec, "kdd2012-fm.crec",
                                                True))
    assert list(result_line.expected_metrics(spec, CELL, True)) == sibling
    assert list(result_line.expected_metrics(spec, CELL, False)) == [
        "rows_per_s", "setup_s"]


def test_the_three_entries_and_files_are_as_issue_36_names_them(
        spec, proposed, extended):
    # what the parent cannot report is listed for no cell of BENCHMARK.json
    assert not {m["name"] for m in spec["per_layer"]} & set(NEW)
    entries = {m["name"]: m for m in proposed}
    assert list(entries) == list(NEW)
    assert list(result_line.expected_metrics(extended, CELL, True))[-3:] == \
        list(NEW)
    for name, (unit, hist, per) in NEW.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "step loop",
            "moves": "rows_per_s", "workloads": [CELL]}
        assert cells.load_json("metrics", name + ".json") == {
            "reader": "hist_ratio", "histograms": [hist], "per": per}


def test_the_six_cells_that_were_keep_their_metrics_letter_for_letter(spec):
    assert [w["name"] for w in spec["workloads"]][:6] == list(OLD)
    for cell, names in OLD.items():
        assert list(result_line.expected_metrics(spec, cell, True)) == names
        assert list(result_line.expected_metrics(spec, cell, False)) == [
            "rows_per_s", "setup_s"]


def _read(recorded, name):
    plain = types.SimpleNamespace(**recorded["plain"])
    ctx = {"telemetry": (recorded["before"], recorded["after"]),
           "plain": plain}
    return hist_ratio.read(ctx, cells.load_json("metrics", name + ".json"))


def test_recorded_snapshots_give_the_three_values_and_a_valid_line(
        extended, recorded):
    spec = extended
    values = {name: _read(recorded, name) for name in NEW}
    assert all(v is not None and v == v and abs(v) != float("inf")
               for v in values.values()), values
    # the run's own line read the same histograms the same way
    for name, v in values.items():
        assert v == pytest.approx(recorded["metrics"][name]["value"])
    assert 50_000 < values["hold.us_per_batch"] < 200_000   # a step of 90 ms
    assert 0 <= values["hold.excess_share"] < 100
    assert 0 <= values["pulse.late_share"] < 100
    # wait + hold is the window: the hold a batch and the mean wait a batch
    # come to the window over its steps, to 2%
    p = recorded["plain"]
    wait_pct = _read(recorded, "input_wait_share")
    wait_us = wait_pct / 100 * p["seconds"] * 1e6 / p["steps"]
    step_us = p["seconds"] * 1e6 / p["steps"]
    assert (values["hold.us_per_batch"] + wait_us) == pytest.approx(
        step_us, rel=0.02)
    # a traced line of the cell with them validates; without them it is
    # refused by name, which is what a program without the histograms gets
    want = result_line.expected_metrics(spec, CELL, True)
    metrics = {n: {"value": values.get(n, 1.5), "unit": m["unit"]}
               for n, m in want.items()}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 11756820480, "window_s": 3.04,
              "busy_s": 2.93}
    line = result_line.build(True, 140, 0, metrics, device, None, {}, {})
    result_line.validate(line, spec, CELL, True)
    for name in NEW:
        lacking = copy.deepcopy(line)
        del lacking["metrics"][name]
        with pytest.raises(result_line.LineError, match=name):
            result_line.validate(lacking, spec, CELL, True)


def test_a_program_without_the_histograms_reads_nothing_and_does_not_raise(
        recorded):
    bare = {"histograms": [h for h in recorded["after"]["histograms"]
                           if h["name"] == "device_wait_us"]}
    plain = types.SimpleNamespace(**recorded["plain"])
    ctx = {"telemetry": (bare, bare), "plain": plain}
    for name in NEW:
        assert hist_ratio.read(
            ctx, cells.load_json("metrics", name + ".json")) is None
