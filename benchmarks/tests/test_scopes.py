"""Tests of what PR 26 adds to the benchmark: the scope and span readers on a
sample cut from a chip trace of that PR, the wire-format reader behind them,
the seven metric files, and the entries that wait in
``proposed_per_layer.json`` (PERF.md section 7 says why they wait).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

``recorded_scopes.json`` (``trace_dump.py --cut`` of cell 1's traced run, my
chip run, PR 26): three whole steps around an epoch turnover, in a window
narrowed to start 40% into the step before and end 25% into the step after,
so ``%fusion.8`` straddles its start and ``%fusion.5`` its end. Hand-checked
from the file's own numbers:

- ``dp.apply`` holds two operations a step, 15.2865 + 0.9573 ms
  (``%multiply_subtract_fusion`` and ``.1``): 16.244 ms a step;
- the one long idle gap is [122.813, 141.385] ms after the window's start;
  in it the consumer sat in ``dmlc.device.wait`` 0.042 ms, in
  ``dmlc.device.epoch_turnover`` 2.808 ms and in the first wait of the new
  epoch 12.088 ms; one more wait of 0.080 ms falls in the gap at 217.351 ms;
  the waits at 48.020 and 297.128 ms start after their gaps have closed.
  15.018 ms of a 315.762 ms window: 4.756%.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from harness import cells, result_line  # noqa: E402
from readers import _xplane, hist_ratio, scope_time, span_idle  # noqa: E402

NEW = ("fm_step.gather_ms", "fm_step.grad_table_ms", "fm_step.apply_ms",
       "idle.input_pct", "dispatch.us_per_step", "turnover.us_per_epoch",
       "first_batch.us_per_epoch")


def how(name):
    return cells.load_json("metrics", name + ".json")


@pytest.fixture(scope="module")
def recorded():
    return cells.load_json("tests", "recorded_scopes.json")


# -- the readers on the recorded sample ------------------------------------------

@pytest.mark.parametrize("metric,ms", [("fm_step.gather_ms", 15.4253),
                                       ("fm_step.grad_table_ms", 32.2013),
                                       ("fm_step.apply_ms", 16.2441)])
def test_scope_time_on_the_recorded_sample(recorded, metric, ms):
    assert scope_time.reduce(recorded, how(metric)) == pytest.approx(
        ms, abs=1e-3)


def test_scope_time_by_hand(recorded):
    lo, _ = recorded["window"]
    mods, ops = _xplane.step_ops(recorded, "sharded_step")
    assert len(mods) == 3
    apply = [op for op in ops if "dp.apply" in op[3]]
    assert [op[0] for op in apply] == ["%multiply_subtract_fusion",
                                       "%multiply_subtract_fusion.1"] * 3
    assert sum(op[2] for op in apply) / 3 / 1e6 == pytest.approx(
        15.2865 + 0.9573, abs=2e-3)
    # the forward gather and the scatter into the dense gradient are told
    # apart by the transpose in the path, whatever the fusions are numbered
    fwd = {op[0] for op in ops if _xplane.matches(op[3],
                                                  how("fm_step.gather_ms"))}
    bwd = {op[0] for op in ops if _xplane.matches(
        op[3], how("fm_step.grad_table_ms"))}
    assert fwd and bwd and not fwd & bwd
    assert "%fusion.8" in bwd  # PERF.md section 5: the scatter into [F,K]


def test_a_step_cut_by_the_windows_edge_is_left_out(recorded):
    lo, hi = recorded["window"]
    straddling = [op for op in recorded["ops"]
                  if op[1] < lo < op[1] + op[2] or op[1] < hi < op[1] + op[2]]
    assert [op[0] for op in straddling] == ["%fusion.8", "%fusion.5"]
    assert not set(map(tuple, straddling)) & set(
        map(tuple, _xplane.inside(recorded["ops"], lo, hi)))
    before = scope_time.reduce(recorded, how("fm_step.grad_table_ms"))
    # 5 ms earlier the straddling scatter is wholly inside the window, but
    # its step is not: neither side of the ratio moves
    wider = dict(recorded, window=[lo - 5e6, hi])
    assert scope_time.reduce(wider, how("fm_step.grad_table_ms")) == before
    # with the whole step inside, it counts on both sides
    whole = dict(recorded, window=[recorded["modules"][0][1] - 1.0, hi])
    assert len(_xplane.step_ops(whole, "sharded_step")[0]) == 4
    assert scope_time.reduce(whole, how("fm_step.grad_table_ms")) == \
        pytest.approx(before, rel=0.01)


def test_span_idle_on_the_recorded_sample(recorded):
    got = span_idle.reduce(recorded, how("idle.input_pct"))
    lo, hi = recorded["window"]
    by_hand = (0.042 + 2.808 + 12.088 + 0.080) / ((hi - lo) / 1e6)
    assert got == pytest.approx(100.0 * by_hand, abs=2e-3)
    assert got == pytest.approx(4.7562, abs=1e-3)
    # a part of the idle share, never more
    idle = sum(b - a for a, b in _xplane.idle_gaps(recorded)) / (hi - lo)
    assert idle == pytest.approx(0.080776, abs=1e-5)
    assert got < 100.0 * idle
    # the turnover alone
    assert span_idle.reduce(recorded, {"spans": [
        "dmlc.device.epoch_turnover"]}) == pytest.approx(
            100.0 * 2.808 / ((hi - lo) / 1e6), abs=2e-3)


def test_three_threads_hold_the_programs_spans(recorded):
    lines = {i: {e[0] for e in evs} for i, evs in recorded["host"].items()}
    owners = {name: [i for i, names in lines.items() if name in names]
              for name in ("dmlc.device.wait", "dmlc.device.stage",
                           "dmlc.device.put")}
    assert all(owners.values())
    assert len({i for own in owners.values() for i in own}) >= 3
    consumer = owners["dmlc.device.wait"][0]
    assert {"dmlc.model.step", "dmlc.device.epoch_turnover",
            "bench.next_batch"} <= lines[consumer]


def test_a_program_without_scopes_or_spans_gives_none(recorded):
    """The parent commit's trace: no scope path, no ``dmlc.`` annotation."""
    parent = copy.deepcopy(recorded)
    for op in parent["ops"]:
        op[3] = op[3].replace("dp.loss_grad/", "").replace("dp.apply/", "") \
            .replace("fm.gather", "").replace("fm.linear", "")
    parent["host"] = {i: [e for e in evs if not e[0].startswith("dmlc.")]
                      for i, evs in parent["host"].items()}
    for name in NEW[:3]:
        assert scope_time.reduce(parent, how(name)) is None
    assert span_idle.reduce(parent, how("idle.input_pct")) is None
    assert scope_time.reduce(recorded, dict(how("fm_step.apply_ms"),
                                            module="no_such_module")) is None


def test_hist_ratio_reads_the_new_histograms_and_none_without_them():
    def snap(count, total):
        return {"histograms": [{"name": "device_turnover_us",
                                "count": count, "sum": total}]}
    ctx = {"telemetry": (snap(2, 5000.0), snap(7, 20000.0)),
           "plain": types.SimpleNamespace(rows=1, seconds=1.0)}
    assert hist_ratio.read(ctx, how("turnover.us_per_epoch")) == 3000.0
    assert hist_ratio.read(ctx, how("dispatch.us_per_step")) is None
    assert hist_ratio.read(ctx, how("first_batch.us_per_epoch")) is None


# -- the wire-format reader ---------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(num, payload):  # a length-delimited field
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _vi(num, value):
    return _varint(num << 3) + _varint(value)


def test_metadata_stats_reads_str_and_ref_values(tmp_path):
    """A hand-built ``XSpace``: a device plane whose events' metadata carry
    ``tf_op`` once as a string and once as a reference to another stat
    metadata entry, beside numeric stats of every wire type, and a host plane
    that is skipped."""
    def stat_meta(i, name):
        return _ld(5, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name)))

    def event_meta(i, name, stats):
        body = (_vi(1, i) + _ld(2, name) + _ld(4, b"display name")
                + b"".join(_ld(5, s) for s in stats))
        return _ld(4, _vi(1, i) + _ld(2, body))

    path_a = b"jit(f)/dp.loss_grad/transpose(jvp(fm.gather))/scatter-add:"
    device = (_vi(1, 2) + _ld(2, b"/device:TPU:0")
              + _ld(3, _ld(2, b"XLA Ops") + b"\x00" * 40)  # a line, skipped
              + stat_meta(1, b"tf_op") + stat_meta(2, b"hlo_category")
              + stat_meta(4, b"flops") + stat_meta(300, b"jit(f)/dp.apply/sub:")
              + event_meta(7, b"%fusion.8 = f32[1]", [
                  _vi(1, 2) + _ld(5, b"fusion"),
                  _vi(1, 4) + _vi(3, 12345678901),
                  _vi(1, 1) + _ld(5, path_a)])
              + event_meta(800, b"%sub.1", [
                  _vi(1, 1) + _vi(7, 300),
                  _vi(1, 4) + _varint(2 << 3 | 1) + b"\x00" * 8])
              + event_meta(9, b"%copy", []))
    host = _vi(1, 1) + _ld(2, b"/host:CPU") + event_meta(
        1, b"dmlc.device.wait", [])
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_ld(1, host) + _ld(1, device) + _ld(4, b"hostname"))
    stats = _xplane.metadata_stats(str(f))
    assert set(stats) == {"/device:TPU:0"}
    assert stats["/device:TPU:0"]["%fusion.8 = f32[1]"] == {
        "hlo_category": "fusion", "tf_op": path_a.decode()}
    assert _xplane.op_scopes(str(f))["/device:TPU:0"] == {
        "%fusion.8 = f32[1]": path_a.decode(),
        "%sub.1": "jit(f)/dp.apply/sub:", "%copy": ""}


# -- the metric files and the entries that wait --------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_names_a_reader(name):
    h = how(name)
    reader = cells.load_module("readers", h["reader"])
    assert callable(reader.read)
    if h["reader"] == "hist_ratio":
        sys.path.insert(0, REPO)
        from dmlc_core_tpu import telemetry
        assert set(h["histograms"]) <= set(telemetry.METRIC_HELP)


def test_proposed_entries_fit_benchmark_json():
    """What a ``benchmark`` PR appends to ``per_layer``: the shape
    ``test_benchmark_json_shape`` asks of an entry, the accepted layers'
    names letter for letter, and all fifteen metrics expected of a traced
    run of every cell (``parse.us_per_krow`` not in ``.crec``)."""
    spec = cells.load_spec()
    proposed = cells.load_json("tests", "proposed_per_layer.json")
    assert [m["name"] for m in proposed] == list(NEW)
    have = {m["name"] for m in spec["per_layer"]}
    layers = {m["layer"] for m in spec["per_layer"]}
    cell_names = [w["name"] for w in spec["workloads"]]
    for m in proposed:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in have and m["layer"] in layers
        assert m["moves"] == "rows_per_s" and m["workloads"] == cell_names
        assert m["source"] in ("device_trace", "program_span")
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
    both = dict(spec, per_layer=spec["per_layer"] + proposed)
    for cell in cell_names:
        want = result_line.expected_metrics(both, cell, True)
        assert len(want) == (14 if cell.endswith(".crec") else 15)


def test_scope_key_names_phase_and_model_scope():
    from traced_proposed import scope_key
    j = "jit(sharded_step)/"
    assert scope_key(j + "dp.loss_grad/transpose(jvp(fm.gather))/"
                     "scatter-add:") == \
        "dp.loss_grad/transpose(jvp(fm.gather))"
    assert scope_key(j + "dp.loss_grad/jvp(fm.linear)/jit(_take)/gather:") \
        == "dp.loss_grad/fm.linear"
    assert scope_key(j + "dp.apply/sub:") == "dp.apply"
    assert scope_key(j + "dp.loss_grad/jvp()/reduce_sum:") == "dp.loss_grad"
    assert scope_key("") == "(none)"
