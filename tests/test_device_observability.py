"""Device-lane flight instruments (doc/observability.md "Device lane").

Covers the ISSUE 15 acceptance surface on the deterministic CPU backend:

- The device spans: a real `DeviceRowBlockIter` run leaves
  `device.stage` / `device.put` (+ submit/block children) /
  `device.wait` spans that render nested-or-disjoint per lane on ONE
  wall clock alongside the native `parse.*` spans.
- The overlap ratio: in [0, 1] after a run, −1 (sentinel gauge) before
  any transfer, and exact on hand-built span sets.
- Stall attribution: the synthetic verdict matrix extended with the
  device-lane verdicts (`stage_bound`, `compile_bound`, a forced
  `transfer_bound` with tiny compute), plus BOTH injected e2e flips — a
  throttled batcher must read `stage_bound`, an injected `device_put`
  stall `transfer_bound`.
- Compile-churn telemetry: a growing-nnz corpus crosses exactly the
  expected buckets of the nnz ladder (powers of two map to themselves;
  counts between them land on an eighth-of-an-octave rung); replaying the
  same corpus reports zero new shapes.
- `_device_put` failures: counted and flight-dumped like host aborts.
- The hold (ISSUE 36): the time between handing a batch over and being
  asked for the next is `device.hold` / `device_hold_us` on both consumer
  loops, with `device_wait_us` the consumer's whole time; a hold far over
  the running median is counted once and recorded with a verdict, each
  verdict produced by its made cause (a sleep, a spin, a held interpreter
  lock, a stopped process, a compile; puts that flow, hang or do not run).
- One clock for host and device (ISSUE 26): under `jax.profiler` the
  program's opened spans are `dmlc.*` events of the trace's host plane;
  the learner's step counts its builds and times its dispatch; turnover
  and the first batch's wait are observed once per `before_first()`; the
  jitted steps carry their named scopes; with telemetry off none of it
  runs and the step's results are bit-identical.
"""

from __future__ import annotations

import ctypes
import json
import logging
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.tpu import device_iter
from dmlc_core_tpu.tpu.device_iter import (DeviceRowBlockIter,
                                           jax_profiler_capture)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()
    yield
    telemetry.reset()
    telemetry.enable(True)
    device_iter._reset_shape_census()


def write_libsvm(path, rows, features=8, seed=0):
    rng = random.Random(seed)
    lines = []
    for i in range(rows):
        feats = " ".join(
            f"{j}:{rng.uniform(-1, 1):.4f}" for j in range(features))
        lines.append(f"{i % 2} {feats}")
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def _run_iter(path, **kw):
    kw.setdefault("batch_rows", 256)
    kw.setdefault("min_nnz_bucket", 128)
    kw.setdefault("layout", "csr")
    with DeviceRowBlockIter(path, **kw) as it:
        return sum(b.total_rows for b in it)


# -- device spans on one clock ------------------------------------------------
def test_device_spans_nested_disjoint_with_parse_on_one_clock(tmp_path):
    path = write_libsvm(tmp_path / "a.libsvm", rows=1500)
    assert _run_iter(path, nthread=2) == 1500
    doc = json.loads(telemetry.trace_json())
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    # the full device-lane span catalog, plus the host parse spans, in
    # ONE merged document
    assert {"device.stage", "device.put", "device.put.submit",
            "device.put.block", "device.wait"} <= names, names
    assert "parse.fill" in names or "batch.fill" in names, names
    # one wall clock: every merged span within a sane window
    now_us = time.time() * 1e6
    for e in evs:
        assert abs(e["ts"] - now_us) < 300e6, (e["name"], e["ts"])
        assert e["dur"] >= 0
    # per-lane ordering (the Perfetto render contract, same check as the
    # tracing suite): consecutive spans per (pid, tid) lane either nest
    # inside their predecessor or begin after it ends; 1 ms slack
    lanes = {}
    for e in evs:
        lanes.setdefault(e["tid"], []).append(e)
    for lane_evs in lanes.values():
        lane_evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        for a, b in zip(lane_evs, lane_evs[1:]):
            nested = b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1000
            disjoint = b["ts"] >= a["ts"] + a["dur"] - 1000
            assert nested or disjoint, (a, b)
    # submit/block partition their parent put (within rounding) and
    # genuinely parent under it in the ring (the `parent` field, not
    # just timestamp containment)
    puts = [e for e in evs if e["name"] == "device.put"]
    subs = [e for e in evs if e["name"] == "device.put.submit"]
    blocks = [e for e in evs if e["name"] == "device.put.block"]
    assert len(puts) == len(subs) == len(blocks) >= 2
    assert all("bytes" in p["args"] for p in puts)
    put_ids = {p["args"]["span_id"] for p in puts}
    for child in subs + blocks:
        assert child["args"]["parent"] in put_ids, child


def test_device_stage_spans_carry_rows_and_histograms_fill(tmp_path):
    path = write_libsvm(tmp_path / "b.libsvm", rows=700)
    assert _run_iter(path) == 700
    stages = [s for s in telemetry.spans() if s["name"] == "device.stage"]
    assert sum(s["args"]["rows"] for s in stages) == 700
    snap = telemetry.snapshot(native=False)
    hists = {h["name"]: h for h in snap["histograms"] if not h["labels"]}
    for name in ("device_stage_us", "device_transfer_us",
                 "device_put_submit_us", "device_put_block_us",
                 "device_wait_us"):
        assert hists[name]["count"] >= 3, name  # 700 rows / 256 batches
    gauges = {g["name"]: g["value"] for g in snap["gauges"]}
    assert gauges["device_host_q_depth"] >= 0
    assert gauges["device_ready_q_depth"] >= 0
    counters = {c["name"]: c["value"] for c in snap["counters"]
                if not c["labels"]}
    assert counters["device_batches_total"] == 3
    assert counters["device_transfer_bytes_total"] > 0


# -- overlap ratio ------------------------------------------------------------
def test_overlap_ratio_in_unit_interval_after_run(tmp_path):
    path = write_libsvm(tmp_path / "c.libsvm", rows=2000)
    assert _run_iter(path) == 2000
    ratio = telemetry.device_overlap_ratio()
    assert ratio is not None and 0.0 <= ratio <= 1.0
    snap = telemetry.snapshot(native=False)
    gauge = [g["value"] for g in snap["gauges"]
             if g["name"] == "device_overlap_ratio"]
    assert gauge and 0.0 <= gauge[0] <= 1.0


def test_overlap_ratio_sentinel_and_exact_math():
    # no device.put spans at all -> None, and the snapshot gauge is -1
    assert telemetry.device_overlap_ratio() is None
    snap = telemetry.snapshot(native=False)
    gauge = [g["value"] for g in snap["gauges"]
             if g["name"] == "device_overlap_ratio"]
    assert gauge == [-1.0]
    # hand-built rings: a transfer fully inside a consumer wait is fully
    # exposed (ratio 0); fully outside every wait is fully hidden (1);
    # half-covered is 0.5
    def ring(xfers, waits):
        return ([{"name": "device.put", "ts": a, "dur": b - a}
                 for a, b in xfers]
                + [{"name": "device.wait", "ts": a, "dur": b - a}
                   for a, b in waits])
    assert telemetry.device_overlap_ratio(
        ring([(10, 20)], [(0, 30)])) == 0.0
    assert telemetry.device_overlap_ratio(
        ring([(10, 20)], [(40, 50)])) == 1.0
    assert telemetry.device_overlap_ratio(
        ring([(10, 20)], [(15, 25)])) == pytest.approx(0.5)
    # overlapping wait intervals merge instead of double-subtracting
    assert telemetry.device_overlap_ratio(
        ring([(10, 20)], [(8, 15), (12, 18)])) == pytest.approx(0.2)


# -- stall attribution: the extended synthetic matrix -------------------------
def _scenario(fill=0, parse=0, wait=0, transfer=0, stage=0, compile_us=0):
    hists = [
        {"name": name, "labels": {}, "count": 1, "sum": s,
         "buckets": [0] * (telemetry.HIST_BUCKETS + 1)}
        for name, s in (("parse_stage_fill_us", fill),
                        ("parse_stage_parse_us", parse),
                        ("parse_stage_reassemble_wait_us", wait),
                        ("device_transfer_us", transfer),
                        ("device_stage_us", stage),
                        ("device_compile_us", compile_us)) if s]
    return telemetry.stall_attribution(
        {"counters": [], "gauges": [], "histograms": hists})


def test_stall_verdict_synthetic_matrix_extended():
    # the four legacy verdicts are untouched (stage/compile both zero)
    assert _scenario()["verdict"] == "unknown"
    assert _scenario(9000, 1000, 5000)["verdict"] == "fill_bound"
    assert _scenario(1000, 9000, 5000)["verdict"] == "parse_bound"
    assert _scenario(5000, 5000, 100)["verdict"] == "consumer_bound"
    # forced transfer_bound, tiny compute: the host->HBM hop dominates
    # even against a busy staging thread (its NET assembly time —
    # stage minus the nested fill/parse/wait — stays small)
    att = _scenario(fill=1000, parse=500, wait=800, transfer=9000,
                    stage=3000)
    assert att["verdict"] == "transfer_bound"
    assert att["stage_us"]["stage"] == pytest.approx(700)  # net of nested
    # forced stage_bound, throttled batcher: assembly dwarfs everything
    att = _scenario(fill=500, parse=500, wait=0, transfer=1000, stage=9000)
    assert att["verdict"] == "stage_bound"
    assert att["occupancy"]["stage"] == pytest.approx(8000 / 10000)
    # compile_bound: XLA re-tracing dominates every stage
    att = _scenario(fill=500, parse=500, transfer=1000, stage=2000,
                    compile_us=20000)
    assert att["verdict"] == "compile_bound"
    # every verdict has a stable gauge code
    for v in ("stage_bound", "compile_bound"):
        assert v in telemetry.VERDICT_CODES
    assert telemetry.VERDICT_CODES["stage_bound"] == 4
    assert telemetry.VERDICT_CODES["compile_bound"] == 5


# -- stall attribution: injected e2e flips ------------------------------------
def test_stall_verdict_stage_bound_under_throttled_batcher(tmp_path):
    """An injected batcher stall (sleep per staged batch) must flip the
    verdict to stage_bound: assembly dominates while fill/parse/transfer
    stay slivers."""
    path = write_libsvm(tmp_path / "d.libsvm", rows=1200)
    it = DeviceRowBlockIter(path, batch_rows=128, min_nnz_bucket=64,
                            layout="csr")
    orig = it.batcher.next_batch

    def throttled():
        time.sleep(0.02)  # the pad/bucket/pack stage is the slow one
        return orig()

    it.batcher.next_batch = throttled
    try:
        telemetry.reset()
        assert sum(b.total_rows for b in it) == 1200
    finally:
        it.close()
    att = telemetry.stall_attribution()
    assert att["verdict"] == "stage_bound", att


def test_stall_verdict_transfer_bound_under_injected_stall(tmp_path,
                                                           monkeypatch):
    """An injected device_put stall with tiny (zero) compute must flip
    the verdict to transfer_bound."""
    path = write_libsvm(tmp_path / "e.libsvm", rows=1200)
    real_put = jax.device_put

    def slow_put(tree, *a, **kw):
        time.sleep(0.02)  # the host->HBM hop is the slow one
        return real_put(tree, *a, **kw)

    monkeypatch.setattr(jax, "device_put", slow_put)
    telemetry.reset()
    assert _run_iter(path, batch_rows=128, min_nnz_bucket=64) == 1200
    att = telemetry.stall_attribution()
    assert att["verdict"] == "transfer_bound", att


# -- compile-churn telemetry --------------------------------------------------
def _bucket_of_key(key: str, leaf: str = "big") -> int:
    # key format: "aux(D, K, R),big(D, Kb, NNZ),cols(D, U)" — the big
    # leaf's last dim is the nnz bucket, the cols leaf's the capacity of
    # the distinct-column list
    return int(re.search(leaf + r"\(([0-9, ]+)\)", key).group(1)
               .split(",")[-1])


@pytest.mark.parametrize("nfeats,want", [
    # batch nnz 64, 128, 256, 512: powers of two are rungs of the ladder
    ((1, 2, 4, 8), {64, 128, 256, 512}),
    # batch nnz 192 and 704 (11 a row, kdd2012's count) lie between powers
    # of two, on rungs of 256/16 and 1024/16: no padding at all
    ((3, 11), {192, 704}),
    # 320 + 1 rounds up one granule of 512/16
    ((5, 5.015625), {320, 352}),
])
def test_compile_churn_crosses_expected_buckets_and_replays_clean(
        tmp_path, nfeats, want):
    """A growing-nnz corpus crosses exactly the expected buckets of the
    nnz ladder (floor 16); replaying the same corpus reports zero new
    shapes."""
    # 64-row batches whose per-batch nnz grows with the features per row
    # (a fractional count gives that share of the rows one feature more)
    lines = []
    for nfeat in nfeats:
        extra = round((nfeat - int(nfeat)) * 64)
        for i in range(64):
            feats = " ".join(f"{j}:1.0"
                             for j in range(int(nfeat) + (i < extra)))
            lines.append(f"{i % 2} {feats}")
    path = tmp_path / "grow.libsvm"
    path.write_text("\n".join(lines) + "\n")

    def census():
        snap = telemetry.snapshot(native=False)
        # value-filtered: registered-but-zeroed series from earlier
        # census epochs (telemetry.reset keeps registrations) are not
        # compile events of THIS corpus
        events = {c["labels"]["shape"]: c["value"]
                  for c in snap["counters"]
                  if c["name"] == "device_compile_events_total"
                  and c["value"]}
        shapes = [g["value"] for g in snap["gauges"]
                  if g["name"] == "device_distinct_shapes"]
        return events, (shapes[0] if shapes else 0)

    rows = 64 * len(nfeats)
    assert _run_iter(str(path), batch_rows=64, min_nnz_bucket=16) == rows
    events, distinct = census()
    assert {_bucket_of_key(k) for k in events} == want
    # at most 9 distinct columns a batch: the list stays on its floor
    assert {_bucket_of_key(k, "cols") for k in events} == {16}
    assert len(events) == len(want) and distinct == len(want)
    assert all(v == 1 for v in events.values())
    # replay the SAME corpus through a fresh iterator: the census is
    # process-wide (jit-cache semantics) — zero new shapes, zero new
    # compile events
    assert _run_iter(str(path), batch_rows=64, min_nnz_bucket=16) == rows
    events2, distinct2 = census()
    assert events2 == events and distinct2 == len(want)


# -- device_put failures ------------------------------------------------------
def test_device_put_failure_counted_and_flight_dumped(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("DMLC_TRACE_DUMP", str(tmp_path / "dumps"))
    path = write_libsvm(tmp_path / "f.libsvm", rows=300)

    def exploding_put(tree, *a, **kw):
        raise RuntimeError("injected transfer failure")

    monkeypatch.setattr(jax, "device_put", exploding_put)
    with pytest.raises(RuntimeError, match="injected transfer failure"):
        _run_iter(path)
    assert telemetry.counter("device_put_failures_total").value >= 1
    dumps = [f for f in os.listdir(tmp_path / "dumps")
             if f.startswith("flight_")]
    assert dumps
    docs = [json.load(open(tmp_path / "dumps" / f)) for f in dumps]
    assert any(d["reason"] == "device-put-failure" for d in docs)


# -- one clock for host and device ---------------------------------------------
def _hist(name):
    snap = telemetry.snapshot(native=False)
    return sum(h["count"] for h in snap["histograms"] if h["name"] == name)


def _learner(model, mesh, features=8):
    from dmlc_core_tpu.models import FMLearner, LinearLearner
    if model == "fm":
        return FMLearner(features, k=4, mesh=mesh, learning_rate=0.2)
    return LinearLearner(features, mesh=mesh, learning_rate=0.5)


def test_profiler_trace_holds_the_program_spans(tmp_path, monkeypatch):
    """A run of the iterator and the learner under the profiler leaves the
    program's own spans in the trace's host plane, on the profiler's
    clock: each lies inside the capture's span of time."""
    import glob
    from jax.profiler import ProfileData
    out = tmp_path / "xprof"
    monkeypatch.setenv("DMLC_JAX_PROFILE", str(out))
    path = write_libsvm(tmp_path / "p.libsvm", rows=700)
    learner = _learner("fm", None)
    params = learner.init()
    with jax_profiler_capture() as on:
        assert on
        with jax.profiler.TraceAnnotation("test.capture"):
            with DeviceRowBlockIter(path, batch_rows=256, layout="csr",
                                    min_nnz_bucket=128) as it:
                for _ in range(2):
                    for batch in it:
                        params, loss = learner.step(params, batch)
                    it.before_first()
            float(loss)
    assert not [f for f in os.listdir(out) if f.startswith("dmlc_")]
    [xplane] = glob.glob(str(out / "plugins" / "profile" / "*" /
                             "*.xplane.pb"))
    pd = ProfileData.from_file(xplane)
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    assert len(host) == 1
    found, lines_with_spans, capture = {}, 0, None
    for line in host[0].lines:
        mine = [e for e in line.events if e.name.startswith("dmlc.")]
        lines_with_spans += bool(mine)
        for e in mine:
            found.setdefault(e.name, []).append(e)
        for e in line.events:
            if e.name == "test.capture":
                capture = (e.start_ns, e.start_ns + e.duration_ns)
    assert {"dmlc.device.stage", "dmlc.device.put", "dmlc.device.wait",
            "dmlc.model.step", "dmlc.device.epoch_turnover"} <= set(found)
    # consumer, staging and transfer threads each have a line of their own
    assert lines_with_spans >= 3
    assert capture is not None
    for name, evs in found.items():
        for e in evs:
            assert capture[0] <= e.start_ns and \
                e.start_ns + e.duration_ns <= capture[1], name
    assert len(found["dmlc.device.epoch_turnover"]) == 2
    assert len(found["dmlc.model.step"]) == 6
    # the args ride along as the event's stats
    assert dict(found["dmlc.model.step"][0].stats).get("built") == 1
    assert {dict(e.stats).get("rows") for e in
            found["dmlc.device.stage"]} >= {256, 188}


def test_model_step_counts_builds_and_times_every_dispatch(tmp_path):
    path = write_libsvm(tmp_path / "s.libsvm", rows=700)
    learner = _learner("linear", None)
    params = learner.init()
    builds = telemetry.counter("model_step_builds_total",
                               {"model": "LinearLearner"})
    seen = []
    with DeviceRowBlockIter(path, batch_rows=256, layout="csr",
                            min_nnz_bucket=128) as it:
        for _ in range(2):
            for batch in it:
                params, _ = learner.step(params, batch)
                seen.append(builds.value)
            it.before_first()
    # 256, 256 rows pad to one signature, and the epoch's short last batch
    # (188 rows, 1,504 entries: its own rung is the one below) is sent at
    # the rungs of the batch before it (device_iter.tail_rung): one build,
    # on the first step, never at an epoch's end or in the second epoch
    assert seen == [1, 1, 1, 1, 1, 1]
    assert _hist("model_step_dispatch_us") == 6
    assert telemetry.counter("device_tail_batches_total").value == 2
    steps = [s for s in telemetry.spans() if s["name"] == "model.step"]
    assert [s["args"]["built"] for s in steps] == [1, 0, 0, 0, 0, 0]
    # each new batch signature builds once more, a repeat never
    sigs = set()
    with DeviceRowBlockIter(path, batch_rows=128, layout="csr",
                            min_nnz_bucket=128) as it:
        for batch in it:
            sigs.add(tuple(sorted((k, v.shape)
                                  for k, v in batch.tree().items())))
            params, _ = learner.step(params, batch)
            assert builds.value == 1 + len(sigs)
    assert len(sigs) >= 1
    assert telemetry.counter("model_step_builds_total",
                             {"model": "FMLearner"}).value == 0


@pytest.mark.parametrize("prefetch", [0, 2])
def test_turnover_and_first_wait_count_once_per_before_first(tmp_path,
                                                             prefetch):
    path = write_libsvm(tmp_path / "t.libsvm", rows=700)
    with DeviceRowBlockIter(path, batch_rows=256, layout="csr",
                            min_nnz_bucket=128, prefetch=prefetch) as it:
        assert sum(b.total_rows for b in it) == 700
        assert _hist("device_turnover_us") == 0
        assert _hist("device_first_batch_wait_us") == 1
        for epoch in (1, 2, 3):
            it.before_first()
            assert _hist("device_turnover_us") == epoch
            assert _hist("device_first_batch_wait_us") == epoch
            assert sum(b.total_rows for b in it) == 700
            assert _hist("device_first_batch_wait_us") == epoch + 1
    spans = telemetry.spans()
    turns = [s for s in spans if s["name"] == "device.epoch_turnover"]
    assert [s["args"]["epoch"] for s in turns] == [1, 2, 3]
    firsts = [s for s in spans if s["name"] == "device.wait"
              and s.get("args", {}).get("first") == 1]
    assert len(firsts) == 4
    if prefetch:  # every wait is a span there; one per epoch is the first
        assert _hist("device_wait_us") > 4


@pytest.mark.parametrize("mesh_devices", [0, 2], ids=["nomesh", "mesh"])
@pytest.mark.parametrize("model", ["fm", "linear"])
def test_jitted_step_carries_the_named_scopes(tmp_path, model, mesh_devices):
    from dmlc_core_tpu.tpu import data_mesh
    mesh = data_mesh(mesh_devices) if mesh_devices else None
    path = write_libsvm(tmp_path / "n.libsvm", rows=256)
    learner = _learner(model, mesh)
    params = learner.init()
    with DeviceRowBlockIter(path, batch_rows=256, layout="csr", mesh=mesh,
                            min_nnz_bucket=128) as it:
        batch = next(iter(it))
    tree = batch.tree()
    step = learner._build_step(batch.rows_per_shard,
                               tuple(sorted(tree.keys())))
    text = step.lower(params, tree).as_text(debug_info=True)
    want = {"dp.unpack", "dp.loss_grad", "dp.apply"}
    want |= ({"fm.linear", "fm.gather", "fm.interaction"} if model == "fm"
             else {"linear.margin"})
    if mesh is not None:
        want.add("dp.allreduce")
    for scope in sorted(want):
        assert scope in text, scope
    if mesh is None:
        assert "dp.allreduce" not in text
    # the backward of a model scope nests under dp.loss_grad
    inner = "fm.gather" if model == "fm" else "linear.margin"
    if model == "fm":
        # row form (a CSR batch, with or without a mesh): the gathers are
        # not differentiated, the rest of the margin is, and the gradient's
        # rows are scattered into the tables under dp.apply; on a mesh the
        # shards' rows are gathered under dp.allreduce first
        assert f"transpose(jvp({inner}))" not in text
        # (the margin's backward is hand-written: its operations carry
        # the forward's scopes under the transposed dp.loss_grad)
        assert ("dp.loss_grad/transpose(dp.loss_grad)/jvp(fm.interaction)"
                in text)
        assert "dp.loss_grad/transpose(dp.loss_grad)/jvp(fm.expand)" in text
        assert "dp.apply/scatter-add" in text
        assert ("dp.allreduce/all_gather" in text) == (mesh is not None)
    else:
        assert f"dp.loss_grad/transpose(jvp({inner}))" in text
    # predict carries its own scope
    learner.predict(params, batch)
    fwd = next(iter(learner._fwd_fn.values()))
    assert f"{model}.predict" in fwd.lower(params, tree).as_text(
        debug_info=True)


def test_telemetry_off_times_nothing_and_steps_bit_identically(
        tmp_path, monkeypatch):
    path = write_libsvm(tmp_path / "o.libsvm", rows=700)
    made = []

    class Recording(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(telemetry, "_annotation_cls", Recording)

    def run():
        learner = _learner("fm", None)
        params = learner.init(3)
        losses = []
        with DeviceRowBlockIter(path, batch_rows=256, layout="csr",
                                min_nnz_bucket=128) as it:
            for batch in it:
                params, loss = learner.step(params, batch)
                losses.append(np.asarray(loss))
            it.before_first()
        return jax.tree.map(np.asarray, params), losses

    p_on, l_on = run()
    assert "dmlc.model.step" in made and "dmlc.device.wait" in made
    assert telemetry.spans()
    telemetry.reset()
    made.clear()
    telemetry.enable(False)
    p_off, l_off = run()
    assert made == [] and telemetry.spans() == []
    for name in ("model_step_dispatch_us", "device_turnover_us",
                 "device_first_batch_wait_us", "device_wait_us",
                 "device_stage_us"):
        assert _hist(name) == 0, name
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        assert a.tobytes() == b.tobytes()
    assert [x.tobytes() for x in l_on] == [x.tobytes() for x in l_off]


def test_jax_profiler_capture_raises_when_it_cannot_start(tmp_path,
                                                          monkeypatch):
    # the caller asked for a trace: a run without one is not that run
    monkeypatch.setenv("DMLC_JAX_PROFILE", str(tmp_path / "xprof"))

    def refuse(*a, **kw):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    ran = []
    with pytest.raises(RuntimeError, match="profiler busy"):
        with jax_profiler_capture():
            ran.append(1)
    assert not ran


def test_jax_profiler_capture_noop_without_env(monkeypatch):
    monkeypatch.delenv("DMLC_JAX_PROFILE", raising=False)
    with jax_profiler_capture() as started:
        assert started is False


# -- the hold between batches (ISSUE 36) ---------------------------------------
HOLD_BATCH = 64


def _consume(path, act, **kw):
    """Every batch of the file through the iterator, ``act(i)`` as the
    consumer's hold of batch ``i``; returns the loop's wall seconds and the
    seconds ``act`` took by the test's own clock."""
    kw.setdefault("batch_rows", HOLD_BATCH)
    kw.setdefault("min_nnz_bucket", 128)
    kw.setdefault("layout", "csr")
    held = 0.0
    with DeviceRowBlockIter(path, **kw) as it:
        t0 = time.perf_counter()
        for i, _batch in enumerate(it):
            t = time.perf_counter()
            act(i)
            held += time.perf_counter() - t
        return time.perf_counter() - t0, held


def _hist_sum(name):
    return sum(h["sum"] for h in telemetry.snapshot()["histograms"]
               if h["name"] == name)


def _long_holds():
    return [e for e in telemetry.events() if e["event"] == "long_hold"]


def _one_long(i, long_act, at=20, short_s=0.01):
    """Twenty short holds, then one made long by ``long_act``."""
    if i == at:
        long_act()
    else:
        time.sleep(short_s)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_hold_and_excess_are_observed_every_batch(tmp_path, prefetch):
    batches = 20
    path = write_libsvm(tmp_path / "h.libsvm", rows=HOLD_BATCH * batches)
    _, held = _consume(path, lambda i: time.sleep(0.03), prefetch=prefetch)
    assert _hist("device_hold_us") == batches
    assert _hist("device_hold_excess_us") == batches
    spans = telemetry.spans()
    holds = [s for s in spans if s["name"] == "device.hold"]
    assert len(holds) == batches
    assert all(0 <= s["args"]["cpu_us"] <= s["dur"] + 1000 for s in holds)
    # the hold is what the consumer did between two batches, no more
    hold_s = _hist_sum("device_hold_us") / 1e6
    assert held <= hold_s <= held * 1.02, (held, hold_s)
    # no hold ran long: the excess is the jitter of a 30 ms sleep
    assert _hist_sum("device_hold_excess_us") < 0.1 * hold_s * 1e6
    assert _long_holds() == []
    if prefetch:
        # wait + hold is the consumer's whole time, from its first asking
        # for a batch to its being told there is none left
        waits = [s for s in spans if s["name"] == "device.wait"]
        assert len(waits) == _hist("device_wait_us") == batches + 1
        wall_us = waits[-1]["ts"] + waits[-1]["dur"] - waits[0]["ts"]
        covered = _hist_sum("device_wait_us") + hold_s * 1e6
        assert wall_us * 0.98 <= covered <= wall_us, (wall_us, covered)


def test_an_abandoned_generator_records_no_hold(tmp_path):
    path = write_libsvm(tmp_path / "g.libsvm", rows=HOLD_BATCH * 8)
    with DeviceRowBlockIter(path, batch_rows=HOLD_BATCH, layout="csr",
                            min_nnz_bucket=128) as it:
        gen = iter(it)
        next(gen)
        next(gen)           # ends the first hold, begins the second
        assert telemetry._running_holds
        gen.close()         # the consumer walks away holding a batch
        assert not telemetry._running_holds
    assert _hist("device_hold_us") == 1


def test_one_long_hold_is_counted_once_recorded_and_logged(tmp_path, caplog):
    path = write_libsvm(tmp_path / "l.libsvm", rows=HOLD_BATCH * 30)
    with caplog.at_level(logging.WARNING, logger="dmlc_core_tpu"):
        _consume(path, lambda i: _one_long(i, lambda: time.sleep(0.3)))
    snap = telemetry.snapshot()
    assert [c["value"] for c in snap["counters"]
            if c["name"] == "device_long_holds_total"] == [1]
    recs = _long_holds()
    assert len(recs) == 1
    rec = recs[0]
    assert {"verdict", "hold_us", "median_us", "cpu_us", "pulse_py_late_us",
            "pulse_native_late_us", "compile_us", "step_builds", "utime_us",
            "stime_us", "nvcsw", "nivcsw", "majflt", "put_spans", "lanes",
            "stack", "puts"} <= set(rec)
    assert 290_000 <= rec["hold_us"] <= 600_000
    assert 9_000 <= rec["median_us"] <= 30_000
    assert rec["verdict"] == "consumer_waiting"
    assert rec["compile_us"] == 0 and rec["step_builds"] == 0
    assert {"n", "median_us", "block_n", "block_median_us",
            "usual_us"} == set(rec["put_spans"])
    # the pulse saw the hold run past its limit and took the consumer's
    # stack: the test's own sleep, innermost first
    assert rec["stack"] and "test_device_observability.py" in rec["stack"][0]
    assert len(rec["stack"]) <= 5
    # the record is an event of every snapshot, and of the jsonl export
    assert any(e["event"] == "long_hold" for e in snap["events"])
    assert '"long_hold"' in telemetry.events_jsonl()
    # and one WARNING line on the package's logger
    lines = [r for r in caplog.records if r.name == "dmlc_core_tpu"
             and r.levelno == logging.WARNING
             and r.getMessage().startswith("long hold:")]
    assert len(lines) == 1
    assert "consumer_waiting" in lines[0].getMessage()
    # the excess histogram holds the stall's weight, the count the holds'
    assert _hist("device_hold_excess_us") == 30
    assert 250_000 <= _hist_sum("device_hold_excess_us") <= 700_000


def test_long_hold_dumps_the_flight_recorder(tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_TRACE_DUMP", str(tmp_path / "dump"))
    path = write_libsvm(tmp_path / "d.libsvm", rows=HOLD_BATCH * 30)
    _consume(path, lambda i: _one_long(i, lambda: time.sleep(0.2)))
    dumps = sorted((tmp_path / "dump").glob("flight_*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "long-hold: consumer_waiting"
    assert any(e["event"] == "long_hold" for e in doc["metrics"]["events"])


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _hold_the_gil(seconds):
    """A helper thread that keeps the interpreter lock through a C call
    (``ctypes.PyDLL`` does not release it) while the consumer sleeps."""
    usleep = ctypes.PyDLL(None).usleep
    th = threading.Thread(target=usleep, args=(int(seconds * 1e6),))
    th.start()
    time.sleep(0.02)   # gives the lock up; wants it back after 20 ms
    th.join()


def _compile_something():
    """A step whose shape is new: a jitted function never seen before (the
    constant keeps it out of any compile cache)."""
    salt = random.random()

    @jax.jit
    def f(x):
        for _ in range(120):
            x = jax.numpy.sin(x) * salt + x[::-1]
        return x

    jax.block_until_ready(f(np.arange(17, dtype=np.float32)))


@pytest.mark.parametrize("cause,verdict", [
    (lambda: time.sleep(0.3), "consumer_waiting"),
    (lambda: _spin(0.3), "consumer_busy"),
    (lambda: _hold_the_gil(0.3), "gil_held"),
    (_compile_something, "compile"),
], ids=["sleep", "spin", "gil", "compile"])
def test_long_hold_verdict_follows_the_made_cause(tmp_path, cause, verdict):
    path = write_libsvm(tmp_path / "v.libsvm", rows=HOLD_BATCH * 30)
    _consume(path, lambda i: _one_long(i, cause))
    recs = _long_holds()
    assert [r["verdict"] for r in recs] == [verdict], recs
    rec = recs[0]
    excess = rec["hold_us"] - rec["median_us"]
    if verdict == "gil_held":
        # the Python pulse could not wake, the native one could
        assert rec["pulse_py_late_us"] >= excess / 2
        assert rec["pulse_native_late_us"] < excess / 2
        assert rec["cpu_us"] < rec["hold_us"] / 2
    elif verdict == "compile":
        assert rec["compile_us"] >= excess / 2
    else:
        assert rec["pulse_py_late_us"] < excess / 2
        assert rec["compile_us"] == 0
        busy = rec["cpu_us"] >= rec["hold_us"] / 2
        assert busy == (verdict == "consumer_busy")
    assert ("puts" in rec) == (verdict == "consumer_waiting")


def test_long_hold_of_a_stopped_process_reads_host_frozen(tmp_path):
    path = write_libsvm(tmp_path / "f.libsvm", rows=HOLD_BATCH * 45)
    worker = os.path.join(REPO, "tests", "hold_worker.py")
    proc = subprocess.Popen([sys.executable, worker, REPO, path],
                            stdout=subprocess.PIPE, text=True,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.05)
        proc.send_signal(signal.SIGSTOP)   # every thread stops, mid-hold
        time.sleep(0.3)
        proc.send_signal(signal.SIGCONT)
        out, _ = proc.communicate(timeout=60)
    finally:
        proc.kill()
    assert proc.returncode == 0
    recs = json.loads(out.strip().splitlines()[-1])
    assert [r["verdict"] for r in recs] == ["host_frozen"], recs
    rec = recs[0]
    assert rec["hold_us"] >= 300_000
    half = (rec["hold_us"] - rec["median_us"]) / 2
    assert rec["pulse_py_late_us"] >= half
    assert rec["pulse_native_late_us"] >= half


@pytest.mark.parametrize("prefetch,hang,want", [
    (2, False, "puts_flowing"), (2, True, "puts_blocked"),
    (0, False, "puts_idle")], ids=["flowing", "blocked", "idle"])
def test_waiting_consumer_says_what_the_puts_did(tmp_path, monkeypatch,
                                                 prefetch, hang, want):
    path = write_libsvm(tmp_path / "p.libsvm", rows=HOLD_BATCH * 30)
    puts = []
    real = DeviceRowBlockIter._copy_put

    def slow_put(self, tree):
        # a put takes its usual 3 ms; from the long hold on, where the
        # test asks for it, the path to the device stands still
        puts.append(1)
        time.sleep(0.5 if hang and len(puts) > 22 else 0.003)
        return real(self, tree)

    monkeypatch.setattr(DeviceRowBlockIter, "_copy_put", slow_put)
    monkeypatch.setenv("DMLC_DEVICE_ZERO_COPY", "0")
    _consume(path, lambda i: _one_long(i, lambda: time.sleep(0.3)),
             prefetch=prefetch)
    recs = _long_holds()
    assert recs and recs[0]["verdict"] == "consumer_waiting"
    assert recs[0]["puts"] == want, recs[0]
    if want == "puts_flowing":
        assert recs[0]["put_spans"]["n"] >= 1
        assert any("device.put" in names
                   for names in recs[0]["lanes"].values())


def test_long_hold_records_are_capped_at_one_a_second(tmp_path):
    path = write_libsvm(tmp_path / "c.libsvm", rows=HOLD_BATCH * 30)
    _consume(path, lambda i: time.sleep(0.12 if i in (20, 22, 24)
                                        else 0.005))
    count = [c["value"] for c in telemetry.snapshot()["counters"]
             if c["name"] == "device_long_holds_total"]
    assert count == [3]              # every long hold is counted
    assert len(_long_holds()) == 1   # one record in a second


def test_telemetry_off_holds_and_pulses_nothing(tmp_path):
    path = write_libsvm(tmp_path / "z.libsvm", rows=HOLD_BATCH * 30)
    telemetry.enable(False)
    for prefetch in (0, 2):
        _consume(path, lambda i: _one_long(i, lambda: time.sleep(0.12),
                                           short_s=0.002),
                 prefetch=prefetch)
    assert not [t for t in threading.enumerate() if t.name == "dmlc-pulse"]
    observed = {h["name"]: h["count"]
                for h in telemetry.snapshot(native=True)["histograms"]}
    for name in ("device_hold_us", "device_hold_excess_us",
                 "pulse_py_late_us", "pulse_native_late_us"):
        assert observed.get(name, 0) == 0, name
    assert telemetry.spans() == [] and _long_holds() == []
    assert not telemetry._running_holds


def test_two_iterators_share_one_pair_of_pulses(tmp_path):
    path = write_libsvm(tmp_path / "t.libsvm", rows=HOLD_BATCH * 12)
    kw = dict(batch_rows=HOLD_BATCH, layout="csr", min_nnz_bucket=128)

    def native_ticks():
        return sum(h["count"]
                   for h in telemetry.snapshot(native=True)["histograms"]
                   if h["name"] == "pulse_native_late_us")

    with DeviceRowBlockIter(path, **kw) as a, \
            DeviceRowBlockIter(path, prefetch=0, **kw) as b:
        ga, gb = iter(a), iter(b)
        next(ga), next(gb)
        assert len([t for t in threading.enumerate()
                    if t.name == "dmlc-pulse"]) == 1
        n0 = native_ticks()
        time.sleep(0.3)
        assert 3 <= native_ticks() - n0 <= 17   # one native thread, not two
        telemetry.enable(False)
        assert not [t for t in threading.enumerate()
                    if t.name == "dmlc-pulse"]
        n1 = native_ticks()
        time.sleep(0.05)
        assert native_ticks() == n1
        ga.close(), gb.close()
