"""Tests of the benchmark itself: the result line, the trace reduction, the
generator, the work count, the plain reference against the program, the
control and the planted faults, the data-driven loading, the refusal to run
without a chip, and the compile-only sizing of both configurations.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The scenarios that drive ``run.run_cell`` run once, in a child process, from
a temporary copy of ``BENCHMARK.json`` + ``benchmarks/`` to which a tiny
configuration, two traffic mixes and their entries were ADDED as files: no
file of the copy is edited but ``BENCHMARK.json``, which gains entries.
"""

from __future__ import annotations

import copy
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

from harness import check, datagen, result_line, trace, work  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)


def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# -- BENCHMARK.json against the contract's shape --------------------------------

def test_benchmark_json_shape():
    import re
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    cells = [w["name"] for w in s["workloads"]]
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert 1 <= s["run_seconds"] <= 51
    for c in s["configs"]:
        assert name.match(c["name"]) and os.path.exists(
            os.path.join(REPO, c["file"]))
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert set(c["reduced"]) <= set(load("configs", c["name"] + ".json"))
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)
        how = load("metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           how["reader"] + ".py"))
    for cell in cells:
        assert result_line.expected_metrics(s, cell, True)
        assert len(result_line.expected_metrics(s, cell, False)) >= 2


# -- the result line ---------------------------------------------------------------

def good_line(traced: bool):
    s = spec()
    cell = s["workloads"][0]["name"]
    metrics = {n: {"value": 1.5, "unit": m["unit"]} for n, m in
               result_line.expected_metrics(s, cell, traced).items()}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 10962958336}
    breakdown = None
    if traced:
        device.update(window_s=3.04, busy_s=2.94)
        breakdown = {"device_ops": [["%fusion.8", 1.07]],
                     "idle_gaps": [["bench.loss_sync", 0.09]]}
    return s, cell, result_line.build(True, 100, 0, metrics, device,
                                      breakdown, {}, {"loss_gap": {
                                          "value": 0.0, "limit": 1e-5}})


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    s, cell, line = good_line(traced)
    result_line.validate(line, s, cell, traced)
    again = json.loads(result_line.dumps(line))
    assert list(again)[-1] == "checks"
    result_line.validate(again, s, cell, traced)


def _drop(path):
    def f(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        del d[path[-1]]
    return f


def _set(path, value):
    def f(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        d[path[-1]] = value
    return f


BAD_LINES = {
    "missing_window_s": (True, _drop(["device", "window_s"])),
    "missing_busy_s": (True, _drop(["device", "busy_s"])),
    "busy_zero": (True, _set(["device", "busy_s"], 0.0)),
    "busy_over_window": (True, _set(["device", "busy_s"], 3.5)),
    "metric_missing_traced": (True, _drop(["metrics", "device_idle_share"])),
    "metric_missing": (False, _drop(["metrics", "rows_per_s"])),
    "metric_zero": (False, _set(["metrics", "rows_per_s", "value"], 0.0)),
    "metric_nan": (False, _set(["metrics", "setup_s", "value"],
                               float("nan"))),
    "wrong_unit": (False, _set(["metrics", "rows_per_s", "unit"], "rows")),
    "foreign_metric": (False, _set(["metrics", "tokens_per_s"],
                                   {"value": 1.0, "unit": "tokens/s"})),
    "no_device": (False, _drop(["device"])),
    "no_memory_peak": (False, _drop(["device", "memory_peak_bytes"])),
    "no_correct": (False, _drop(["correct"])),
    "failed_over_attempted": (False, _set(["failed"], 101)),
    "roofline_over_105": (True, _set(["metrics", "fm_step_roofline",
                                      "value"], 140.0)),
    "breakdown_too_long": (True, _set(["breakdown", "device_ops"],
                                      [["op", 0.1]] * 11)),
    "breakdown_untraced": (False, _set(["breakdown"], {"device_ops": [],
                                                       "idle_gaps": []})),
}


@pytest.mark.parametrize("case", sorted(BAD_LINES))
def test_bad_line_is_refused(case):
    traced, damage = BAD_LINES[case]
    s, cell, line = good_line(traced)
    damage(line)
    with pytest.raises(result_line.LineError):
        result_line.validate(line, s, cell, traced)


# -- the trace reduction on a trace recorded on the chip ---------------------------

@pytest.fixture(scope="module")
def recorded():
    return load("tests", "recorded_trace.json")


def test_recorded_trace_busy_is_a_union_inside_the_window(recorded):
    lo, hi = trace.window_of(recorded)
    ops = recorded["planes"]["/device:TPU:0"]["XLA Ops"]
    b = trace.busy(recorded, 1)
    assert b["window_s"] == pytest.approx((hi - lo) / 1e9)
    # four steps of about 75 ms, a couple of ms apart
    assert 0 < b["busy_s"] < b["window_s"]
    assert b["busy_s"] == pytest.approx(0.3012, abs=2e-4)
    inside = sum(min(s + d, hi) - max(s, lo) for _, s, d in ops
                 if min(s + d, hi) > max(s, lo))
    assert b["busy_s"] <= inside / 1e9 + 1e-12
    # summing the overlapping lines is the fault of PR 22: it passes the window
    lines = recorded["planes"]["/device:TPU:0"]
    summed = sum(d for evs in lines.values() for _, s, d in evs
                 if lo <= s and s + d <= hi) / 1e9
    assert summed > b["window_s"]


def test_recorded_trace_module_ops_and_gaps(recorded):
    mod = trace.module_time(recorded, "sharded_step")
    assert mod["count"] == 4
    assert mod["seconds"] / mod["count"] == pytest.approx(0.0753, abs=5e-4)
    assert trace.module_time(recorded, "no_such_module")["count"] == 0
    bd = trace.breakdown(recorded)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("%fusion.8")
    seconds = [s for _, s in bd["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert bd["idle_gaps"][0][0] == "bench.loss_sync"
    idle = sum(s for _, s in bd["idle_gaps"])
    b = trace.busy(recorded, 1)
    assert idle == pytest.approx(b["window_s"] - b["busy_s"], rel=1e-6)


def test_inventory_lists_what_to_read_by_hand(recorded):
    inv = trace.inventory(recorded)
    assert set(inv["/device:TPU:0"]) >= {"XLA Ops", "XLA Modules", "Steps"}
    count, names = inv["/device:TPU:0"]["XLA Modules"]
    assert count == 5 and names[0].startswith("jit_sharded_step(")
    assert "bench.trace_window" in inv["/host:CPU"]["python3"][1]


def test_trace_faults_are_errors(recorded):
    no_ops = copy.deepcopy(recorded)
    no_ops["planes"]["/device:TPU:0"]["XLA Ops"] = []
    with pytest.raises(trace.TraceError):
        trace.busy(no_ops, 1)
    no_window = copy.deepcopy(recorded)
    no_window["planes"]["/host:CPU"]["python3"] = [
        e for e in no_window["planes"]["/host:CPU"]["python3"]
        if e[0] != trace.WINDOW_EVENT]
    with pytest.raises(trace.TraceError):
        trace.busy(no_window, 1)
    with pytest.raises(trace.TraceError):
        trace.busy(recorded, 4)  # the cell asks for more chips than traced


# -- the generator -------------------------------------------------------------------

@pytest.mark.parametrize("config", ["kdd2012-fm", "kdd2010b-fm"])
def test_generator_is_the_seed_and_keeps_the_published_space(config):
    cfg = load("configs", config + ".json")
    tab = datagen.field_table(cfg["data"])
    assert tab["num_features"] == cfg["num_features"] \
        == cfg["published"]["num_features"]
    a = datagen.make_block(cfg["data"], 2 ** 31 + 7, 3, 4096)
    b = datagen.make_block(cfg["data"], 2 ** 31 + 7, 3, 4096)
    c = datagen.make_block(cfg["data"], 2 ** 31 + 8, 3, 4096)
    assert all(np.array_equal(getattr(a, k), getattr(b, k))
               for k in ("label", "lens", "col", "field", "val"))
    assert not np.array_equal(a.col, c.col)
    assert a.rows == 4096 and a.lens.sum() == a.col.size
    assert 0 <= a.col.min() and a.col.max() < cfg["num_features"]
    assert a.lens.mean() == pytest.approx(
        cfg["published"]["nonzeros_per_row"], rel=0.03)
    assert set(np.unique(a.label)) == {0.0, 1.0}
    ends = np.cumsum(a.lens)
    for r in (0, 17, 4095):  # ids are distinct within a row
        ids = a.col[ends[r] - a.lens[r]:ends[r]]
        assert np.unique(ids).size == ids.size


@pytest.mark.parametrize("fmt", ["libfm", "libsvm"])
def test_text_is_what_the_programs_parser_reads_back(tmp_path, fmt):
    from dmlc_core_tpu.io import NativeParser
    cfg = load("configs", "kdd2010b-fm.json")
    rows = datagen.BLOCK_ROWS + 1000  # two blocks, the second short
    path = str(tmp_path / ("rows." + fmt))
    written, lens = datagen.write_text(path, cfg["data"], 12345, rows, fmt,
                                       threads=3)
    assert written == os.path.getsize(path)
    want = datagen.first_rows(cfg["data"], 12345, rows, rows)
    assert want.rows == rows and np.array_equal(lens, want.lens)
    label, lens, col, val = [], [], [], []
    with NativeParser(path, fmt=fmt) as p:
        for b in p:
            label.append(np.array(b.label))
            lens.append(np.diff(b.offset))
            col.append(np.array(b.index))
            val.append(np.ones(b.nnz, np.float32) if b.value is None
                       else np.array(b.value))
    assert np.concatenate(label).size == rows  # exact row count
    assert np.array_equal(np.concatenate(label), want.label)
    assert np.array_equal(np.concatenate(lens), want.lens)
    assert np.array_equal(np.concatenate(col).astype(np.int64), want.col)
    assert np.array_equal(np.concatenate(val), want.val)
    head = datagen.first_rows(cfg["data"], 12345, 300, rows)
    assert np.array_equal(head.col, want.slice_rows(0, 300).col)


# -- the work count and the peaks --------------------------------------------------------

def test_fm_step_bytes_follow_the_batch_not_the_table():
    w = work.fm_sgd_step(nnz=180224, rows=16384, rank=16, batch_bytes=4.4e6)
    assert w["bytes"] == 2 * 180224 * 17 * 4 + 4.4e6
    assert "num_features" not in work.fm_sgd_step.__code__.co_varnames
    least = work.least_seconds(w, peaks_for("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(w["bytes"] / 819e9)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


# -- the comparison ----------------------------------------------------------------------

def test_gaps_take_the_worst_leaf_against_the_median_leaf():
    ref = check.Readings([0.7, 0.69, 0.68], [0.2, 0.3, 1e-9], [0.05, 0.08,
                                                                0.04])
    got = check.Readings([0.7, 0.69, 0.68], [0.2, 0.3, 5e-9], [0.05, 0.08,
                                                                0.08])
    g = check.gaps(got, ref)
    assert g["loss_gap"] == 0
    # the all-but-zero leaf is measured against the median leaf's norm ...
    assert g["grad_norm_gap"] == pytest.approx(4e-9 / 0.2)
    # ... and left out of the change: its reference gradient is under a
    # thousandth of the median leaf's
    assert g["change_norm_gap"] == 0
    verdict = check.judge({"loss_gap": 0.0}, {"loss_gap": 1e-5,
                                               "grad_norm_gap": 1e-4})
    assert not verdict["ok"]  # a number with a limit that was not read
    assert not check.judge({"loss_gap": float("nan")},
                           {"loss_gap": 1e-5})["ok"]


# -- the harness end to end, off the chip, from a copy that only gained files -----------------

SCENARIOS = r"""
import json, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import run
from faults import FAULTS
from harness import cells, check, result_line
out = {}
def go(tag, cell, faults=None):
    line = run.run_cell(cell, 2**31 + 77, 0.6, False, require_chip=False,
                        faults=faults)
    out[tag] = line
go("libfm", "tiny-fm.tinylibfm")
go("crec", "tiny-fm.tinycrec")
for name, fault in FAULTS.items():
    go(name, "tiny-fm.tinylibfm", fault)
spec = cells.load_spec()
cell = cells.load_cell(spec, "tiny-fm.tinylibfm")
runner = cells.load_module("runners", "fm")
s = runner.Session(cell, 5, 1)
ref = s.reference_readings()
out["control"] = check.judge(check.gaps(s.reference_readings("bfloat16"), ref),
                             cell["config_file"]["limits"])
try:
    result_line.validate(out["libfm"], spec, "tiny-fm.tinylibfm", False)
    out["off_chip_line"] = "passes"
except result_line.LineError as e:
    out["off_chip_line"] = str(e)
print("SCENARIOS " + json.dumps(out))
"""


def tiny_copy(dst):
    """A copy of the benchmark that gains a configuration, two traffic mixes
    and one metric as new files, and their entries in BENCHMARK.json."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {}
    for root, _, files in os.walk(dst):
        for f in files:
            p = os.path.join(root, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    cfg = load("configs", "kdd2012-fm.json")
    cfg.update(num_features=5000, fm_rank=4, batch_rows=64)
    cfg["data"]["fields"] = [{"name": f"f{i}", "cardinality": 500,
                              "present": 1.0 if i % 2 else 0.6}
                             for i in range(10)]
    s = spec()
    s["configs"].append({"name": "tiny-fm", "source": "a test",
                         "file": "benchmarks/configs/tiny-fm.json",
                         "reduced": [], "why": "a test"})
    with open(os.path.join(dst, "benchmarks/configs/tiny-fm.json"),
              "w") as f:
        json.dump(cfg, f)
    for base, name in (("libfm", "tinylibfm"), ("crec", "tinycrec")):
        t = load("traffic", base + ".json")
        t["epoch_batches"] = 8
        with open(os.path.join(dst, f"benchmarks/traffic/{name}.json"),
                  "w") as f:
            json.dump(t, f)
        cell = "tiny-fm." + name
        s["workloads"].append({"name": cell, "config": "tiny-fm",
                               "traffic": name, "chips": 1, "why": "a test"})
        for m in s["per_layer"]:
            if name == "tinylibfm" or m["name"] != "parse.us_per_krow":
                m["workloads"].append(cell)
    with open(os.path.join(dst, "benchmarks/metrics/steps_p50.json"),
              "w") as f:
        json.dump({"reader": "step_percentile", "percentile": 50}, f)
    s["per_layer"].append({"name": "steps_p50", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "step loop", "moves": "rows_per_s",
                           "workloads": ["tiny-fm.tinylibfm"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(s, f)
    return before


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy"))
    before = tiny_copy(dst)
    r = subprocess.run([sys.executable, "-c", SCENARIOS], cwd=dst, env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    for p, blob in before.items():  # files were added; none was edited
        with open(p, "rb") as fh:
            assert fh.read() == blob, p
    last = [l for l in r.stdout.splitlines() if l.startswith("SCENARIOS ")]
    return json.loads(last[-1][len("SCENARIOS "):])


@pytest.mark.parametrize("cell", ["libfm", "crec"])
def test_program_agrees_with_the_plain_reference(scenarios, cell):
    line = scenarios[cell]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 10
    c = line["checks"]
    assert c["loss_gap"]["value"] < 1e-6
    assert c["grad_norm_gap"]["value"] < 1e-5
    assert c["change_norm_gap"]["value"] < 1e-5
    assert c["epoch_rows_gap"]["value"] == 0  # every epoch, every row once
    assert line["notes"]["epochs_finished"] >= 2
    assert c["compiles_in_window"]["value"] == 0
    assert list(line)[-1] == "checks"


def test_a_cell_added_as_files_loads_and_runs(scenarios):
    assert scenarios["libfm"]["metrics"]["rows_per_s"]["value"] > 0
    assert scenarios["crec"]["notes"]["setup"]["crec_bytes"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "drop_last_token"])
def test_a_broken_timed_path_is_not_correct(scenarios, fault):
    line = scenarios[fault]
    assert line["correct"] is False
    over = [k for k, c in line["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over, line["checks"]
    if fault == "state_unchanged":
        assert line["checks"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0)


def test_the_control_in_bfloat16_is_not_correct(scenarios):
    assert scenarios["control"]["ok"] is False


def test_off_chip_line_carries_no_device_peak(scenarios):
    # the CPU has no memory_peak_bytes: the validator refuses such a line,
    # and run.py's command line never gets that far without a chip
    assert "memory_peak_bytes" in scenarios["off_chip_line"]


def test_command_line_refuses_to_run_without_a_chip():
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "kdd2012-fm.libfm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode == 3
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_bare_directory_gives_no_result(tmp_path):
    shutil.copytree(BENCH, str(tmp_path / "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), str(tmp_path))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    code = ("import sys; sys.path.insert(0, 'benchmarks'); import run; "
            "run.run_cell('kdd2012-fm.libfm', 1, 1.0, False, "
            "require_chip=False)")
    r = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 5
    assert r.stdout.strip() == ""


# -- compile-only sizing of both configurations' steps for the v5e -------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("config,bucket", [("kdd2012-fm", 262144),
                                           ("kdd2010b-fm", 524288)])
def test_step_fits_a_v5e_chip_and_fills_a_quarter(topo, no_compile_cache,
                                                  config, bucket):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from dmlc_core_tpu.models import FMLearner
    from dmlc_core_tpu.models.fm import FMParams
    cfg = load("configs", config + ".json")
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    learner = FMLearner(num_features=cfg["num_features"], k=cfg["fm_rank"],
                        mesh=mesh, objective=cfg["objective"],
                        learning_rate=cfg["learning_rate"])
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    F, K, R = cfg["num_features"], cfg["fm_rank"], cfg["batch_rows"]
    params = FMParams(jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F,), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F, K), jnp.float32, sharding=rep))
    planes = 4 if config == "kdd2012-fm" else 3  # libfm carries fields
    tree = {"aux": jax.ShapeDtypeStruct((1, 3, R), jnp.int32, sharding=row),
            "big": jax.ShapeDtypeStruct((1, planes, bucket), jnp.int32,
                                        sharding=row)}
    compiled = learner._build_step(R, ("aux", "big")).lower(
        params, tree).compile()
    m = compiled.memory_analysis()
    table = F * (K + 1) * 4
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{config}: arguments {m.argument_size_in_bytes} outputs "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"alias {m.alias_size_in_bytes}")
    assert m.argument_size_in_bytes >= table
    assert 0.25 * 16e9 < peak < 16e9
