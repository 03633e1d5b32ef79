"""Tests of what PR 28 adds to the benchmark for the four-chip cell
``kdd2012-fm-dp4.libfm``: the replica's work count, the link table, the two
new readers and the ``dp.allreduce`` metric file on a sample cut from a chip
trace of that PR, the configuration against the one it deploys, the runner's
``replica_gap`` and the dropped-shard fault (in a child process on four host
devices), and the entry that waits in ``proposed_per_layer_dp.json``.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

``recorded_dp4.json`` (``trace_cut_dp.py`` of the cell's traced run, my chip
run, PR 28): three whole steps from the middle of the traced window on all
four chips, the window narrowed to start 40% into the step before and to end
25% into the step after. Hand-checked from the file's own numbers:

- the step program takes 139.99, 139.16 and 140.79 ms on the first chip (the
  other three within 0.1 ms of it, but for the third chip's 139.98 in the
  last): 139.98 ms a step;
- ``dp.allreduce`` holds three operations a step: ``%psum_invariant.38``,
  the all-reduce of ``v``'s gradient (63.50, 61.62 and 63.49 ms),
  ``%psum_invariant.37``, that of ``w``'s (3.85 ms), and ``%all-reduce.3``,
  the three scalars together (loss sum, weight sum, ``b``'s gradient):
  66.720 ms a step;
- ``dp.apply`` holds ``%multiply_subtract_fusion`` and ``.1``, 15.285 +
  0.957 ms: 16.244 ms a step, what PR 26 read on one chip.
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

from harness import cells, links, result_line, trace, work, work_dp  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402
from readers import (_xplane, allreduce_rate, module_roofline_dp,  # noqa: E402
                     scope_time)

CELL = "kdd2012-fm-dp4.libfm"
KIND = "TPU v5 lite"
# the cell's step: 65,536 rows x 11 nonzeros; aux [4,3,16384] and big
# [4,4,262144] int32
NNZ, ROWS, RANK, CHIPS = 720896, 65536, 16, 4
BATCH_BYTES = 4 * (4 * 3 * 16384 + 4 * 4 * 262144)


def how(name):
    return cells.load_json("metrics", name + ".json")


@pytest.fixture(scope="module")
def recorded():
    return cells.load_json("tests", "recorded_dp4.json")


# -- what a replica needs ---------------------------------------------------------

def test_replica_work_at_the_cells_numbers():
    need = work_dp.fm_sgd_step_replica(NNZ, ROWS, RANK, BATCH_BYTES, CHIPS)
    # every touched (w, v) row of the GLOBAL batch read and written once,
    # and the replica's own quarter of the batch
    assert need["bytes"] == 2 * NNZ * 17 * 4 + BATCH_BYTES / 4
    assert need["bytes"] == pytest.approx(98.0e6 + 4.4e6, rel=2e-3)
    # the other three shards' row gradients come in over the links
    assert need["link_bytes"] == 0.75 * NNZ * 17 * 4
    assert need["link_bytes"] == pytest.approx(36.8e6, rel=2e-3)
    least = work_dp.least_seconds(need, peaks_for(KIND),
                                  links.links_for(KIND))
    assert least["by"]["bytes"] == pytest.approx(125e-6, rel=2e-3)
    assert least["by"]["link_bytes"] == pytest.approx(184e-6, rel=2e-3)
    assert least["bound"] == "link_bytes"
    assert least["seconds"] == least["by"]["link_bytes"]
    # from the batch alone, never from the table or the step's build
    assert "num_features" not in \
        work_dp.fm_sgd_step_replica.__code__.co_varnames
    # one replica of one is the one-chip count, and no link
    alone = work_dp.fm_sgd_step_replica(NNZ, ROWS, RANK, BATCH_BYTES, 1)
    assert alone["link_bytes"] == 0
    assert alone["bytes"] == work.fm_sgd_step(NNZ, ROWS, RANK,
                                              BATCH_BYTES)["bytes"]


def test_link_table_names_its_source_and_refuses_an_unknown_chip():
    v5e = links.links_for(KIND)
    assert v5e["ici_bytes_per_s"] == 200e9  # 1,600 Gbit/s
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        links.links_for("TPU v9 imaginary")


# -- the readers on the recorded sample ---------------------------------------

def ctx_of(recorded, **more):
    steps = 3
    return dict({
        "traced": types.SimpleNamespace(steps=steps, nnz=steps * NNZ,
                                        rows=steps * ROWS),
        "session": types.SimpleNamespace(cfg={"fm_rank": RANK},
                                         bytes_per_batch=BATCH_BYTES),
        "events": recorded, "peaks": peaks_for(KIND),
        "device": {"count": CHIPS, "kind": KIND}}, **more)


def test_recorded_sample_holds_four_chips_and_three_whole_steps(recorded):
    planes = trace.device_planes(recorded)
    assert planes == [f"/device:TPU:{i}" for i in range(4)]
    lo, hi = trace.window_of(recorded)
    for p in planes:
        whole = [m for m in recorded["planes"][p][trace.MODULES_LINE]
                 if "sharded_step" in m[0] and m[1] >= lo
                 and m[1] + m[2] <= hi]
        assert len(whole) == 3, p
    assert recorded["scopes"]["window"] == [lo, hi]
    busy = trace.busy(recorded, 4)
    one = trace.busy(recorded, 1)
    assert 0 < busy["busy_s"] <= busy["window_s"]
    # the average over the chips, not the first chip's
    per_chip = []
    for p in planes:
        spans = trace._union(trace._clip(
            recorded["planes"][p][trace.OPS_LINE], lo, hi))
        per_chip.append(sum(b - a for a, b in spans) / 1e9)
    assert one["busy_s"] == pytest.approx(per_chip[0])
    assert busy["busy_s"] == pytest.approx(sum(per_chip) / 4)


def test_module_roofline_dp_on_the_recorded_sample(recorded):
    got = module_roofline_dp.read(ctx_of(recorded), how("fm_step_roofline.dp"))
    lo, hi = trace.window_of(recorded)
    mods = [m for m in recorded["planes"]["/device:TPU:0"][trace.MODULES_LINE]
            if "sharded_step" in m[0] and m[1] >= lo and m[1] + m[2] <= hi]
    a_step = sum(m[2] for m in mods) / len(mods) / 1e9
    assert a_step == pytest.approx(0.13998, abs=1e-5)
    assert got == pytest.approx(100.0 * 183.83e-6 / a_step, rel=1e-4)
    assert got == pytest.approx(0.13133, abs=1e-4)
    # the one-chip roofline would charge one chip the whole batch and no link
    from readers import module_roofline
    old = module_roofline.read(ctx_of(recorded), how("fm_step_roofline"))
    assert old == pytest.approx(
        100.0 * (2 * NNZ * 17 * 4 + BATCH_BYTES) / 819e9 / a_step, rel=1e-4)
    assert module_roofline_dp.read(
        ctx_of(recorded), dict(how("fm_step_roofline.dp"),
                               module="no_such_module")) is None


def test_allreduce_time_on_the_recorded_sample(recorded):
    scopes = recorded["scopes"]
    got = scope_time.reduce(scopes, how("dp.allreduce_ms"))
    mods, ops = _xplane.step_ops(scopes, "sharded_step")
    assert len(mods) == 3
    mine = [op for op in ops if "dp.allreduce" in op[3]]
    assert mine and all("shard_map/dp.allreduce/" in op[3] for op in mine)
    assert got == pytest.approx(sum(op[2] for op in mine) / 3 / 1e6)
    assert got == pytest.approx(66.720, abs=1e-3)
    assert [op[0] for op in mine] == ["%psum_invariant.38", "%all-reduce.3",
                                      "%psum_invariant.37"] * 3
    # the all-reduce of v's gradient table is nearly all of it
    table = [op[2] / 1e6 for op in mine if op[0] == "%psum_invariant.38"]
    assert table == pytest.approx([63.503, 61.620, 63.490], abs=1e-3)
    assert scope_time.reduce(scopes, how("fm_step.apply_ms")) == \
        pytest.approx(15.285 + 0.957, abs=3e-3)
    # a one-chip trace has no such scope: the reader says nothing
    one_chip = cells.load_json("tests", "recorded_scopes.json")
    assert scope_time.reduce(one_chip, how("dp.allreduce_ms")) is None


def test_allreduce_rate_is_the_counter_over_the_scopes_time(recorded,
                                                           monkeypatch):
    h = how("dp.allreduce_gbps")
    assert h["counter"] == "model_step_allreduce_bytes_total"
    monkeypatch.setattr(_xplane, "of_run", lambda ctx: recorded["scopes"])
    a_step = 8 + 4 * (1 + 54686452 * 17)

    def snap(value):
        return {"counters": [
            {"name": h["counter"], "labels": {"model": "FMLearner"},
             "value": value},
            {"name": "device_batches_total", "labels": {}, "value": 9}]}
    ctx = {"telemetry": (snap(5 * a_step), snap(85 * a_step)),
           "plain": types.SimpleNamespace(steps=80)}
    ms = scope_time.reduce(recorded["scopes"], how("dp.allreduce_ms"))
    assert allreduce_rate.bytes_a_step(ctx, h) == a_step
    assert allreduce_rate.read(ctx, h) == pytest.approx(
        a_step / (ms * 1e-3) / 1e9)
    # the parent commit has no such counter: nothing, and no error
    parent = dict(ctx, telemetry=({"counters": []}, {"counters": []}))
    assert allreduce_rate.read(parent, h) is None


# -- the configuration and BENCHMARK.json -------------------------------------

def test_dp4_configuration_is_kdd2012_fm_but_for_what_the_deployment_owns():
    one = cells.load_json("configs", "kdd2012-fm.json")
    dp4 = cells.load_json("configs", "kdd2012-fm-dp4.json")
    owned = {"runner", "source", "deployment", "guarantees", "limits",
             "assumed"}
    assert set(dp4) - set(one) == {"deployment", "guarantees"}
    for key in set(one) - owned:
        assert dp4[key] == one[key], key
    assert dp4["runner"] == "fm_dp" and dp4["reference"] == "fm"
    # the limits are the one-chip cell's, and one exact check more
    assert dp4["limits"] == dict(one["limits"], replica_gap=0)
    assert {k: v for k, v in dp4["assumed"].items() if k != "driver"} \
        == one["assumed"]
    d = dp4["deployment"]
    assert d["workers"] * d["chips_per_worker"] == 4
    assert d["global_batch_rows"] == 4 * dp4["batch_rows"] == 65536
    assert set(dp4["guarantees"]) == {"synchronous", "replicas_identical",
                                      "exactly_once"}
    assert dp4["reduced"] == ["train_rows"]
    assert dp4["source"] != one["source"]


def test_benchmark_json_lists_the_cell_and_no_reader_of_the_counter():
    spec = cells.load_spec()
    cell = cells.load_cell(spec, CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "libfm"
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert four == [CELL] and len(spec["workloads"]) // 4 >= len(four)
    want = result_line.expected_metrics(spec, CELL, True)
    assert set(want) == {
        "parse.us_per_krow", "stage.us_per_batch", "put.us_per_batch",
        "input_wait_share", "step_ms_p95", "fm_step.mfu_hbm",
        "device_idle_share", "dp.allreduce_ms", "fm_step.apply_ms",
        "fm_step_roofline.dp"}
    assert want["dp.allreduce_ms"]["layer"] == "gradient exchange"
    for name in ("dp.allreduce_ms", "fm_step.apply_ms",
                 "fm_step_roofline.dp"):
        assert want[name]["workloads"] == [CELL]
    # the one-chip roofline charges one chip the whole batch: not this cell's
    assert "fm_step_roofline" not in want
    for m in spec["per_layer"]:
        assert "counter" not in how(m["name"]), m["name"]
    proposed = cells.load_json("tests", "proposed_per_layer_dp.json")
    have = {m["name"] for m in spec["per_layer"]}
    layers = {m["layer"] for m in spec["per_layer"]}
    for m in proposed:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in have and m["layer"] in layers
        assert m["source"] == "program_counter"
        assert how(m["name"])["reader"] == "allreduce_rate"


# -- the runner's own check and the dropped shard, on four host devices -------

SCENARIOS = r"""
import json, sys
sys.path.insert(0, "benchmarks"); sys.path.insert(0, "benchmarks/tests")
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import run
from faults_dp import FAULTS
from runners import fm_dp
out = {}
for tag, faults in (("sound", None), ("drop_shard", FAULTS["drop_shard"])):
    out[tag] = run.run_cell("tiny-dp4.tinylibfm", 2**31 + 78, 0.5, False,
                            require_chip=False, faults=faults)
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
rep = NamedSharding(mesh, P())
host = {"b": np.array(0.5, np.float32), "w": np.linspace(-1, 1, 5000, dtype=np.float32),
        "v": np.random.default_rng(0).normal(size=(5000, 4)).astype(np.float32)}


def replicated(nudge):
    # a "replicated" array whose copy on the third chip differs in one
    # element's last bit
    def leaf(name, x):
        copies = []
        for i, d in enumerate(mesh.devices.flat):
            y = x.copy()
            if nudge == name and i == 2:
                flat = y.reshape(-1)
                flat[-1:] = np.nextafter(flat[-1:], np.float32(9))
            copies.append(jax.device_put(y, d))
        return jax.make_array_from_single_device_arrays(x.shape, rep, copies)
    return {k: leaf(k, x) for k, x in host.items()}


for nudge in (None, "b", "w", "v"):
    stats = fm_dp.replica_stats(replicated(nudge), mesh)
    out[f"nudge_{nudge}"] = [list(stats.shape), fm_dp.replica_gap(stats)]
print("SCENARIOS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("copy_dp"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = cells.load_json("configs", "kdd2012-fm-dp4.json")
    cfg.update(num_features=5000, fm_rank=4, batch_rows=64)
    cfg["data"]["fields"] = [{"name": f"f{i}", "cardinality": 500,
                              "present": 1.0 if i % 2 else 0.6}
                             for i in range(10)]
    with open(os.path.join(dst, "benchmarks/configs/tiny-dp4.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = dict(cells.load_json("traffic", "libfm.json"), epoch_batches=8)
    with open(os.path.join(dst, "benchmarks/traffic/tinylibfm.json"),
              "w") as f:
        json.dump(traffic, f)
    spec = cells.load_spec()
    spec["configs"].append({"name": "tiny-dp4", "source": "a test",
                            "file": "benchmarks/configs/tiny-dp4.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "tiny-dp4.tinylibfm",
                              "config": "tiny-dp4", "traffic": "tinylibfm",
                              "chips": 4, "why": "a test"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCENARIOS], cwd=dst, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    last = [l for l in r.stdout.splitlines() if l.startswith("SCENARIOS ")]
    return json.loads(last[-1][len("SCENARIOS "):])


def test_dp_cell_is_correct_on_four_devices_and_reads_replica_gap(scenarios):
    line = scenarios["sound"]
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    c = line["checks"]
    assert c["replica_gap"] == {"value": 0, "limit": 0}
    assert c["epoch_rows_gap"]["value"] == 0
    assert c["loss_gap"]["value"] < 1e-6
    assert c["grad_norm_gap"]["value"] < 1e-5
    assert c["change_norm_gap"]["value"] < 1e-5
    assert line["notes"]["epochs_finished"] >= 2


def test_a_dropped_shard_is_not_correct_though_the_replicas_agree(scenarios):
    line = scenarios["drop_shard"]
    assert line["correct"] is False
    c = line["checks"]
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        assert c[name]["value"] > c[name]["limit"], (name, c[name])
    assert c["replica_gap"]["value"] == 0


@pytest.mark.parametrize("nudge,gap", [("None", 0), ("b", 3), ("w", 1),
                                       ("v", 1)])
def test_replica_gap_reads_a_nudged_replica(scenarios, nudge, gap):
    """One element of one replica moved by one unit in the last place: the
    wrapping sum of the bits always moves; the float sums move where one
    element is the leaf (``b``)."""
    shape, got = scenarios[f"nudge_{nudge}"]
    assert shape == [4, 3, 3]
    assert got >= gap and (got > 0) == (gap > 0)


# -- compile-only sizing of the mesh step for the four-chip host --------------

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


def test_mesh_step_fits_four_v5e_chips_beside_the_checks_table(
        topo, no_compile_cache):
    """The table form on a mesh of four: two tables live at once (the
    parameters in; the gradient, its all-reduce and the new parameters share
    the output's buffer), so the check's regenerated table fits beside the
    step on a 16 GB chip."""
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    sys.path.insert(0, REPO)
    from dmlc_core_tpu.models import FMLearner
    from dmlc_core_tpu.models.fm import FMParams
    cfg = cells.load_json("configs", "kdd2012-fm-dp4.json")
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    learner = FMLearner(num_features=cfg["num_features"], k=cfg["fm_rank"],
                        mesh=mesh, objective=cfg["objective"],
                        learning_rate=cfg["learning_rate"])
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    F, K, R = cfg["num_features"], cfg["fm_rank"], cfg["batch_rows"]
    params = FMParams(jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F,), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F, K), jnp.float32, sharding=rep))
    tree = {"aux": jax.ShapeDtypeStruct((4, 3, R), jnp.int32, sharding=row),
            "big": jax.ShapeDtypeStruct((4, 4, 262144), jnp.int32,
                                        sharding=row)}
    compiled = learner._build_step(R, ("aux", "big")).lower(
        params, tree).compile()
    m = compiled.memory_analysis()
    table = F * (K + 1) * 4
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"dp4: arguments {m.argument_size_in_bytes} outputs "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"alias {m.alias_size_in_bytes}")
    assert m.argument_size_in_bytes >= table
    assert m.temp_size_in_bytes < 0.05 * table   # no third table
    assert 0.25 * 16e9 < peak < 16e9 - table     # and room for the check's
    text = compiled.as_text()
    reduced = re.findall(r"= f32\[54686452,16\]\S* all-reduce\(", text)
    assert len(reduced) == 1
    assert "dp.allreduce/psum" in text
