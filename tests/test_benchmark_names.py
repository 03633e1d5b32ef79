"""The names the cell benchmark reads out of the program exist in the program.

``benchmarks/metrics/*.json`` find the program by name: telemetry histograms
and a counter, opened spans (``dmlc.<name>`` in the profiler's trace), the
jitted step's module and the ``jax.named_scope``s of its operations. The
driver's tier-1 collects ``tests/`` only, and a PR that claims a gain may not
edit ``benchmarks/``: a rename that no test here sees would pass tier-1 and
be refused on the chip as a malformed result line. So the names are read from
the metric files, never spelt out, and looked up in one real run on the CPU
for each cell of ``BENCHMARK.json``: a small file of the cell's format (as
text, or converted to ``.crec`` where the traffic file stores it so, or put
under 24 keys of the test process's mock S3 and read as part 1 of 16 where it
stores it there: ISSUE 34) through ``DeviceRowBlockIter`` for two epochs and
``FMLearner.step`` on as many
devices as the cell has chips (one: the row form of the step; four: the row
form under ``shard_map``, with its exchange under ``dp.allreduce`` or, where
the configuration's ``deployment`` says ``table_layout``, on range-sharded
tables with ``dp.pull`` and ``dp.push``: ISSUE 39). A metric
that ``BENCHMARK.json`` promises a cell has to be found in that cell's run; a
metric file it lists for no cell yet (the proposals under
``benchmarks/tests/``) in some cell's.
"""

import json
import os
import re

import numpy as np
import pytest

from tests.s3_shared import STATE as S3

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.io.convert import rows_to_csr_recordio
from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh, device_iter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
# the keys of a metric file that name something of the program
NAME_KEYS = ("histograms", "counter", "less", "counters", "spans", "module",
             "any")
# more features than `dense_max_features`: the iterator's layout="auto" then
# makes CSR batches, as it does of every cell's data
ROWS, BATCH, FIELDS, CARD = 600, 256, 4, 200
# the hashed format's feature space: its ids are below 2**HASH_BITS
HASH_BITS = 10


# proposed metric files (listed for no cell) whose names no cell's program
# has any more; a PR that may not edit ``benchmarks/`` says why here, and the
# case fails again, loudly, the day the name comes back
NOTHING_TO_READ = {
    "fm_step.grad_table_ms.json":
        "reads transpose(jvp(fm.gather)), the scatter into a gradient of the "
        "tables' shape, which only the table form of a CSR step lowers; "
        "since ISSUE 33 every cell steps in the row form, on the mesh too. "
        "The gate PR repoints or drops the file (PERF.md section 7)",
}


def _json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _json(REPO, "BENCHMARK.json")


def _how(fname):
    return _json(BENCH, "metrics", fname)


def _names_something(fname):
    return any(k in _how(fname) for k in NAME_KEYS)


def _cases():
    """(cell, metric file) for every per-layer metric ``BENCHMARK.json``
    lists for the cell (``harness/result_line.py``: no ``workloads`` key
    means every cell), then (None, file) for the files it lists nowhere."""
    cases = [(cell["name"], m["name"] + ".json")
             for cell in SPEC["workloads"] for m in SPEC["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])
             and _names_something(m["name"] + ".json")]
    listed = {f for _, f in cases}
    return cases + [
        pytest.param(None, f, marks=pytest.mark.xfail(
            strict=True, reason=NOTHING_TO_READ[f]))
        if f in NOTHING_TO_READ else (None, f)
        for f in sorted(os.listdir(os.path.join(BENCH, "metrics")))
        if f.endswith(".json") and f not in listed and _names_something(f)]


def _write_rows(path, fmt):
    """A small file of the format; returns the URI arguments it needs."""
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for i in range(ROWS):
            cols = rng.integers(0, CARD, FIELDS) + CARD * np.arange(FIELDS)
            if fmt == "criteo":   # 40 cells: the label, 13 + 26, most empty
                cells = [f"{c}" for c in cols] + [""] * (13 - FIELDS) + \
                    [f"{c:08x}" for c in cols] + [""] * (26 - FIELDS)
                f.write("\t".join([f"{i % 2}"] + cells) + "\n")
                continue
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{c}:1" if fmt == "libfm" else f"{c}:1"
                for j, c in enumerate(cols)) + "\n")
    return f"?hash_bits={HASH_BITS}" if fmt == "criteo" else ""


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """What the readers would find in each cell: the telemetry snapshot, the
    names in the span ring, the jitted step's name and the ``op_name`` of
    every operation it lowers to."""
    work = tmp_path_factory.mktemp("names")
    telemetry.enable(True)
    found = {}
    for cell in SPEC["workloads"]:
        traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
        fmt = traffic["format"]
        uri = str(work / f"{cell['name']}.{fmt}")
        uri += _write_rows(uri, fmt)
        part = {}
        if traffic["store"] == "crec":
            text, uri, fmt = uri, uri + ".crec", "crec"
            assert rows_to_csr_recordio(text, uri,
                                        fmt=traffic["format"]) == ROWS
        elif traffic["store"] == "s3":
            # one worker's part of a directory of objects, as the cell's
            # runner reads it (benchmarks/runners/fm_s3.py)
            text, args = uri.partition("?")[::2]
            with open(text, "rb") as f:
                body = f.read()
            # an object the ranged reader splits (doc/io-ranged.md: twice
            # its least range, 512 KiB, and up)
            body *= -(-(600 << 10) // len(body))
            for day in range(24):
                S3.objects[("names", f"day_{day:02d}")] = body
            uri = f"s3://names/?format={fmt}" + (args and "&" + args)
            fmt, part = "auto", {"part": 1, "npart": 16}
        telemetry.reset()
        device_iter._reset_shape_census()
        mesh = data_mesh(cell["chips"])
        layout = _json(BENCH, "configs", cell["config"] + ".json").get(
            "deployment", {}).get("table_layout", "replicated")
        learner = FMLearner(num_features=max(FIELDS * CARD, 1 << HASH_BITS),
                            k=4, mesh=mesh, table_layout=layout)
        params = learner.init(0)
        with DeviceRowBlockIter(uri, mesh=mesh, batch_rows=BATCH, fmt=fmt,
                                col_owners=learner.col_owners, **part) as it:
            for _ in range(2):
                for batch in it:
                    params, loss = learner.step(params, batch)
                assert np.isfinite(float(loss))
                it.before_first()
        assert "x" not in batch.tree(), "a dense batch: no cell steps on one"
        fn = next(iter(learner._step_fn.values()))
        hlo = fn.lower(params, batch.tree()).as_text(dialect="hlo",
                                                     debug_info=True)
        found[cell["name"]] = {
            "snapshot": telemetry.snapshot(native=True),
            "spans": {s["name"] for s in telemetry.spans()},
            "step": fn.__name__,
            "ops": set(re.findall(r'op_name="([^"]+)"', hlo))}
    telemetry.reset()
    device_iter._reset_shape_census()
    return found


def _missing(how, runs):
    """Every name of the metric file that none of ``runs`` showed."""
    out = []
    for name in how.get("histograms", ()):
        if not any(h["count"] for r in runs
                   for h in r["snapshot"]["histograms"] if h["name"] == name):
            out.append(f"histogram {name!r} was never observed")
    # readers/hist_per_counter.py: the rise of `counter` less that of `less`
    # and readers/counter_ratio.py: the rise of one of `counters` over the
    # other's
    for name in [how[k] for k in ("counter", "less") if k in how] + \
            how.get("counters", []):
        if not any(c["value"] for r in runs
                   for c in r["snapshot"]["counters"] if c["name"] == name):
            out.append(f"counter {name!r} never rose")
    for name in how.get("spans", ()):
        # an opened span `x` is the annotation `dmlc.x` of the trace
        if not any(name.removeprefix("dmlc.") in r["spans"] for r in runs):
            out.append(f"span {name!r} was never opened")
    if "module" in how and not any(how["module"] in r["step"] for r in runs):
        out.append(f"module {how['module']!r} is not in the step's name "
                   f"{sorted({r['step'] for r in runs})}")
    for scope in how.get("any", ()):
        # readers/scope_time.py: the scope path holds one of `any` and none
        # of `none`
        if not any(scope in op and not any(n in op
                                           for n in how.get("none", ()))
                   for r in runs for op in r["ops"]):
            out.append(f"no operation of the step is under scope {scope!r} "
                       f"(less {how.get('none', [])})")
    return out


@pytest.mark.parametrize("cell,fname", _cases(),
                         ids=lambda v: v or "some-cell")
def test_names_a_metric_file_reads_exist_in_the_program(cell, fname, program):
    runs = [program[cell]] if cell else list(program.values())
    missing = _missing(_how(fname), runs)
    assert not missing, (f"benchmarks/metrics/{fname}, "
                         f"{cell or 'listed for no cell, sought in all'}: "
                         + "; ".join(missing))
