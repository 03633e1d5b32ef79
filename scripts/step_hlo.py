#!/usr/bin/env python3
"""The optimized HLO of each benchmark configuration's FM step, compiled (not
run) for a described TPU v5e, one file a configuration, without what says
where in the source each operation was traced: the source files, lines and
stack frames of its metadata and the module's tables of them. The scope
path (``op_name``), which the per-layer metrics read, is kept.

Two checkouts that compile the same programs under the same scopes write
the same files (names numbered by first appearance), so a change that must
leave every cell's step as it was is shown by::

    python3 scripts/step_hlo.py --out /tmp/parent    # in the parent's tree
    python3 scripts/step_hlo.py --out /tmp/change    # in the change's tree
    diff -r /tmp/parent /tmp/change

Every configuration that a workload of ``BENCHMARK.json`` names is compiled
(one missing from ``SHAPES`` is an error), as the benchmark's runners build
its step from ``benchmarks/configs/<name>.json`` (read, never written): SGD
without weight decay, FTRL-proximal with the configuration's rule where it
names ``updater``, range-sharded tables where its ``deployment`` says so; on
as many devices of a described ``v5e:2x2`` as its cells have chips. The
batch is abstract, at the shapes the assemblers send for an epoch of the
cell's file. tests/test_step_compiles_for_v5e.py compiles the same steps
through ``abstract_step``. Needs the TPU compiler's library, no chip; half
a minute and a few GB of host memory a configuration.
"""

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CONFIGS = os.path.join(REPO, "benchmarks", "configs")

# configuration: (planes of the big pack, nnz rung, distinct-column rung of
# a shard; on range-sharded tables the stretch rung of one owner), the rungs
# that tests/test_fm_dp.py and tests/test_fm_ps.py find for an epoch of each
# cell's file
SHAPES = {
    "kdd2012-fm": (4, 180224, 106496),
    "kdd2010b-fm": (3, 491520, 262144),
    "kdd2012-fm-dp4": (4, 180224, 106496),
    "criteo1tb-fm": (3, 589824, 212992),
    "criteo1tb-fm-s3": (3, 589824, 212992),
    "criteo1tb-fm-ps4": (3, 589824, 53248),
    "criteo1tb-fm-ftrl": (3, 589824, 212992),
}
FTRL_RULE = ("alpha", "beta", "l1", "l2", "v_lr", "v_beta")
# where in the source: a metadata field's key and value (a quoted string may
# hold anything but an unescaped quote), and the module's tables of files,
# functions, lines and call stacks
SOURCE = re.compile(r'(?:(?<=\{)|\s)(?:source_(?:file|line|end_line|column'
                    r'|end_column)|stack_frame_id)=(?:"(?:[^"\\]|\\.)*"|\d+)')
FRAMES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                    r"(?:.+\n)*\n?", re.M)
QUOTED = re.compile(r'"(?:[^"\\]|\\.)*"')
# an instruction, computation or parameter name (not an opcode: those are
# followed by their operands' parenthesis)
NAME = re.compile(r"(?<![\w.\-])[A-Za-z_][\w.\-]*(?![\w.\-(])")


def stripped(text: str) -> str:
    """``text`` without the source files, lines and stack frames of its
    operations' metadata and the module's tables of them, and with the
    numbers in the instructions' and computations' names, which count the
    operations traced before each, made the order of each name's first
    appearance (a name becomes ``stem.#i``; quoted strings, the scope
    paths among them, are left as they are). Two programs equal but for
    the lines they were traced from, or the order in which independent
    operations were traced, then read the same; a change of an operation,
    its scope, a layout, a fusion or the schedule does not."""
    text = FRAMES.sub("", SOURCE.sub("", text))
    text = re.sub(r",? metadata=\{\s*\}", "",
                  re.sub(r"metadata=\{\s+", "metadata={", text))
    names = {}
    for name in re.findall(r"%([A-Za-z_][\w.\-]*)", text):
        names.setdefault(name, "%s.#%d" % (
            re.match(r"[A-Za-z_][\w\-]*", name).group(), len(names)))

    def renamed(part):
        return NAME.sub(lambda m: names.get(m.group(), m.group()), part)
    out, at = [], 0
    for m in QUOTED.finditer(text):
        out += [renamed(text[at:m.start()]), m.group()]
        at = m.end()
    return "".join(out + [renamed(text[at:])])


def configurations() -> dict:
    """Each configuration a workload of ``BENCHMARK.json`` names, with the
    chips of its cells."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        workloads = json.load(f)["workloads"]
    chips = {}
    for w in workloads:
        chips.setdefault(w["config"], set()).add(int(w["chips"]))
    for name, n in chips.items():
        if len(n) != 1:
            raise SystemExit(f"{name}: cells on {sorted(n)} chips")
    return {name: n.pop() for name, n in chips.items()}


def abstract_step(name: str, topo):
    """The learner of configuration ``name`` on as many devices of ``topo``
    as its cells have chips, and the abstract state and batch its step
    takes: ``(learner, state, tree, batch_rows)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from dmlc_core_tpu.models import FMLearner

    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    chips = configurations()[name]
    planes, nnz, distinct = SHAPES[name]
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    kw = dict(num_features=int(cfg["num_features"]), k=int(cfg["fm_rank"]),
              mesh=mesh, objective=cfg["objective"],
              init_scale=float(cfg["init_scale"]))
    if cfg.get("updater", "sgd") != "sgd":
        kw.update(updater=cfg["updater"],
                  **{k: float(cfg[k]) for k in FTRL_RULE})
    else:
        kw.update(learning_rate=float(cfg["learning_rate"]), l2=0.0)
    layout = cfg.get("deployment", {}).get("table_layout", "replicated")
    learner = FMLearner(table_layout=layout, **kw)
    shapes = jax.eval_shape(learner.init)
    specs = learner._table_specs() if layout == "range_sharded" else \
        jax.tree.map(lambda _: P(), shapes)
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, specs)
    if layout == "range_sharded":
        distinct *= learner.col_owners[0]      # a stretch an owner
    row = NamedSharding(mesh, P("data"))
    R = int(cfg["batch_rows"])
    tree = {"aux": jax.ShapeDtypeStruct((chips, 3, R), jnp.int32,
                                        sharding=row),
            "big": jax.ShapeDtypeStruct((chips, planes, nnz), jnp.int32,
                                        sharding=row),
            "cols": jax.ShapeDtypeStruct((chips, distinct), jnp.int32,
                                         sharding=row)}
    return learner, state, tree, R


def compiled_step(name: str, topo):
    """The step of configuration ``name``, compiled for ``topo``."""
    learner, state, tree, R = abstract_step(name, topo)
    return learner._build_step(R, tuple(sorted(tree))).lower(
        state, tree).compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True,
                    help="directory for <configuration>.hlo")
    args = ap.parse_args(argv)
    names = sorted(configurations())
    missing = [name for name in names if name not in SHAPES]
    if missing:
        print(f"no shapes for {missing}: add them to SHAPES",
              file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    # a compile for a described chip would be written to the persistent
    # cache, which cannot read it back without one
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    os.makedirs(args.out, exist_ok=True)
    for name in names:
        text = stripped(compiled_step(name, topo).as_text())
        with open(os.path.join(args.out, name + ".hlo"), "w") as f:
            f.write(text)
        print(f"{name}: {len(text.splitlines())} lines", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
