"""The FM step of each benchmark configuration, compiled (not run) for the
v5e at the cell's own shapes: the batch as the assemblers send it since
ISSUE 31 (the packs, and the shards' distinct columns at their rung). What
the chip's compiler refuses, what does not fit beside the check's spare
table, and a table the step copies where it could write in place (ISSUE
40), shows here and costs no chip time. One file, one fixture: only the
worker that gets this file loads the TPU's library."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.models.fm import FMParams

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")


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
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _entry_array_touches(text, nnz):
    """Reads and writes of a float ``[nnz, n]`` array in the chip's row-major
    tiled layout (``{1,0:T(8,128)}``: n padded to 128 lanes whatever it is)
    by the instructions of the compiled module's entry computation: each
    result and each operand once; a bitcast or a tuple's element passes its
    operand on under another name and moves nothing."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    padded = re.compile(r"f32\[%d,(\d+)\]\{1,0:T\(8,128\)[^}]*\}" % nnz)
    free = ("bitcast", "get-tuple-element", "parameter", "tuple")
    made, touches = {}, []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)"
                     r"(?:, |$)", line)
        if not m:
            continue
        name, types, op, args = m.groups()
        made[name] = outs = padded.findall(types)
        if op in free:
            continue
        ins = [n for a in re.findall(r"%[\w.\-]+", args)
               for n in made.get(a, [])]
        touches += [(name, "writes", n) for n in outs]
        touches += [(name, "reads", n) for n in ins]
    return touches


# (configuration, chips, planes of the big pack, nnz rung, distinct rung,
# the temporaries' limit as a share of the tables, the most reads and
# writes of a padded [NNZ, n] array): the rungs tests/test_fm_dp.py finds
# for an epoch of each cell's file. The compiler pads every [NNZ, n]
# intermediate to 128 lanes whatever its width (302 MB at 589,824 entries,
# a one-lane [NNZ, 1] column too), so the step pays for each by its bytes.
# Since ISSUE 37 the margin's backward is written by hand
# (models/fm.py _fm_margin_rows_bwd) and the entry computation reads or
# writes such an array 13 times (the expansion; the products; the squares;
# the segment sum reads both; the gather back; dm's lane read out of it;
# the pass that reads the gathered and the expanded rows and writes the
# cotangent; the merge), where autodiff's slices, pads and one-lane
# columns left 24 (ISSUE 35's tree). At kdd2012-fm's 180,224 entries the
# compiler moves one of them into its faster memory space and back (an
# asynchronous copy: 6 touches more, 19 for the 30 of ISSUE 35's tree).
# Temporaries 0.207 / 0.783 / 0.935 GB where ISSUE 35's tree held 0.391 /
# 1.289 / 1.542. Since ISSUE 40 the step donates its state and the compiler
# writes each table into the buffer it came in (alias 3.72 / 3.95 / 2.28
# GB, no copy of a table left in the program): a step alone holds 3.93 /
# 4.74 / 3.22 GB (kdd2012-fm / kdd2010b-fm / criteo1tb-fm), one table and
# the temporaries, where criteo1tb-fm's held 5.51 beside tables it did not
# donate; in the benchmark the process holds the check's spare table
# beside it (init()'s state through the first step, p0 made again after
# the last), which is the window asserted below. The limits leave less
# than one array of room (0.025 / 0.064 / 0.13 of the tables): 0.056 /
# 0.198 / 0.410 read. One shape a configuration is all there is to
# compile: since ISSUE 34 an epoch's short last batch is sent at the rungs of
# the batch before it (device_iter.tail_rung), so criteo1tb-fm-s3, whose
# part of 24 objects ends every epoch in one, steps at criteo1tb-fm's shape
@pytest.mark.slow
@pytest.mark.parametrize(
    "config,chips,planes,nnz,distinct,temp_share,touches", [
        ("kdd2012-fm", 1, 4, 180224, 106496, 0.07, 19),
        ("kdd2010b-fm", 1, 3, 491520, 262144, 0.22, 13),
        ("kdd2012-fm-dp4", 4, 4, 180224, 106496, 0.07, 19),
        ("criteo1tb-fm", 1, 3, 589824, 212992, 0.45, 13),
        ("criteo1tb-fm-s3", 1, 3, 589824, 212992, 0.45, 13),
    ])
def test_step_compiles_and_fits_beside_the_checks_table(
        topo, no_compile_cache, config, chips, planes, nnz, distinct,
        temp_share, touches):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cfg = json.load(f)
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    learner = FMLearner(num_features=cfg["num_features"], k=cfg["fm_rank"],
                        mesh=mesh, objective=cfg["objective"],
                        learning_rate=cfg["learning_rate"])
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    F, K, R = cfg["num_features"], cfg["fm_rank"], cfg["batch_rows"]
    params = FMParams(jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F,), jnp.float32, sharding=rep),
                      jax.ShapeDtypeStruct((F, K), jnp.float32, sharding=rep))
    tree = {"aux": jax.ShapeDtypeStruct((chips, 3, R), jnp.int32,
                                        sharding=row),
            "big": jax.ShapeDtypeStruct((chips, planes, nnz), jnp.int32,
                                        sharding=row),
            "cols": jax.ShapeDtypeStruct((chips, distinct), jnp.int32,
                                         sharding=row)}
    assert learner._takes_row_form(tree)   # on one chip and on the mesh
    compiled = learner._build_step(R, tuple(sorted(tree))).lower(
        params, tree).compile()
    m = compiled.memory_analysis()
    table = F * (K + 1) * 4
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{config}: arguments {m.argument_size_in_bytes} outputs "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"alias {m.alias_size_in_bytes}")
    assert m.argument_size_in_bytes >= table
    # the state is donated and the compiler takes the offer: b, w and v
    # are written where they lie (models/_dp.py _own_state hands the step
    # a copy of any state that is not the learner's own)
    assert m.alias_size_in_bytes >= table + 4
    # no second table: the temporaries are the batch's [NNZ, K] and [U, K]
    # intermediates (0.21, 0.78 and 0.93 GB in the one-chip cells)
    assert m.temp_size_in_bytes < temp_share * table
    # what the process holds in the benchmark, the step beside the check's
    # spare table: a quarter of the chip at least, and it fits
    assert 0.25 * 16e9 < peak + table < 16e9
    text = compiled.as_text()
    copies_of_a_table = [
        line for line in text.splitlines()
        if re.search(r"= f32\[%d(,%d)?\]\{[^}]*\} copy\(" % (F, K), line)]
    assert not copies_of_a_table, copies_of_a_table
    touched = _entry_array_touches(text, nnz)
    print(f"{config}: {len(touched)} reads and writes of a padded "
          f"[{nnz}, n] array: {touched}")
    assert len(touched) <= touches, touched
    # the tables are updated at the distinct columns alone, one scatter a
    # table. One shard's list ascends and the scatter is told so; the four
    # shards' lists go in as one, which ascends within a shard's stretch
    # only, so nothing is promised of it: the compiler sorts the list
    # itself for the scatter into w, and not for the one into v
    if chips > 1:
        # the shards' rows are exchanged at their own widths: w's as [D*U]
        # and not as a [D*U, 1] slice of the merged gradient, which the
        # compiler pads to 128 lanes (ISSUE 35: 1.84 ms a step for 0.02;
        # models/_dp.py settles the leaves before the exchange, and
        # tests/test_fm.py holds that in the lowered step)
        gathered = [line for line in text.splitlines()
                    if " all-gather(" in line and "= f32[" in line]
        assert any(f"= f32[{chips * distinct}]{{" in line
                   for line in gathered), gathered
        assert not any(f"= f32[{chips * distinct},1]" in line
                       for line in gathered), gathered
    into_tables = [line for line in text.splitlines()
                   if " scatter(" in line and f"= f32[{F}" in line]
    assert len(into_tables) == 2, into_tables
    sorts_of_the_list = [line for line in text.splitlines()
                         if " sort(" in line and "dp.apply/scatter-add" in line]
    for line in into_tables:
        assert "dp.apply/scatter-add" in line, line
        # on the mesh a scatter reads as sorted only where the compiler
        # sorted the list itself
        assert ("indices_are_sorted=true" in line) == (
            chips == 1 or (f"= f32[{F}]" in line and bool(sorts_of_the_list)))
    assert not (chips == 1 and sorts_of_the_list), sorts_of_the_list
    # the mesh step sums three scalars and gathers the shards' lists and
    # rows: no collective, and nothing else but the parameters in and out
    # (the scatters write into them), has a table's shape
    collectives = [line for line in text.splitlines()
                   if re.search(r" all-(reduce|gather)(-start)?\(", line)]
    assert bool(collectives) == (chips == 4)
    for line in collectives:
        assert f"[{F}" not in line, line
        assert "dp.allreduce" in line, line
    if chips == 4:
        # three gathers, of every shard's list, w rows and v rows (the
        # compiler reshapes them: count elements)
        sizes = sorted(
            int(np.prod([int(n) for n in re.search(
                r"= [a-z0-9]+\[([0-9,]+)\]", line).group(1).split(",")]))
            for line in collectives if " all-gather" in line)
        assert sizes == [chips * distinct] * 2 + [chips * distinct * K], sizes
