"""The FM step of each benchmark configuration, compiled (not run) for the
v5e at the cell's own shapes: the batch as the assemblers send it since
ISSUE 31 (the packs, and the shards' distinct columns at their rung). What
the chip's compiler refuses, what does not fit beside the check's spare
table, and a table the step copies where it could write in place (ISSUE
40), shows here and costs no chip time. One file, one fixture: only the
worker that gets this file loads the TPU's library."""

import os
import re
import sys

import numpy as np
import pytest

import jax

from dmlc_core_tpu.models.fm import STREAM_ROWS_PER_UPDATE

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
# the cells' shapes and the step as the benchmark builds it: one builder,
# which scripts/step_hlo.py prints the compiled steps of
import step_hlo  # noqa: E402


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


# (configuration, the temporaries' limit as a share of the tables, the most
# reads and writes of a padded [NNZ, n] array), the chips and the rungs
# (planes of the big pack, nnz, distinct columns) from step_hlo: those
# tests/test_fm_dp.py finds for an epoch of each cell's file. The compiler
# pads every [NNZ, n] intermediate to 128 lanes whatever its width (302 MB
# at 589,824 entries, a one-lane [NNZ, 1] column too), so the step pays for
# each by its bytes.
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
# part of 24 objects ends every epoch in one, steps at criteo1tb-fm's shape.
# v is lane-dense where K divides 128 (models/fm.py _Lanes): the scatter
# into it writes whole rows of f32[F·K/128, 128] in the row-major tiled
# layout, and no f32[F, K] is scattered into. On one chip the compiler
# fuses the placement of the gradient's rows at their lanes into the
# scatter where the scatter is told that its list ascends (kdd2010b-fm,
# criteo1tb-fm: the temporaries 782.7 / 934.8 -> 785.3 / 937.0 MB); where
# it is not (kdd2012-fm, whose table holds 64 rows a column of the list)
# the placed rows are made: 206.7 -> 407.8 MB, 0.110 of the tables. On the
# mesh the four lists go in as one, the compiler makes the placed
# f32[425984, 128] rows (218 MB): 206.7 -> 440.1 MB, 0.118 of the tables
# (one scatter a shard's sorted list held 331.1 MB but cost 46.7 ms on the
# chip where the one list costs 32.4: PERF.md section 5). Now that
# one list is sorted first, stably, where its table holds fewer rows than
# STREAM_ROWS_PER_UPDATE a column of it (kdd2012-fm-dp4: 16), and both
# scatters are told: one sort of s32[425984] under dp.apply, and the
# compiler's own sort of the list before the scatter into w is gone (one
# sort of that shape is left); v's rows are all-gathered flat, 27 MB, where
# [425984, 16] went row-major with its lanes padded, 218 MB; temporaries
# 440.4 -> 440.7 MB
@pytest.mark.slow
@pytest.mark.parametrize("config,temp_share,touches", [
    ("kdd2012-fm", 0.12, 19),
    ("kdd2010b-fm", 0.22, 13),
    ("kdd2012-fm-dp4", 0.13, 19),
    ("criteo1tb-fm", 0.45, 13),
    ("criteo1tb-fm-s3", 0.45, 13),
])
def test_step_compiles_and_fits_beside_the_checks_table(
        topo, no_compile_cache, config, temp_share, touches):
    learner, params, tree, R = step_hlo.abstract_step(config, topo)
    chips = step_hlo.configurations()[config]
    _, nnz, distinct = step_hlo.SHAPES[config]
    F, K = learner.num_features, learner.k
    assert learner.lane_dense
    rows = -(-F // (128 // K))              # ceil(F / p)
    assert params.v.shape == (rows, 128)
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
        if re.search(r"= f32\[(%d(,%d)?|%d,128)\]\{[^}]*\} copy\("
                     % (F, K, rows), line)]
    assert not copies_of_a_table, copies_of_a_table
    touched = _entry_array_touches(text, nnz)
    print(f"{config}: {len(touched)} reads and writes of a padded "
          f"[{nnz}, n] array: {touched}")
    assert len(touched) <= touches, touched
    # the tables are updated at the distinct columns alone, one scatter a
    # table. One shard's list ascends, and the scatter into w is told so;
    # the one into v only where its table holds fewer rows than
    # STREAM_ROWS_PER_UPDATE a column of the list (models/fm.py: told, XLA
    # passes over the whole table). The four shards' lists go in as one,
    # D·U long, which ascends within a shard's stretch only: where v's
    # scatter would be told, the step sorts it once, stably, under
    # dp.apply (models/fm.py _list_order) and tells both scatters; else
    # nothing is promised of it, and the compiler sorts the list itself for
    # the scatter into w and not for v
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
        # v's rows flat, never row-major with their K lanes padded to 128
        assert not any(f"= f32[{chips * distinct},{K}]{{1,0" in line
                       for line in gathered), gathered
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    into_tables = [line for line in scatters
                   if f"= f32[{F}]" in line or f"= f32[{rows},128]" in line]
    assert len(into_tables) == 2, into_tables
    # v's whole 128-lane rows, row-major: a feature's K floats one run
    assert any(f"= f32[{rows},128]{{1,0:T(8,128)}} scatter(" in line
               for line in into_tables), into_tables
    assert not any(f"f32[{F},{K}]" in line for line in scatters), scatters
    updates = chips * distinct
    told_v = rows < STREAM_ROWS_PER_UPDATE * updates
    sorts_of_the_list = [line for line in text.splitlines()
                         if " sort(" in line and f"s32[{updates}]" in line]
    print(f"{config}: sorts of the list {sorts_of_the_list}")
    assert len(sorts_of_the_list) == (1 if chips > 1 and told_v else 0)
    for line in sorts_of_the_list:
        assert "dp.apply/sort" in line and "is_stable=true" in line, line
    for line in into_tables:
        assert "dp.apply/scatter-add" in line, line
        told = told_v if f"= f32[{rows},128]" in line else (
            chips == 1 or told_v)
        assert ("indices_are_sorted=true" in line) == told, line
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


# criteo1tb-fm-ftrl: the row form under FTRL-proximal on w and
# AdaGrad on v, a state of 8 leaves (b, w, v; zb, nb, z, n, G): 35 floats a
# row, 4.70 GB at 2^25 ids. Donated like SGD's three: every leaf written
# where it lies, no whole-table copy, and what a step holds beyond its
# state is the SGD step's temporaries and the slots' gathered rows
@pytest.mark.slow
def test_ftrl_step_compiles_in_place(topo, no_compile_cache):
    from dmlc_core_tpu.models.fm import FMParams, FMSlots, FMState
    learner, state, tree, R = step_hlo.abstract_step("criteo1tb-fm-ftrl",
                                                     topo)
    F, K = learner.num_features, learner.k
    assert learner.updater == "ftrl"
    rows = -(-F // (128 // K))              # v and G lane-dense
    assert jax.tree.map(lambda a: a.shape, state) == FMState(
        FMParams((), (F,), (rows, 128)),
        FMSlots((), (), (F,), (F,), (rows, 128)))
    compiled = learner._build_step(R, tuple(sorted(tree))).lower(
        state, tree).compile()
    m = compiled.memory_analysis()
    size = F * (2 * K + 3) * 4 + 12
    print(f"criteo1tb-fm-ftrl: arguments {m.argument_size_in_bytes} outputs "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
          f"{m.alias_size_in_bytes}")
    # all eight leaves written where they lie
    assert m.alias_size_in_bytes >= size
    text = compiled.as_text()
    copies = [line for line in text.splitlines()
              if re.search(r"= f32\[(%d(,%d)?|%d,128)\]\{[^}]*\} copy\("
                           % (F, K, rows), line)]
    assert not copies, copies
    # one write-back a table leaf (w, v; z, n, G), under dp.apply: v's and
    # G's scatter-adds of whole 128-lane rows
    into_tables = [line for line in text.splitlines()
                   if " scatter(" in line and (f"= f32[{F}]" in line or
                                               f"= f32[{rows},128]" in line)]
    assert len(into_tables) == 5, into_tables
    assert sum(f"= f32[{rows},128]{{1,0:T(8,128)}} scatter(" in line
               for line in into_tables) == 2, into_tables
    assert all("dp.apply" in line for line in into_tables)
    assert "opt.update" in text
    # the step beside the check's spare state (init()'s, held through the
    # first step): a quarter of the chip at least, and it fits
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0.25 * 16e9 < peak + size < 16e9
