"""Runner of the factorisation-machine cells whose tables are sharded by key
range over the chips (workers and servers of a parameter-server job, worker d
and server d on chip d): the session of ``runners/fm_criteo.py`` (its file,
its reference rows, ``epoch_nnz_gap``) with the layout of the configuration's
``deployment`` handed to ``FMLearner`` and to the iterator, ``runners/
fm_dp.py``'s ``replica_gap`` over the leaves every chip holds, and two exact
numbers more: ``owner_gap`` and ``rows_lost``.

The deployment's guarantees and what holds each (``configs/<name>.json``):
synchronous, by the comparison with the plain reference, which steps the
global batch and knows no layout; one owner a row, by ``owner_gap`` (every
table leaf's shard is its chip's range of features and nothing else, whatever
number of features a row of the leaf holds, and no chip's peak reaches the
whole tables' bytes); no row lost on the way, by
``rows_lost`` (in every check step the real columns each owner receives from
each shard over the pull's all-to-all against the counts
``reference/owners.py`` slices from the generator's own cells); what every
chip holds bit-identical, by ``replica_gap``; every row and every present cell
once an epoch, by ``epoch_rows_gap`` and ``epoch_nnz_gap``.
"""

from __future__ import annotations

import inspect
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dmlc_core_tpu.models import FMLearner
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh
from harness import cells, check, datagen, datagen_criteo
from runners import fm_criteo, fm_dp
from runners.fm import drive, end_to_end, traced_drive  # noqa: F401


def received_counts(cols, mesh, owner_rows: int) -> np.ndarray:
    """``[owners, shards]``: the real columns each owner gets from each shard
    when the owner-major lists ``cols`` ``[D, owners * C]`` cross the mesh as
    the step's pull sends them (stretch ``o`` of every shard to chip ``o``):
    ids of the owner's own range, counted on the owner."""
    axis = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def on_owner(c):
        asked = jax.lax.all_to_all(c[0].reshape(n, -1), axis, 0, 0,
                                   tiled=True)
        asked = asked - jax.lax.axis_index(axis) * owner_rows
        return jnp.sum((asked >= 0) & (asked < owner_rows), axis=1)[None]
    return np.asarray(jax.jit(jax.shard_map(
        on_owner, mesh=mesh, in_specs=P(axis), out_specs=P(axis)))(cols))


LANES = 128  # a row of a lane-dense table: one vector register's lanes


def owner_gap(leaves, place: Dict, owners: int, num_features: int,
              rank: int, peaks) -> int:
    """What of the table ``leaves`` (every leaf of the state with a leading
    axis) is not where one owner a row puts it, counted 1 a fault.

    A leaf first has to be a table of features: ``num_features`` features of
    1 or ``rank`` numbers each, so of ``F`` or ``F * rank`` numbers, its
    ``n`` rows holding ``F / n`` whole features each, in order, and a row of
    several features no wider than one lane-dense row (``[F, K]`` holds one
    feature a row, ``[F/8, 128]`` eight; a factor-major ``[K, F]`` or a table
    of part of the features is refused); ``n`` has to split into ``owners``
    blocks. Then each shard more or fewer than ``owners`` counts, and each
    shard on the chip at mesh position ``place[device]`` = o that is not rows
    ``[o*n/owners, (o+1)*n/owners)`` with every other axis whole, which is the
    features ``[o*F/owners, (o+1)*F/owners)`` whatever the layout; and each
    chip whose peak (``peaks``, one a chip) reaches the bytes of all the
    leaves together."""
    gap = 0
    for leaf in leaves:
        n = leaf.shape[0]
        size = math.prod(leaf.shape)
        gap += not (num_features % n == 0 and n % owners == 0
                    and size in (num_features, num_features * rank)
                    and (n == num_features or size // n <= LANES))
        rows = n // owners
        shards = leaf.addressable_shards
        gap += abs(len(shards) - owners)
        for sh in shards:
            o = place[sh.device]
            want = ((o * rows, (o + 1) * rows, 1),) + tuple(
                (0, d, 1) for d in leaf.shape[1:])
            got = tuple(s.indices(d) for s, d in zip(sh.index, leaf.shape))
            gap += got != want or sh.data.shape[0] != rows
    whole = sum(int(leaf.nbytes) for leaf in leaves)
    return int(gap + sum(peak >= whole for peak in peaks))


class Session(fm_criteo.Session):
    """``fm_criteo.Session`` on range-sharded tables."""

    def __init__(self, cell: Dict, seed: int, chips: int):
        super().__init__(cell, seed, chips)
        self.layout = self.cfg["deployment"]["table_layout"]
        self.received: List[np.ndarray] = []   # of each check step
        self._checking = False
        self.replica_gap_read = self.owner_gap_read = None

    def write_data(self, threads: int) -> None:
        if "table_layout" not in inspect.signature(FMLearner).parameters:
            # a program from before the layout fails here, at once, and not
            # after a gigabyte of text is written
            raise RuntimeError(
                f"the program's FMLearner has no table_layout: it cannot "
                f"keep its tables {self.layout!r}")
        super().write_data(threads)

    def build(self) -> None:
        cfg = self.cfg
        if cfg.get("l2", 0.0) != 0.0:
            raise ValueError("the compact reference holds for l2 = 0 only")
        mesh = data_mesh(self.chips)
        self.learner = FMLearner(
            num_features=int(cfg["num_features"]), k=int(cfg["fm_rank"]),
            mesh=mesh, objective=cfg["objective"],
            learning_rate=float(cfg["learning_rate"]), l2=0.0,
            init_scale=float(cfg["init_scale"]), table_layout=self.layout)
        self.params = self.learner.init(self.init_seed)
        jax.block_until_ready(self.params)
        self.it = DeviceRowBlockIter(
            self.uri, mesh=mesh, batch_rows=self.batch_rows, fmt=self.fmt,
            prefetch=int(self.traffic.get("prefetch", 2)),
            nthread=int(self.traffic.get("nthread", 0)),
            col_owners=self.learner.col_owners)
        self._stream = iter(self.it)

    def dispatch(self, batch):
        if self._checking:
            self.received.append(received_counts(
                batch.cols, self.learner.mesh, self.learner.col_owners[1]))
        return super().dispatch(batch)

    def first_steps(self) -> check.Readings:
        self._checking = True
        try:
            return super().first_steps()
        finally:
            self._checking = False

    def owner_gap(self) -> int:
        """``owner_gap`` of the state's table leaves on the learner's mesh,
        for the configuration's servers, features and rank."""
        place = {d: o for o, d in enumerate(self.learner.mesh.devices.flat)}
        return owner_gap(
            [leaf for leaf in jax.tree.leaves(self.params) if leaf.ndim],
            place, int(self.cfg["deployment"]["servers"]),
            int(self.cfg["num_features"]), int(self.cfg["fm_rank"]),
            [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in place])

    def free(self) -> None:
        # run.py asks for the exact numbers after free(): read the state
        # here, after the window's last step
        if self.params is not None:
            self.replica_gap_read = fm_dp.replica_gap(fm_dp.replica_stats(
                (self.params.b,), self.learner.mesh))
            self.owner_gap_read = self.owner_gap()
        super().free()

    def rows_lost(self) -> int:
        """What the owners received in the check steps against what the
        generator's own cells say each shard asks of each owner."""
        owners = cells.load_module("reference", "owners")
        rule = cells.load_module("reference", "criteo")
        cfg = self.cfg
        rows = self.check_steps * self.batch_rows
        block = datagen.first_rows(cfg["data"], self.seed, rows,
                                   self.file_rows)
        c = datagen_criteo.cells(cfg["data"], block)
        ids = rule.cell_ids(c.column, c.text, c.lens, int(cfg["hash_bits"]))
        ends = np.concatenate([[0], np.cumsum(block.lens)])
        shard_rows = self.batch_rows // self.chips
        lost = abs(len(self.received) - self.check_steps)
        for i, got in enumerate(self.received):
            shards = [ids[ends[r0]:ends[r0 + shard_rows]] for r0 in range(
                i * self.batch_rows, (i + 1) * self.batch_rows, shard_rows)]
            want = owners.stretch_counts(shards, int(cfg["num_features"]),
                                         int(cfg["deployment"]["servers"]))
            lost += int(np.abs(got.T - want).sum())
        return lost

    def exact_numbers(self) -> Dict[str, int]:
        numbers = super().exact_numbers()
        if self.replica_gap_read is not None:  # never read: judged missing
            numbers["replica_gap"] = self.replica_gap_read
            numbers["owner_gap"] = self.owner_gap_read
            numbers["rows_lost"] = self.rows_lost()
        return numbers
