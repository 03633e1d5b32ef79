"""Runner of the data-parallel factorisation-machine cells: the session,
loop and window of ``runners/fm.py`` (which already multiplies the batch by
the chips and builds ``data_mesh(chips)``), and one exact number more that a
job of several replicas has to show: ``replica_gap``.

The deployment's guarantees and what holds each (``configs/<name>.json``):
synchronous, by the comparison with the plain reference, which steps the
global batch; replicas bit-identical, by ``replica_gap``; every row once an
epoch, by ``epoch_rows_gap``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from runners import fm
from runners.fm import drive, end_to_end, traced_drive  # noqa: F401


def replica_stats(params, mesh) -> np.ndarray:
    """``[chips, leaves, 3]`` uint32: of every leaf, on each chip's own copy,
    the bits of its float32 sum and sum of squares and the wrapping sum of
    its elements' bits (which moves with any one element). One program over
    the mesh: every chip reduces the buffer it holds, nothing is moved."""
    axis = mesh.axis_names[0]

    def own_copy(p):
        def bits(x):
            return jax.lax.bitcast_convert_type(x, jnp.uint32)
        rows = [jnp.stack([bits(jnp.sum(x)), bits(jnp.sum(x * x)),
                           jnp.sum(bits(x))]) for x in jax.tree.leaves(p)]
        return jnp.stack(rows)[None]
    # the check is the point: the replicas are not taken on trust as equal
    return np.asarray(jax.jit(jax.shard_map(
        own_copy, mesh=mesh, in_specs=P(), out_specs=P(axis),
        check_vma=False))(params))


def replica_gap(stats: np.ndarray) -> int:
    """How many (chip, leaf, statistic) differ in any bit from the first
    chip's."""
    return int(np.count_nonzero(stats != stats[:1]))


class Session(fm.Session):
    """``fm.Session`` that reads, before it drops its state, how far the
    replicas are apart."""

    replica_gap_read = None

    def free(self) -> None:
        # run.py asks for the exact numbers after free(): read the state
        # here, after the window's last step
        if self.params is not None:
            self.replica_gap_read = replica_gap(
                replica_stats(self.params, self.learner.mesh))
        super().free()

    def exact_numbers(self) -> Dict[str, int]:
        numbers = super().exact_numbers()
        if self.replica_gap_read is not None:  # never read: judged missing
            numbers["replica_gap"] = self.replica_gap_read
        return numbers
