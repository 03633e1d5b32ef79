"""The owner rule of range-sharded tables, stated plainly in numpy: which
server owns a row, and how a worker slices its sorted key list by the servers'
ranges. It imports nothing of the program; the benchmark's exact checks and
the tier-1 tests count against it.

The rule is ps-lite's as wormhole's ``linear`` and ``difacto`` use it (from
memory: no network here): the key space is divided evenly into as many
contiguous ranges as there are servers, server ``o`` holds the rows of range
``o``, and a worker, whose keys for a batch are sorted and distinct, cuts the
list where the ranges end: the keys of one server are one contiguous stretch.
Here the key space is the hashed id space ``[0, num_features)``.
"""

from __future__ import annotations

from typing import List

import numpy as np

PAD = 2 ** 31 - 1  # beyond any table: what a stretch's tail repeats


def owner_rows(num_features: int, owners: int) -> int:
    """Rows a server holds: the ranges are equal, so the table divides."""
    if num_features % owners:
        raise ValueError(f"{num_features} rows do not divide among {owners} "
                         f"owners")
    return num_features // owners


def owner_of(ids: np.ndarray, num_features: int, owners: int) -> np.ndarray:
    """The server that holds each row."""
    return np.asarray(ids) // owner_rows(num_features, owners)


def slice_by_ranges(sorted_ids: np.ndarray, num_features: int,
                    owners: int) -> List[np.ndarray]:
    """A worker's sorted, distinct keys cut into one stretch a server."""
    ids = np.asarray(sorted_ids)
    if ids.size and (np.any(np.diff(ids) <= 0) or ids[0] < 0
                     or ids[-1] >= num_features):
        raise ValueError("the keys are not sorted, distinct and in the "
                         "table")
    rows = owner_rows(num_features, owners)
    return [ids[(ids >= o * rows) & (ids < (o + 1) * rows)]
            for o in range(owners)]


def stretch_counts(shard_ids: List[np.ndarray], num_features: int,
                   owners: int) -> np.ndarray:
    """``[shards, owners]``: how many distinct keys each worker asks of each
    server, from the workers' raw keys (any order, repeats and all)."""
    return np.array([[len(s) for s in slice_by_ranges(
        np.unique(ids), num_features, owners)] for ids in shard_ids])


def owner_major(sorted_ids: np.ndarray, num_features: int, owners: int,
                capacity: int) -> np.ndarray:
    """The list as it travels, ``[owners * capacity]``: stretch after
    stretch, each padded to ``capacity`` by ``PAD``; a key beyond a stretch's
    capacity is an error, never dropped."""
    out = np.full((owners, capacity), PAD, np.int64)
    for o, stretch in enumerate(slice_by_ranges(sorted_ids, num_features,
                                                owners)):
        if len(stretch) > capacity:
            raise ValueError(f"a stretch of {len(stretch)} keys does not fit "
                             f"{capacity}")
        out[o, :len(stretch)] = stretch
    return out.reshape(-1)
