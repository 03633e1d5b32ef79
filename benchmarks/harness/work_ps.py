"""What one worker-and-server of a step on range-sharded tables needs,
counted from the batch alone, never from how the step is built
(``harness/work.py`` counts the one-chip step, ``harness/work_dp.py`` a
replica, the same way). Functions are found by name from a metric's file."""

from __future__ import annotations

from typing import Dict

from harness import work
from harness.work_dp import least_seconds  # noqa: F401  (HBM, links, flops)


def fm_sgd_step_owner(nnz: int, rows: int, rank: int, batch_bytes: int,
                      chips: int) -> Dict:
    """One SGD step of a second-order factorisation machine on a global
    batch with ``nnz`` real nonzeros, seen from one of ``chips`` chips that
    each work a ``1/chips`` share of the rows and own a ``1/chips`` range of
    the tables' rows.

    HBM bytes: its range's touched rows are read once and written once,
    ``rank + 1`` float32 each: with ranges that fill evenly a ``1/chips``
    share of the one-chip step's table bytes (by ``work.py``'s convention
    every entry's row counts); and it reads its share of the batch. Link
    bytes: the rows it pulls and the gradients it pushes that another chip
    owns, ``2 * (chips - 1) / chips`` of its shard's ``nnz / chips *
    (rank + 1) * 4``. Operations: its share of the one-chip step's."""
    one = work.fm_sgd_step(nnz, rows, rank, 0)
    return {"bytes": (one["bytes"] + batch_bytes) / chips,
            "link_bytes": 2 * (chips - 1) / chips
            * (nnz / chips) * (rank + 1) * 4,
            "flops": one["flops"] / chips}


FUNCTIONS = {"fm_sgd_step_owner": fm_sgd_step_owner}
