"""What one replica of a synchronous data-parallel step needs, counted from
the batch alone, never from how the step is built (``harness/work.py`` counts
the one-chip step the same way). Functions are found by name from a metric's
file."""

from __future__ import annotations

from typing import Dict

from harness import work


def fm_sgd_step_replica(nnz: int, rows: int, rank: int, batch_bytes: int,
                        chips: int) -> Dict:
    """One SGD step of a second-order factorisation machine on a global
    batch with ``nnz`` real nonzeros, seen from one of ``chips`` replicas
    that each hold the whole tables and a ``1/chips`` share of the rows.

    HBM bytes: every replica applies the whole update, so it reads once and
    writes once every touched (w, v) row of the global batch, ``rank + 1``
    float32 each, and reads its own share of the batch. Link bytes: it takes
    in the row gradients of the other shards, ``(chips - 1) / chips`` of
    ``nnz * (rank + 1) * 4``. Operations: its share of the one-chip step's."""
    one = work.fm_sgd_step(nnz, rows, rank, 0)
    return {"bytes": one["bytes"] + batch_bytes / chips,
            "link_bytes": (chips - 1) / chips * nnz * (rank + 1) * 4,
            "flops": one["flops"] / chips}


def least_seconds(need: Dict, peaks: Dict, links: Dict) -> Dict:
    """The least time a replica could take for ``need``: the largest of HBM
    bytes, link bytes and operations each over its published peak, and which
    of them sets it."""
    by = {"bytes": need["bytes"] / peaks["hbm_bytes_per_s"],
          "link_bytes": need["link_bytes"] / links["ici_bytes_per_s"],
          "flops": need["flops"] / peaks["flops_bf16"]}
    bound = max(by, key=by.get)
    return {"seconds": by[bound], "bound": bound, "by": by}


FUNCTIONS = {"fm_sgd_step_replica": fm_sgd_step_replica}
