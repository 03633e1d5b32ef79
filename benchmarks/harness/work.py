"""What the algorithm needs, counted from the batch alone, never from how
the step is implemented. Functions are found by name from a metric's file."""

from __future__ import annotations

from typing import Dict


def fm_sgd_step(nnz: int, rows: int, rank: int, batch_bytes: int) -> Dict:
    """One SGD step of a second-order factorisation machine on a batch with
    ``nnz`` real nonzeros (padding not counted) and factor rank ``rank``.

    Bytes: every touched (w, v) row, ``rank + 1`` float32, is read once and
    written once, and the batch itself is read once. Operations: the margin
    by Rendle's identity and its gradient, about ``8 * nnz * rank`` (multiply
    and add for s1 and s2, then the same again backwards), plus the row-wise
    logistic terms. The step has no matrix product; bytes bound it."""
    table = 2 * nnz * (rank + 1) * 4
    return {"bytes": table + batch_bytes,
            "flops": 8 * nnz * rank + 4 * nnz + 12 * rows}


def least_seconds(work: Dict, peaks: Dict) -> Dict:
    """The least time the chip could take for ``work`` and which peak sets
    it."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = work["flops"] / peaks["flops_bf16"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "bytes" if by_bytes >= by_flops else "flops"}


FUNCTIONS = {"fm_sgd_step": fm_sgd_step}
