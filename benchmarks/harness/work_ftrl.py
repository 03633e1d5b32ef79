"""What a step of the factorisation machine under FTRL-proximal (``b``,
``w``) and AdaGrad (``v``) needs, counted from the batch alone, never from
how the step is implemented (``harness/work.py`` counts the SGD step the same
way). Functions are found by name from a metric's file."""

from __future__ import annotations

from typing import Dict

from harness import work
from harness.work import least_seconds  # noqa: F401  (HBM and flops)


def fm_ftrl_step(distinct: float, nnz: float, rows: float, rank: int,
                 batch_bytes: float) -> Dict:
    """One step on a batch with ``nnz`` real nonzeros over ``distinct``
    distinct columns (the batch's own count, padding not counted), factor
    rank ``rank``.

    Bytes: every touched row of the six leaves a column has (``w``, ``z``,
    ``n``: one float32 each; ``v``, ``G``: ``rank`` each), ``2 * rank + 3``
    float32, is read once and written once, and the batch itself is read
    once (``b`` and its two slots are three scalars). Operations: the SGD
    step's margin and gradient (``work.fm_sgd_step``) and the rule's
    elementwise work, about ten operations an element of a touched row. The
    step has no matrix product; bytes bound it."""
    return {"bytes": 2 * distinct * (2 * rank + 3) * 4 + batch_bytes,
            "flops": work.fm_sgd_step(nnz, rows, rank, 0)["flops"]
            + 10 * distinct * (2 * rank + 3)}


FUNCTIONS = {"fm_ftrl_step": fm_ftrl_step}
