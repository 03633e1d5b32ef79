"""The comparison that decides ``correct`` for a training cell.

The program's readings (``Readings``) come from the timed object itself: the
losses of its first steps, the norm of each leaf of the first gradient as the
optimizer got it, worked out from the state after one step
(``(p0 - p1) / learning_rate``), and the norm of each leaf's change after the
last check step. The plain reference follows the same steps on the generator's
rows. Gaps are between norms (not norms of differences), by the worst leaf,
against the reference's norm of that leaf or of the median leaf, whichever is
larger. Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the change.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, NamedTuple, Sequence


class Readings(NamedTuple):
    losses: List[float]
    grad_norms: List[float]     # per leaf, first step
    change_norms: List[float]   # per leaf, after the last check step


def worst_leaf_gap(got: Sequence[float], ref: Sequence[float],
                   counted: Sequence[bool]) -> float:
    floor = statistics.median(ref)
    gaps = [abs(g - r) / max(r, floor)
            for g, r, c in zip(got, ref, counted) if c]
    return max(gaps) if gaps else 0.0


def gaps(program: Readings, reference: Readings) -> Dict[str, float]:
    """The numbers compared, by name."""
    med = statistics.median(reference.grad_norms)
    moved = [g >= 1e-3 * med for g in reference.grad_norms]
    every = [True] * len(reference.grad_norms)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(program.losses, reference.losses)),
        "grad_norm_gap": worst_leaf_gap(program.grad_norms,
                                        reference.grad_norms, every),
        "change_norm_gap": worst_leaf_gap(program.change_norms,
                                          reference.change_norms, moved),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each number beside its limit; ``ok`` only if every number named in
    ``limits`` is there, finite and within it."""
    out = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and value == value and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    for name, value in numbers.items():
        if name not in out:
            out[name] = {"value": value, "limit": None}
    return {"ok": ok, "numbers": out}
