"""``readers/step_mfu.py`` for an update rule with a state for every row: the
whole step's share of the chip's HBM peak over the untraced part of the
window, the work of every step counted by ``harness/work_ftrl.py`` (the
batch's own distinct columns, six leaves a column).

how: {"work": name in harness/work_ftrl.py}

A section without the runner's count of distinct columns
(``runners/fm_ftrl.py``) gives None.
"""

from harness import work_ftrl


def read(ctx, how):
    sec, s = ctx["plain"], ctx["session"]
    distinct = getattr(sec, "distinct", None)
    if not sec.steps or not distinct:
        return None
    need = work_ftrl.FUNCTIONS[how["work"]](
        distinct=distinct, nnz=sec.nnz, rows=sec.rows,
        rank=int(s.cfg["fm_rank"]),
        batch_bytes=s.bytes_per_batch * sec.steps)
    least = work_ftrl.least_seconds(need, ctx["peaks"])["seconds"]
    return 100.0 * least / (sec.seconds * ctx["device"]["count"])
