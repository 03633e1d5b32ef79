"""The whole step's share of the chip's peak over the untraced part of the
window: least time for the work of every step taken, over the wall time of
all of them (waits, turnovers and host time included), per chip.

how: {"work": name in harness/work.py}
"""

from harness import work


def read(ctx, how):
    sec, s = ctx["plain"], ctx["session"]
    if not sec.steps:
        return None
    need = work.FUNCTIONS[how["work"]](
        nnz=sec.nnz, rows=sec.rows, rank=int(s.cfg["fm_rank"]),
        batch_bytes=s.bytes_per_batch * sec.steps)
    least = work.least_seconds(need, ctx["peaks"])["seconds"]
    return 100.0 * least / (sec.seconds * ctx["device"]["count"])
