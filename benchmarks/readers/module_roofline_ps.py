"""A chip's share of its roofline in a step on range-sharded tables: the
least time one chip could take for what one *worker-and-server* needs in a
step (HBM bytes of its range and its share of the batch, bytes over its links
for the rows it pulls and pushes, operations: ``harness/work_ps.py``), over
the device time of the step program's ``XLA Modules`` events on the first
chip in the traced window. ``readers/module_roofline_dp.py`` charges a
replica the whole update: it is the replicated mesh's.

how: {"work": name in harness/work_ps.py, "module": substring of the module
      name}
"""

from harness import trace, work_ps
from harness.links import links_for


def read(ctx, how):
    sec, s = ctx["traced"], ctx["session"]
    mod = trace.module_time(ctx["events"], how["module"])
    if not mod["count"] or not sec.steps:
        return None
    need = work_ps.FUNCTIONS[how["work"]](
        nnz=sec.nnz / sec.steps, rows=sec.rows / sec.steps,
        rank=int(s.cfg["fm_rank"]), batch_bytes=s.bytes_per_batch,
        chips=ctx["device"]["count"])
    least = work_ps.least_seconds(need, ctx["peaks"],
                                  links_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / (mod["seconds"] / mod["count"])
