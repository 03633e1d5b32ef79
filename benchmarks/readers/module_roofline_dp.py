"""A replica's share of its roofline in a data-parallel step: the least time
one chip could take for what a *replica* needs in a step (HBM bytes, bytes
over its links, operations: ``harness/work_dp.py``), over the device time of
the step program's ``XLA Modules`` events on the first chip in the traced
window. ``readers/module_roofline.py`` charges one chip the whole batch and
knows no link: it is the one-chip cells'.

how: {"work": name in harness/work_dp.py, "module": substring of the module
      name}
"""

from harness import trace, work_dp
from harness.links import links_for


def read(ctx, how):
    sec, s = ctx["traced"], ctx["session"]
    mod = trace.module_time(ctx["events"], how["module"])
    if not mod["count"] or not sec.steps:
        return None
    need = work_dp.FUNCTIONS[how["work"]](
        nnz=sec.nnz / sec.steps, rows=sec.rows / sec.steps,
        rank=int(s.cfg["fm_rank"]), batch_bytes=s.bytes_per_batch,
        chips=ctx["device"]["count"])
    least = work_dp.least_seconds(need, ctx["peaks"],
                                  links_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / (mod["seconds"] / mod["count"])
