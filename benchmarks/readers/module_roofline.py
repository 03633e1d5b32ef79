"""A jitted program's share of its roofline: the least time the chip could
take for the work the algorithm needs in a step, over the device time of the
program's ``XLA Modules`` events in the traced window.

how: {"work": name in harness/work.py, "module": substring of the module name}
"""

from harness import trace, work


def read(ctx, how):
    sec, s = ctx["traced"], ctx["session"]
    mod = trace.module_time(ctx["events"], how["module"])
    if not mod["count"] or not sec.steps:
        return None
    need = work.FUNCTIONS[how["work"]](
        nnz=sec.nnz / sec.steps, rows=sec.rows / sec.steps,
        rank=int(s.cfg["fm_rank"]), batch_bytes=s.bytes_per_batch)
    least = work.least_seconds(need, ctx["peaks"])["seconds"]
    return 100.0 * least / (mod["seconds"] / mod["count"])
