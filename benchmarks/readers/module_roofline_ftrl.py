"""A step program's share of its roofline under an update rule with a state
for every row: the least time the chip could take for what the rule's step
needs (``harness/work_ftrl.py``: every touched row of every leaf read and
written once, counted by the batch's own distinct columns), over the device
time of the program's ``XLA Modules`` events in the traced window.
``readers/module_roofline.py`` counts the SGD step, by its entries.

how: {"work": name in harness/work_ftrl.py, "module": substring of the
      module name}

A section without the runner's count of distinct columns
(``runners/fm_ftrl.py``) gives None.
"""

from harness import trace, work_ftrl


def read(ctx, how):
    sec, s = ctx["traced"], ctx["session"]
    mod = trace.module_time(ctx["events"], how["module"])
    distinct = getattr(sec, "distinct", None)
    if not mod["count"] or not sec.steps or not distinct:
        return None
    need = work_ftrl.FUNCTIONS[how["work"]](
        distinct=distinct / sec.steps, nnz=sec.nnz / sec.steps,
        rows=sec.rows / sec.steps, rank=int(s.cfg["fm_rank"]),
        batch_bytes=s.bytes_per_batch)
    least = work_ftrl.least_seconds(need, ctx["peaks"])["seconds"]
    return 100.0 * least / (mod["seconds"] / mod["count"])
