"""Share of the traced window in which no operation ran on the first chip
AND a host thread was inside one of the program's spans the metric file
lists: what the chip lost to that cause, as a part of ``device_idle_share``.
Both sides are on the profiler's clock: the program's opened spans are
``dmlc.<name>`` annotations in the trace's host plane.

how: {"spans": [annotation names, e.g. "dmlc.device.wait"]}

The listed spans are opened by the consuming thread only, so the union over
the host plane's lines is that thread's. A trace of a program without such
annotations gives None.
"""

from readers import _xplane


def reduce(doc, how):
    lo, hi = doc["window"]
    names = set(how["spans"])
    spans = _xplane.clipped_union(
        [e for evs in doc["host"].values() for e in evs if e[0] in names],
        lo, hi)
    if not spans:
        return None
    return 100.0 * _xplane.overlap(_xplane.idle_gaps(doc), spans) / (hi - lo)


def read(ctx, how):
    return reduce(_xplane.of_run(ctx), how)
