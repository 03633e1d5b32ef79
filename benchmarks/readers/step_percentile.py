"""A percentile of the time between consecutive completed steps, over all
steps of the untraced part of the window, in milliseconds.

how: {"percentile": 95}
"""

import statistics


def read(ctx, how):
    ends = ctx["plain"].step_end
    if len(ends) < 21:
        return None
    gaps = [b - a for a, b in zip(ends, ends[1:])]
    cuts = statistics.quantiles(gaps, n=100, method="inclusive")
    return 1000.0 * cuts[int(how["percentile"]) - 1]
