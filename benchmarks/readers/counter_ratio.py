"""The rise of one of the program's telemetry counters over the rise of
another across the untraced part of the window, as a percentage.

how: {"counters": [over, under],
      "per_chip": true   (optional) the under counter is shared out over the
                         cell's chips first: over / (under / chips),
      "less_one": true   (optional) the excess over 1: 100 * (ratio - 1)}
A program without either counter, or one that counted nothing, gives None.
"""


def _rise(ctx, name):
    before, after = ctx["telemetry"]

    def total(snap):
        return sum(m["value"] for m in snap["counters"] if m["name"] == name)
    return total(after) - total(before)


def read(ctx, how):
    over, under = (_rise(ctx, name) for name in how["counters"])
    if over <= 0 or under <= 0:
        return None
    if how.get("per_chip"):
        under /= ctx["device"]["count"]
    ratio = over / under
    return 100.0 * (ratio - 1.0 if how.get("less_one") else ratio)
