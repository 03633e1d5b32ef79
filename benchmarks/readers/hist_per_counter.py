"""Time the program's telemetry histograms gathered over the untraced part
of the window, for each unit a counter of the program counted there, in
nanoseconds: the cost of one unit of a lane's own work (a present cell,
where a row's worth of them varies with the missing ones).

how: {"histograms": [names, in microseconds], "counter": name,
      "less": name of a counter of the units that cost no such work}
The units are the rise of `counter` less the rise of `less` (the cells a
lane met, less the empty ones it skipped). A program without the counter,
or one that counted nothing, gives None.
"""


def _rise(ctx, kind, names, field):
    before, after = ctx["telemetry"]

    def total(snap):
        return sum(m[field] for m in snap[kind] if m["name"] in names)
    return total(after) - total(before)


def read(ctx, how):
    units = _rise(ctx, "counters", {how["counter"]}, "value")
    if "less" in how:
        units -= _rise(ctx, "counters", {how["less"]}, "value")
    if units <= 0:
        return None
    total_us = _rise(ctx, "histograms", set(how["histograms"]), "sum")
    return 1e3 * total_us / units
