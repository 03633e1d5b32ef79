"""Sum of the program's telemetry histograms over the untraced part of the
window, per batch, per thousand rows, or as a share of the window.

how: {"histograms": [names], "per": "batch" | "krow" | "window_pct"}
``batch`` divides by the histograms' own observation count.
"""


def _delta(ctx, names, field):
    before, after = ctx["telemetry"]

    def total(snap):
        return sum(h[field] for h in snap["histograms"] if h["name"] in names)
    return total(after) - total(before)


def read(ctx, how):
    names = set(how["histograms"])
    count = _delta(ctx, names, "count")
    if count <= 0:
        return None
    total_us = _delta(ctx, names, "sum")
    sec = ctx["plain"]
    if how["per"] == "batch":
        return total_us / count
    if how["per"] == "krow":
        return total_us / (sec.rows / 1000.0) if sec.rows else None
    if how["per"] == "window_pct":
        return 100.0 * total_us / (sec.seconds * 1e6)
    raise ValueError(f"unknown per {how['per']!r}")
